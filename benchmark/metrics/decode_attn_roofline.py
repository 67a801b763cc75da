"""The paged decode attention kernel's share of its roofline: least
time to read the K and V of every traced decode row's context once, in
every layer (``flops.py``; bandwidth-bound at one query per row) over
the trace time of the Pallas calls inside the decode executable."""
import flops
import trace_reduce


def read(result, ctx):
    traced = result.counters["traced"]
    if not traced["decode_kv_lens"]:
        return None
    t0, t1 = result.trace_window_ns
    dev = result.trace.devices[0]
    calls = [x for run in trace_reduce.ops_within(
        dev, ctx.config["executables"]["decode"], trace_reduce.is_pallas)
        for x in run if x[1] >= t0 - 2e6 and x[1] + x[2] <= t1 + 2e6]
    if not calls:
        return None
    m = flops.model_shape(ctx.config["model"])
    nbytes = m.layers * flops.decode_attention_bytes(
        m, traced["decode_kv_lens"], itemsize=2) / ctx.config["chips"]
    least = nbytes / ctx.peak["hbm_bytes_per_s"]
    return 100.0 * least / (sum(dur for _, _, dur in calls) / 1e9)
