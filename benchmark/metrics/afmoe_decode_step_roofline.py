"""The afmoe decode executable's share of its roofline: least time to
read what the traced decode steps must read (``flops_afmoe.py``: every
weight a step touches once, the held experts at the share its rows
choose, and each row's K and V under its layers' windows;
bandwidth-bound at one query a row) over the device time of those
runs."""
import flops_afmoe
import trace_reduce


def read(result, ctx):
    runs = trace_reduce.runs_between(
        result.trace, ctx.config["executables"]["decode"],
        result.trace_window_ns)
    traced = result.counters["traced"]
    if not runs or not traced["decode_kv_lens"]:
        return None
    m = flops_afmoe.model_shape(flops_afmoe.model_of(ctx.config))
    nbytes = flops_afmoe.decode_steps_bytes(
        m, len(runs), traced["decode_kv_lens"], itemsize=2)
    least = nbytes / ctx.config["chips"] / ctx.peak["hbm_bytes_per_s"]
    return 100.0 * least / (sum(dur for _, _, dur in runs) / 1e9)
