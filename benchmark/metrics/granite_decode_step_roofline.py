"""The Granite-hybrid decode executable's share of its roofline: the
larger of the least time to move what the traced decode steps must move
(``flops_granite_hybrid.py``: every weight once a step; for every real
row, a state-space layer, its float32 state and its tail in and out;
K and V of every cached token once) and the least time to compute them,
over the device time of those runs.  One bound for the whole step, so
that parts which overlap cannot read over 100%.  The bytes counted go
into the run's counters (``granite_decode_step_bytes``)."""
import flops_granite_hybrid as flops
import trace_reduce


def read(result, ctx):
    traced = result.counters.get("traced", {})
    kv_lens = traced.get("decode_kv_lens")
    if not kv_lens or "state_slots" not in result.counters:
        return None
    runs = trace_reduce.runs_between(
        result.trace, ctx.config["executables"]["decode"],
        result.trace_window_ns)
    if not runs:
        return None
    m = flops.model_shape(flops.model_of(ctx.config))
    chips = ctx.config["chips"]
    nbytes = flops.decode_steps_bytes(m, len(runs), len(kv_lens),
                                      sum(kv_lens))
    result.counters["granite_decode_step_bytes"] = nbytes
    work = sum(flops.decode_flops(m, k) for k in kv_lens)
    least = max(nbytes / chips / ctx.peak["hbm_bytes_per_s"],
                work / chips / ctx.peak["bf16_flops_per_s"])
    return 100.0 * least / (sum(dur for _, _, dur in runs) / 1e9)
