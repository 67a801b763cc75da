"""The Nemotron-H decode executable's share of its roofline: the larger
of the least time to move what the traced decode steps must move
(``flops_nemotron_h.py``: every weight once a step but the experts no
row chose, the experts a step touched being the mean of
``engine.decode``'s ``moe_experts_touched`` over the window's decode
steps, and of the embedding the rows looked up; every real row's
float32 state and tail in and out, a Mamba layer; K and V of every
cached token once) and the least time to compute them, over the device
time of those runs.  One bound for the whole step, so that parts which
overlap cannot read over 100%.  The bytes counted go into the run's
counters (``nemotron_decode_step_bytes``)."""
import flops_nemotron_h as flops
import trace_reduce
from metrics import phase_ring


def read(result, ctx):
    traced = result.counters.get("traced", {})
    kv_lens = traced.get("decode_kv_lens")
    if "hybrid_override_pattern" not in ctx.config or not kv_lens \
            or "state_slots" not in result.counters:
        return None
    touched = phase_ring.mean(
        d.attrs["moe_experts_touched"]
        for _, inside in phase_ring.steps(result)
        for d in inside.get("engine.decode", ())
        if "moe_experts_touched" in d.attrs)
    runs = trace_reduce.runs_between(
        result.trace, ctx.config["executables"]["decode"],
        result.trace_window_ns)
    if touched is None or not runs:
        return None
    m = flops.model_shape(flops.model_of(ctx.config))
    chips = ctx.config["chips"]
    nbytes = flops.decode_steps_bytes(m, len(runs), touched, len(kv_lens),
                                      sum(kv_lens))
    result.counters["nemotron_decode_step_bytes"] = nbytes
    work = sum(flops.decode_flops(m, k) for k in kv_lens)
    least = max(nbytes / chips / ctx.peak["hbm_bytes_per_s"],
                work / chips / ctx.peak["bf16_flops_per_s"])
    return 100.0 * least / (sum(dur for _, _, dur in runs) / 1e9)
