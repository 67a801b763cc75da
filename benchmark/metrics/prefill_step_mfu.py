"""The prefill executable's share of the chip's bf16 peak while it
runs: forward FLOPs of the REAL prompt tokens the traced prefills
carried (``flops.py``; the padding of the fixed row is not work, so a
narrower row shows here) / device time of those runs / chips / peak."""
import flops
import trace_reduce


def read(result, ctx):
    runs = trace_reduce.runs_between(
        result.trace, ctx.config["executables"]["prefill"],
        result.trace_window_ns)
    traced = result.counters["traced"]
    if not runs or not traced["prompt_lens"]:
        return None
    m = flops.model_shape(ctx.config["model"])
    work = sum(flops.prefill_flops(m, p) for p in traced["prompt_lens"])
    seconds = sum(dur for _, _, dur in runs) / 1e9
    return 100.0 * work / seconds / ctx.config["chips"] \
        / ctx.peak["bf16_flops_per_s"]
