"""The LFM2-MoE prefill executables' share of the chip's bf16 peak
while they run: forward FLOPs of the REAL tokens whose prompts ended in
the traced part (``flops_lfm2_moe.py``; the padding of a row is not
work) / device time of the whole-row and chunk executables' runs there
/ chips / peak."""
import flops_lfm2_moe as flops
import trace_reduce


def read(result, ctx):
    traced = result.counters.get("traced", {})
    if not traced.get("prompt_lens") \
            or "state_slots" not in result.counters:
        return None
    names = ctx.config["executables"]
    runs = [r for key in ("prefill", "chunk") if key in names
            for r in trace_reduce.runs_between(
                result.trace, names[key], result.trace_window_ns)]
    if not runs:
        return None
    m = flops.model_shape(flops.model_of(ctx.config))
    work = sum(flops.prefill_flops(m, p) for p in traced["prompt_lens"])
    seconds = sum(dur for _, _, dur in runs) / 1e9
    return 100.0 * work / seconds / ctx.config["chips"] \
        / ctx.peak["bf16_flops_per_s"]
