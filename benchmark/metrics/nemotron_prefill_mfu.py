"""The Nemotron-H prefill executables' share of the chip's bf16 peak
while they run: forward FLOPs of the REAL tokens whose prompts ended in
the traced part (``flops_nemotron_h.py``; the padding of a row is not
work, and the chunked scan's quadratic part inside a block is the
program's way, not the algorithm's least) / device time of the
whole-row and chunk executables' runs there / chips / peak."""
import flops_nemotron_h as flops
import trace_reduce


def read(result, ctx):
    traced = result.counters.get("traced", {})
    if "hybrid_override_pattern" not in ctx.config \
            or not traced.get("prompt_lens") \
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
