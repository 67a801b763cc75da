"""The DeepSeek-V2 chunk executable's share of the chip's bf16 peak
while it runs: forward FLOPs of the REAL tokens whose prompts ended in
the traced part (``flops_deepseek_v2.py``: from the shared count on,
over the whole context; padding is not work, and a chunk's row carries
64-512 real tokens, so it reads low) / device time of the chunk (and
whole-row) executables' runs there / chips / peak."""
import flops_deepseek_v2 as flops
import trace_reduce


def read(result, ctx):
    names = ctx.config["executables"]
    runs = [r for key in ("prefill", "chunk") if key in names
            for r in trace_reduce.runs_between(
                result.trace, names[key], result.trace_window_ns)]
    traced = result.counters.get("traced", {})
    if not runs or not traced.get("prompt_lens") \
            or "prompt_shared" not in traced:
        return None
    m = flops.model_shape(flops.model_of(ctx.config))
    work = sum(flops.prefill_flops(m, p, s) for p, s in zip(
        traced["prompt_lens"], traced["prompt_shared"]))
    seconds = sum(dur for _, _, dur in runs) / 1e9
    return 100.0 * work / seconds / ctx.config["chips"] \
        / ctx.peak["bf16_flops_per_s"]
