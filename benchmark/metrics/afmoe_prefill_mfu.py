"""The afmoe prefill executables' share of the chip's bf16 peak while
they run: forward FLOPs of the REAL prompt tokens whose prefill ended
in the traced part (``flops_afmoe.py``; padding is not work) / device
time of the whole-row and the chunk executables' runs there / chips /
peak.  A long prompt's earlier chunks may lie before the traced part
and a prompt still in its chunks at the close is not counted: over the
traced seconds the two even out, in one run they need not."""
import flops_afmoe
import trace_reduce


def read(result, ctx):
    names = ctx.config["executables"]
    runs = [r for key in ("prefill", "chunk") if key in names
            for r in trace_reduce.runs_between(
                result.trace, names[key], result.trace_window_ns)]
    traced = result.counters["traced"]
    if not runs or not traced["prompt_lens"]:
        return None
    m = flops_afmoe.model_shape(flops_afmoe.model_of(ctx.config))
    work = sum(flops_afmoe.prefill_flops(m, p) for p in traced["prompt_lens"])
    seconds = sum(dur for _, _, dur in runs) / 1e9
    return 100.0 * work / seconds / ctx.config["chips"] \
        / ctx.peak["bf16_flops_per_s"]
