"""The DeepSeek-V2 serving model's share of the chip's bf16 peak over
the whole window, the share of the whole step: forward FLOPs of what
the window COMPUTED (``flops_deepseek_v2.py``: a prompt's tokens from
its shared count on, attending over its whole context, tokens that rode
in on shared pages being no work; every decoded token, absorbed over
its latent cache; routed pairs at their expectation) / window / chips /
peak."""
import flops_deepseek_v2 as flops


def read(result, ctx):
    c = result.counters
    if "prompt_shared" not in c:
        return None
    m = flops.model_shape(flops.model_of(ctx.config))
    work = sum(flops.prefill_flops(m, p, s)
               for p, s in zip(c["prompt_lens"], c["prompt_shared"])) \
        + sum(flops.decode_flops(m, k) for k in c["decode_kv_lens"])
    if not work:
        return None
    return 100.0 * work / result.window_s / ctx.config["chips"] \
        / ctx.peak["bf16_flops_per_s"]
