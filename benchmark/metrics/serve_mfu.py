"""The serving model's share of the chip's bf16 peak over the whole
window: forward FLOPs of every real prompt token prefilled (the padding
of the row is not work) and of every token decoded (``flops.py``) /
window / chips / peak."""
import flops


def read(result, ctx):
    c = result.counters
    m = flops.model_shape(ctx.config["model"])
    work = sum(flops.prefill_flops(m, p) for p in c["prompt_lens"]) \
        + sum(flops.decode_flops(m, k) for k in c["decode_kv_lens"])
    if not work:
        return None
    return 100.0 * work / result.window_s / ctx.config["chips"] \
        / ctx.peak["bf16_flops_per_s"]
