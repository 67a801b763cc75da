"""The afmoe serving model's share of the chip's bf16 peak over the
whole window, the share of the whole step: forward FLOPs of every real
prompt token prefilled and of every token decoded (``flops_afmoe.py``:
routed pairs at their expectation, pairs under the windows) / window /
chips / peak."""
import flops_afmoe


def read(result, ctx):
    c = result.counters
    m = flops_afmoe.model_shape(flops_afmoe.model_of(ctx.config))
    work = sum(flops_afmoe.prefill_flops(m, p) for p in c["prompt_lens"]) \
        + sum(flops_afmoe.decode_flops(m, k) for k in c["decode_kv_lens"])
    if not work:
        return None
    return 100.0 * work / result.window_s / ctx.config["chips"] \
        / ctx.peak["bf16_flops_per_s"]
