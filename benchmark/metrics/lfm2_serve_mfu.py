"""The LFM2-MoE serving model's share of the chip's bf16 peak over the
whole window, the share of the whole step: forward FLOPs of every real
prompt token prefilled and of every token decoded
(``flops_lfm2_moe.py``: the GEMMs, every routed pair, the convolution's
gates and taps, attention's pairs) / window / chips / peak.  It reads
low: a decode step is bound by the bytes of the experts it touches."""
import flops_lfm2_moe as flops


def read(result, ctx):
    c = result.counters
    if "state_slots" not in c:
        return None
    m = flops.model_shape(flops.model_of(ctx.config))
    work = sum(flops.prefill_flops(m, p) for p in c["prompt_lens"]) \
        + sum(flops.decode_flops(m, k) for k in c["decode_kv_lens"])
    if not work:
        return None
    return 100.0 * work / result.window_s / ctx.config["chips"] \
        / ctx.peak["bf16_flops_per_s"]
