"""The Granite-hybrid serving model's share of the chip's bf16 peak
over the whole window, the share of the whole step: forward FLOPs of
every real prompt token prefilled and of every token decoded
(``flops_granite_hybrid.py``: the GEMMs, the recurrence at the
equations' own count, attention's pairs) / window / chips / peak.  It
reads low: the step is bound by the bytes of the weights and of the
recurrent state, not by arithmetic."""
import flops_granite_hybrid as flops


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
