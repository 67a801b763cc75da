"""The Nemotron-H serving model's share of the chip's bf16 peak over the
whole window, the share of the whole step: forward FLOPs of every real
prompt token prefilled and of every token decoded
(``flops_nemotron_h.py``: the GEMMs, the routed pairs held here, the
recurrence, attention's pairs) / window / chips / peak.  It reads low: a
decode step is bound by the bytes of the experts it touches and of the
recurrent state."""
import flops_nemotron_h as flops


def read(result, ctx):
    c = result.counters
    if "hybrid_override_pattern" not in ctx.config or "state_slots" not in c:
        return None
    m = flops.model_shape(flops.model_of(ctx.config))
    work = sum(flops.prefill_flops(m, p) for p in c["prompt_lens"]) \
        + sum(flops.decode_flops(m, k) for k in c["decode_kv_lens"])
    if not work:
        return None
    return 100.0 * work / result.window_s / ctx.config["chips"] \
        / ctx.peak["bf16_flops_per_s"]
