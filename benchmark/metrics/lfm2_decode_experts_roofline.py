"""The LFM2-MoE decode step's FFNs against their roofline: the least
time to read what they must read a step (``flops_lfm2_moe.py``
``decode_ffn_bytes``: the experts some row chose, the mean of
``engine.decode``'s ``moe_experts_touched`` over the window's decode
steps, the routers and the two dense layers' MLPs) over the self time a
``jit__decode`` run spends under ``moe_router``, ``moe_experts`` and
``mlp`` (``scope_time.py``, mean over the traced runs).  The rows'
activations are a few hundred KB and are left out."""
import flops_lfm2_moe as flops
from metrics import phase_ring, scope_time

FFN = (("ffn", frozenset({"moe_router", "moe_experts", "mlp"})),)


def read(result, ctx):
    if "state_slots" not in result.counters:
        return None
    touched = phase_ring.mean(
        d.attrs["moe_experts_touched"]
        for _, inside in phase_ring.steps(result)
        for d in inside.get("engine.decode", ())
        if "moe_experts_touched" in d.attrs)
    ms = scope_time.read_group(result, ctx, "decode", FFN, "ffn")
    if touched is None or not ms:
        return None
    m = flops.model_shape(flops.model_of(ctx.config))
    least = flops.decode_ffn_bytes(m, touched) / ctx.config["chips"] \
        / ctx.peak["hbm_bytes_per_s"]
    return 100.0 * least / (ms / 1e3)
