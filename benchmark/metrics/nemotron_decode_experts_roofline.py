"""The Nemotron-H decode step's expert layers against their roofline:
the least time to read what they must read a step
(``flops_nemotron_h.py`` ``decode_experts_bytes``: the experts some row
chose, the mean of ``engine.decode``'s ``moe_experts_touched`` over the
window's decode steps, and each layer's router, latent pair and shared
expert) over the self time a ``jit__decode`` run spends under
``moe_router``, ``moe_latent_down``, ``moe_experts``, ``moe_latent_up``
and ``moe_shared`` (``scope_time.py``, mean over the traced runs): the
share of the grouped ``grouped_relu2_up`` / down kernels and the GEMMs
around them.  The rows' activations are a few MB and are left out."""
import flops_nemotron_h as flops
from metrics import phase_ring, scope_time

EXPERTS = (("experts", frozenset({"moe_router", "moe_latent_down",
                                  "moe_experts", "moe_latent_up",
                                  "moe_shared"})),)


def read(result, ctx):
    if "hybrid_override_pattern" not in ctx.config \
            or "state_slots" not in result.counters:
        return None
    touched = phase_ring.mean(
        d.attrs["moe_experts_touched"]
        for _, inside in phase_ring.steps(result)
        for d in inside.get("engine.decode", ())
        if "moe_experts_touched" in d.attrs)
    ms = scope_time.read_group(result, ctx, "decode", EXPERTS, "experts")
    if touched is None or not ms:
        return None
    m = flops.model_shape(flops.model_of(ctx.config))
    least = flops.decode_experts_bytes(m, touched) / ctx.config["chips"] \
        / ctx.peak["hbm_bytes_per_s"]
    return 100.0 * least / (ms / 1e3)
