"""As ``moe_load_max_over_mean``, with this configuration's keys: the
most (token, expert) pairs any held expert took in any layer of a
decode step (``engine.decode``'s ``moe_load_max``) over the mean a held
expert took (``moe_pairs_held`` / expert layers, the ``E`` of
``hybrid_override_pattern`` / experts held), mean over the window's
decode steps."""
from metrics import phase_ring


def read(result, ctx):
    if "hybrid_override_pattern" not in ctx.config:
        return None
    lo, hi = ctx.config["model"]["experts_held"]
    slots = ctx.config["hybrid_override_pattern"].count("E") * (hi - lo)
    return phase_ring.mean(
        d.attrs["moe_load_max"] * slots / d.attrs["moe_pairs_held"]
        for _, inside in phase_ring.steps(result)
        for d in inside.get("engine.decode", ())
        if d.attrs.get("moe_pairs_held"))
