"""As ``moe_load_max_over_mean``, with this configuration's keys: the
most (token, expert) pairs any held expert took in any layer of a
decode step (``engine.decode``'s ``moe_load_max``) over the mean a held
expert took (``moe_pairs_held`` / expert layers / experts held), mean
over the window's decode steps."""
from metrics import phase_ring


def read(result, ctx):
    if "n_routed_experts" not in ctx.config:
        return None
    expert_layers = ctx.config["num_hidden_layers"] \
        - ctx.config["first_k_dense_replace"]
    slots = expert_layers * ctx.config["n_routed_experts"]
    return phase_ring.mean(
        d.attrs["moe_load_max"] * slots / d.attrs["moe_pairs_held"]
        for _, inside in phase_ring.steps(result)
        for d in inside.get("engine.decode", ())
        if d.attrs.get("moe_pairs_held"))
