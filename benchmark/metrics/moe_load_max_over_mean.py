"""How unevenly a decode step's rows load the held experts: the most
(token, expert) pairs any held expert took in any layer
(``engine.decode``'s ``moe_load_max``) over the mean a held expert took
(``moe_pairs_held`` / expert layers / experts held), mean over the
window's decode steps.  1 would be even; the grouped product's time
follows the fullest group's tiles."""
from metrics import phase_ring


def read(result, ctx):
    expert_layers = ctx.config["num_hidden_layers"] \
        - ctx.config["num_dense_layers"]
    slots = expert_layers * ctx.config["num_experts"]
    return phase_ring.mean(
        d.attrs["moe_load_max"] * slots / d.attrs["moe_pairs_held"]
        for _, inside in phase_ring.steps(result)
        for d in inside.get("engine.decode", ())
        if d.attrs.get("moe_pairs_held"))
