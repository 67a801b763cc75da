"""What the two page lifetimes save: 1 - (pages held, a layer's worth
each, in the full layers' pool and in the window layers') / (what one
lifetime for every layer would hold for the same running requests),
mean over the window's engine steps that had requests running
(``engine.step``'s ``held_full``, ``held_window``, ``held_uniform``)."""
from metrics import phase_ring


def read(result, ctx):
    kinds = ctx.config["layer_types"]
    n_window = sum(k == "sliding_attention" for k in kinds)
    n_full = len(kinds) - n_window
    return phase_ring.mean(
        100.0 * (1.0 - (n_full * a["held_full"] + n_window * a["held_window"])
                 / (len(kinds) * a["held_uniform"]))
        for a in (step.attrs for step, _ in phase_ring.steps(result))
        if a and a.get("held_uniform"))
