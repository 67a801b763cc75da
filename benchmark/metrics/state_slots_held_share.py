"""Slots of recurrent state held, as a share of those a request can
take (``state_slots`` less the scratch slot), mean over the window's
engine steps that decoded (``engine.step``'s ``state_slots_held`` and
``state_slots``): how full the state pool, which is what runs out first
at short contexts, is kept."""
from metrics import phase_ring


def read(result, ctx):
    return phase_ring.mean(
        100.0 * step.attrs["state_slots_held"]
        / (step.attrs["state_slots"] - 1)
        for step, inside in phase_ring.steps(result)
        if "engine.decode" in inside and "state_slots" in step.attrs)
