"""Mean ``decode.build`` per decode step of the window: page
validation, the numpy rebuild of the step's inputs and the page
table."""
from metrics import phase_ring


def read(result, ctx):
    return phase_ring.mean(
        phase_ring.ms(b) for _, inside in phase_ring.steps(result)
        for b in inside.get("decode.build", ()))
