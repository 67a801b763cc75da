"""As ``host_turn_ms_per_step``, in the backlog cell: over the window's
engine steps that decoded and prefilled nothing, mean of ``engine.step``
less ``decode.fetch``."""
from metrics import phase_ring


def read(result, ctx):
    return phase_ring.host_turn_ms_per_step(result)
