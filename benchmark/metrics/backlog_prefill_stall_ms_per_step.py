"""As ``prefill_stall_ms_per_step``, in the backlog cell: sum of the
window's ``engine.prefill`` durations over its decode steps."""
from metrics import phase_ring


def read(result, ctx):
    return phase_ring.prefill_stall_ms_per_step(result)
