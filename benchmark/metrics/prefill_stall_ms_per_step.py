"""Sum of the window's ``engine.prefill`` durations over its decode
steps: how long, on average, a step's running rows wait for prefills."""
from metrics import phase_ring


def read(result, ctx):
    return phase_ring.prefill_stall_ms_per_step(result)
