"""The host's turn between two decode steps, from the program's own
phases: over the window's engine steps that decoded and prefilled
nothing, mean of ``engine.step`` less ``decode.fetch`` (the wait for
the device).  Unlike ``host_gap_ms_per_step`` it holds no launch
latency and is read over the whole window, not its traced part."""
from metrics import phase_ring


def read(result, ctx):
    return phase_ring.host_turn_ms_per_step(result)
