"""99th percentile over every gap between consecutive tokens of one
request whose later token fell in the window: the tail a user reading a
stream feels, which ``tpot_p95_ms`` (a mean per request) averages away.
Token times are rebuilt from the engine's phases; the earlier token of
a gap may lie before the window."""
import harness
from metrics import phase_ring


def read(result, ctx):
    t0, t1 = phase_ring.window_ns(result)
    gaps = [(b - a) / 1e6
            for times in phase_ring.token_times(phase_ring.ring()).values()
            for a, b in zip(times, times[1:]) if t0 <= b <= t1]
    return harness.percentile(gaps, 99) if gaps else None
