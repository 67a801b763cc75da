"""95th percentile, over every request due in the window, of first
token minus due time (the engine's clock); a request that got no token
counts as the worst.  Recorded, not judged: over the hundred requests
of a window it spreads by 5-8% from run to run of one schedule, because
a request's wait depends on where in a decode step it arrives."""
import harness


def read(result, ctx):
    waits = result.counters["ttft_ms"]
    return harness.percentile(waits, 95) if waits else None
