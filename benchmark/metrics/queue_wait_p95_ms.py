"""95th percentile, over every request admitted, of the time from when
it was due to its admission (the engine's clock)."""
import harness


def read(result, ctx):
    waits = result.counters["queue_wait_ms"]
    return harness.percentile(waits, 95) if waits else None
