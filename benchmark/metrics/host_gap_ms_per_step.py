"""Idle time on the device line in the traced part of the window, per
decode step run there: what the host turn costs each step."""
import trace_reduce


def read(result, ctx):
    runs = trace_reduce.runs_between(
        result.trace, ctx.config["executables"]["decode"],
        result.trace_window_ns)
    if not runs:
        return None
    t0, t1 = result.trace_window_ns
    idle = (t1 - t0) / 1e9 - trace_reduce.busy_seconds(result.trace, t0, t1)
    return 1e3 * idle / len(runs)
