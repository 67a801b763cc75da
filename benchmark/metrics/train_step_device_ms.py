"""Device time of one run of the train step's executable: median over
the traced steps (chip 0)."""
import harness
import trace_reduce


def read(result, ctx):
    runs = trace_reduce.module_runs(result.trace).get(
        ctx.config["executables"]["step"])
    if not runs:
        return None
    return harness.median([dur for _, _, dur in runs]) / 1e6
