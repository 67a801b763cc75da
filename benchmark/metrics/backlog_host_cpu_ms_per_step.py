"""Mean process CPU time (``time.process_time_ns`` at both ends of
``engine.step``) per engine step of the window that decoded: all
threads of the process, so above the step's wall time it shows other
threads at work (PERF.md, Open question 9)."""
from metrics import phase_ring


def read(result, ctx):
    return phase_ring.mean(
        step.attrs["cpu_ns"] / 1e6
        for step, inside in phase_ring.steps(result)
        if "engine.decode" in inside)
