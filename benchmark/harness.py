"""What the two window drivers and ``run.py`` share: the run's context,
its result, tracing, device memory, and the comparison's bookkeeping."""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import json
import os
import shutil
import statistics
import time
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# what a run leaves behind goes here, inside the checkout (.gitignore)
OUT_DIR = os.path.join(ROOT, ".bench_out")
# the traced part of a window, its last seconds (steady state; the
# profiler's stop, which takes seconds, then falls after the close): a
# trace of a whole serving window is hundreds of MB and minutes of reading
TRACE_SECONDS = 6.0


@dataclasses.dataclass
class Context:
    workload: dict            # the cell's entry in BENCHMARK.json
    config: dict              # configs/<config>.json
    mix: dict                 # traffic/<mix>.json
    limits: dict              # limits/<workload>.json
    peak: dict                # peaks.json[device_kind]
    seed: int
    seconds: float
    trace: bool
    t_process: float          # perf_counter at process start


@dataclasses.dataclass
class Check:
    """One number compared, beside its limit (``value <= limit``)."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


@dataclasses.dataclass
class Result:
    attempted: int
    failed: int
    end_to_end: Dict[str, float]
    window_start: float                    # perf_counter at window open
    window_s: float
    memory_peak_bytes: int
    checks: List[Check]
    counters: Dict[str, Any] = dataclasses.field(default_factory=dict)
    trace: Any = None                      # trace_reduce.Trace
    trace_window_ns: Optional[tuple] = None
    trace_window_s: Optional[float] = None

    @property
    def correct(self) -> bool:
        return self.failed == 0 and bool(self.checks) \
            and all(c.ok for c in self.checks)


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest chip.  On this runtime the
    counter leaves out a program's temporaries (PERF.md section 3): it
    is what the cell keeps resident, not what the step needs."""
    peaks = []
    for dev in devices:
        stats = dev.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def free_device_memory() -> None:
    import jax

    gc.collect()
    jax.clear_caches()
    gc.collect()


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0..100), linear between order
    statistics, of all of ``values``."""
    vals = sorted(values)
    if not vals:
        return float("nan")
    if len(vals) == 1:
        return float(vals[0])
    pos = (len(vals) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(vals) - 1)
    return float(vals[lo] + (vals[hi] - vals[lo]) * (pos - lo))


def median(values) -> float:
    return float(statistics.median(values)) if values else float("nan")


class Tracer:
    """The profiler around (a part of) the window, and the benchmark's
    own host spans.  Off, every call is a no-op."""

    def __init__(self, on: bool, window_s: float = 0.0):
        self.on = on
        self.start_after = max(0.0, window_s - TRACE_SECONDS)
        self.dir = os.path.join(OUT_DIR, "trace")
        self.running = False
        self.t_start = None

    def start_if_due(self, elapsed: float) -> bool:
        """Start the profiler once ``elapsed`` seconds of the window
        have reached its traced part; True when it started just now."""
        if not self.on or self.running or self.t_start is not None \
                or elapsed < self.start_after:
            return False
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0       # it would slow the host turn
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.running = True
        self.t_start = time.perf_counter()
        return True

    def stop(self) -> None:
        if not self.running:
            return
        import jax

        jax.profiler.stop_trace()
        self.running = False

    def span(self, name: str):
        if not self.running:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation("bench:" + name)

    def reduce(self):
        """(Trace, (t0_ns, t1_ns), seconds) of the traced part; the
        trace's files are deleted once read."""
        import trace_reduce

        trace = trace_reduce.load(trace_reduce.find_xplane(self.dir))
        shutil.rmtree(self.dir, ignore_errors=True)
        window = trace_reduce.span_window(trace)
        return trace, window, (window[1] - window[0]) / 1e9
