"""Host-phase spans on the profiler's clock, kept in one in-memory ring.

:func:`phase` is the host half of :func:`apex_tpu.profiling.annotate`
grown up: a context manager around a piece of host work that

* opens ``jax.profiler.TraceAnnotation("apex:" + name)``, so that in any
  profiler session the span lies in the same trace as the device lines
  (``/host:CPU`` plane, on the profiler's clock) and an idle gap on the
  device line can be put down to what the host was doing;
* on exit appends one :class:`PhaseRecord` to :data:`PHASE_RING`, the
  one bounded process-wide ring, stamped with
  ``time.perf_counter_ns()`` whatever clock the caller keeps for its
  own timing fields.

There is no switch: a ``TraceAnnotation`` costs next to nothing until a
profiler session is live, and the ring is always on and bounded, as an
operator's flight recorder is (docs/telemetry.md, "Step phases").

Identity.  Every record has its own ``id`` and the ``id`` of the phase
that enclosed it on the same thread (``parent``; ``None`` at the top).
The phases of one engine step share its ``step`` index (a phase
inherits the enclosing phase's unless given its own), and a request's
phases carry its ``rid`` in ``attrs``.  A phase's self time is its
duration less what its children cover; while a phase is open its
closed children are at hand as ``span.children``.

What a record keeps alive decides what the ring costs: every container
the ring retains counts towards the collector's thresholds until the
ring is full, and a full collection of a serving process pauses it for
a tenth of a second.  So a record is one flat tuple, ``step`` is a
field and not a dict entry, and ``attrs`` is ``None`` where a phase
has none (PERF.md, PR 27).
"""

from __future__ import annotations

import itertools
import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional

import jax

from apex_tpu.telemetry.recorder import FlightRecorder

#: records kept: a 45 s serving window with its drain leaves about
#: 10,000, so an hour-old stall is gone and the last minutes are not
PHASE_RING_CAPACITY = 65_536
#: never ``bench:`` — that prefix is the benchmark's own
TRACE_PREFIX = "apex:"


class PhaseRecord(NamedTuple):
    name: str
    id: int
    parent: Optional[int]
    step: Optional[int]
    t_start_ns: int               # time.perf_counter_ns()
    t_end_ns: int
    attrs: Optional[Dict[str, Any]]

    @property
    def ms(self) -> float:
        return (self.t_end_ns - self.t_start_ns) / 1e6


PHASE_RING = FlightRecorder(PHASE_RING_CAPACITY)

_ids = itertools.count(1)


class _Open(threading.local):
    """Per thread: the phases now open, outermost first."""

    def __init__(self):
        self.stack: List["phase"] = []


_open = _Open()


class phase:
    """``with phase("decode.build"): ...`` — see the module docstring.
    ``attrs`` may be added to until the block ends
    (``span.attrs["rows"] = n``); ``record`` is set on exit, and
    ``children`` holds the records of the phases that closed inside
    this one, oldest first."""

    __slots__ = ("name", "step", "attrs", "id", "record", "children",
                 "_t_start_ns", "_annotation")

    def __init__(self, name: str, step: Optional[int] = None, **attrs):
        self.name = name
        self.step = step
        self.attrs = attrs
        self.record: Optional[PhaseRecord] = None
        self.children: List[PhaseRecord] = []

    def __enter__(self) -> "phase":
        stack = _open.stack
        self.id = next(_ids)
        if self.step is None and stack:
            self.step = stack[-1].step
        stack.append(self)
        self._annotation = jax.profiler.TraceAnnotation(
            TRACE_PREFIX + self.name)
        self._annotation.__enter__()
        self._t_start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        t_end_ns = time.perf_counter_ns()
        self._annotation.__exit__(*exc)
        stack = _open.stack
        stack.pop()
        outer = stack[-1] if stack else None
        self.record = PhaseRecord(
            self.name, self.id, None if outer is None else outer.id,
            self.step, self._t_start_ns, t_end_ns, self.attrs or None)
        PHASE_RING.record(self.record)
        if outer is not None:
            outer.children.append(self.record)
