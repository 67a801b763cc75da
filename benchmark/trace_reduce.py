"""From a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer readers and the result line's ``device`` and ``breakdown``
need.  Nothing here imports the program.

What a v5e trace holds (jax 0.9.0, libtpu 0.0.34; looked at by hand in
PR 26): one plane ``/device:TPU:<n>`` per chip with the lines
``XLA Modules`` (one event per run of an executable, named
``jit_<fn>(<fingerprint>)``) and ``XLA Ops`` (one event per HLO
instruction run, named by the instruction's whole text,
``%fusion.193 = bf16[4,2048,6144]{...} fusion(...)``; a ``while`` or a
``call`` is an event that contains its body's events).  A Pallas kernel
is an instruction whose text has ``custom_call_target="tpu_custom_call"``.
The plane ``/host:CPU`` has one line per thread; ``TraceAnnotation``
spans of the benchmark's own (``bench:<phase>``) are events there.  The
host's and the device's clocks agree to about a millisecond, not
better.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Callable, Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]          # (name, start_ns, duration_ns)

DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench:"
PALLAS_TARGET = 'custom_call_target="tpu_custom_call"'


@dataclasses.dataclass
class DeviceTrace:
    name: str
    modules: List[Event]
    ops: List[Event]


@dataclasses.dataclass
class Trace:
    devices: List[DeviceTrace]
    spans: List[Event]                    # the benchmark's host spans


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(source, *, text_proto: bool = False) -> Trace:
    """``source``: path of an ``.xplane.pb``, or with ``text_proto`` the
    text form of an XSpace (the small recorded trace of the tests)."""
    from jax.profiler import ProfileData

    data = (ProfileData.from_text_proto(source) if text_proto
            else ProfileData.from_file(source))
    devices, spans = [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            dev = DeviceTrace(plane.name, [], [])
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    dev.modules = [(e.name, e.start_ns, e.duration_ns)
                                   for e in line.events]
                elif line.name == OPS_LINE:
                    dev.ops = [(e.name, e.start_ns, e.duration_ns)
                               for e in line.events]
            dev.modules.sort(key=lambda e: e[1])
            dev.ops.sort(key=lambda e: (e[1], -e[2]))
            devices.append(dev)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    devices.sort(key=lambda d: d.name)
    spans.sort(key=lambda e: e[1])
    return Trace(devices, spans)


# -- names --------------------------------------------------------------------

def module_name(name: str) -> str:
    """``jit__decode(16921252201853463163)`` -> ``jit__decode``."""
    return name.split("(", 1)[0]


def op_head(name: str) -> str:
    """``%fusion.193 = bf16[...] fusion(...)`` -> ``%fusion.193``."""
    return name.split(" = ", 1)[0].strip()


_RESULT = re.compile(r"= \(?([a-z0-9]+\[[0-9,]*\])")
_OPCODE = re.compile(r"[}\])] ([a-z][a-z\-]*)\(")


def op_label(name: str) -> str:
    """A short stable label for the breakdown: head, opcode, first
    result shape; a Pallas kernel is marked."""
    head = op_head(name)
    shape = _RESULT.search(name)
    opcode = _OPCODE.search(name)
    label = head
    if PALLAS_TARGET in name:
        label += " pallas"
    elif opcode:
        label += " " + opcode.group(1)
    if shape:
        label += " " + shape.group(1)
    return label


def is_pallas(name: str) -> bool:
    return PALLAS_TARGET in name


def shapes_in(name: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """Every ``dtype[dims]`` of an instruction's text, results first."""
    out = []
    for dtype, dims in re.findall(r"\b([a-z]+[0-9]+|pred)\[([0-9,]*)\]",
                                  name):
        out.append((dtype, tuple(int(d) for d in dims.split(",") if d)))
    return out


def result_shapes(name: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """The shapes an instruction produces (left of its opcode)."""
    body = name.split(" = ", 1)[-1]
    m = _OPCODE.search(body)
    return shapes_in(body[:m.start() + 1] if m else body)


def operand_shapes(name: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """The shapes an instruction reads (its operand list)."""
    body = name.split(" = ", 1)[-1]
    m = _OPCODE.search(body)
    if not m:
        return []
    depth, i = 0, m.end() - 1
    for j in range(i, len(body)):
        depth += body[j] == "("
        depth -= body[j] == ")"
        if depth == 0:
            return shapes_in(body[i:j])
    return shapes_in(body[i:])


# -- intervals ----------------------------------------------------------------

def _clip(events: Sequence[Event], t0: Optional[float],
          t1: Optional[float]) -> List[Tuple[float, float]]:
    out = []
    for _, start, dur in events:
        a, b = start, start + dur
        if t0 is not None:
            a = max(a, t0)
        if t1 is not None:
            b = min(b, t1)
        if b > a:
            out.append((a, b))
    return out


def union(intervals: Sequence[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_intervals(dev: DeviceTrace, t0=None, t1=None):
    """Union of the intervals in which an operation ran on the device."""
    return union(_clip(dev.ops or dev.modules, t0, t1))


def busy_seconds(trace: Trace, t0=None, t1=None) -> float:
    """Seconds in which an operation ran, averaged over the chips."""
    if not trace.devices:
        return 0.0
    per_dev = [sum(b - a for a, b in busy_intervals(d, t0, t1))
               for d in trace.devices]
    return sum(per_dev) / len(per_dev) / 1e9


def span_window(trace: Trace, name: Optional[str] = None
                ) -> Tuple[float, float]:
    """[start of the first, end of the last] benchmark span (of
    ``name``) in ns: the traced part of the measured window on the
    host's clock."""
    spans = [s for s in trace.spans if name is None or s[0] == name]
    if not spans:
        raise ValueError("the trace has no span of the benchmark")
    return spans[0][1], max(s[1] + s[2] for s in spans)


# -- per executable and per op ------------------------------------------------

def module_runs(trace: Trace, device: int = 0) -> Dict[str, List[Event]]:
    """Runs of each executable on one chip, by its name without the
    fingerprint."""
    out: Dict[str, List[Event]] = {}
    for ev in trace.devices[device].modules:
        out.setdefault(module_name(ev[0]), []).append(ev)
    return out


def runs_between(trace: Trace, name: str, window: Tuple[float, float],
                 device: int = 0, slack_ns: float = 2e6) -> List[Event]:
    """Runs of the executable ``name`` that lie inside ``window`` (ns,
    on the host's clock, hence the slack)."""
    t0, t1 = window
    return [r for r in module_runs(trace, device).get(name, [])
            if r[1] >= t0 - slack_ns and r[1] + r[2] <= t1 + slack_ns]


def op_self_seconds(dev: DeviceTrace,
                    keep: Optional[Callable[[str], bool]] = None
                    ) -> Dict[str, float]:
    """Seconds per instruction (by whole text), a container's time not
    counting what its body's events cover."""
    out: Dict[str, float] = {}
    stack: List[List] = []                # [name, end, self_ns]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, _, self_ns = stack.pop()
            if keep is None or keep(name):
                out[name] = out.get(name, 0.0) + self_ns / 1e9

    for name, start, dur in dev.ops:
        close(start)
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return out


def op_seconds(dev: DeviceTrace, keep: Callable[[str], bool],
               t0=None, t1=None) -> Tuple[float, int]:
    """(seconds, runs) of the instructions ``keep`` picks, whole
    durations (for leaf instructions such as kernels)."""
    total, n = 0.0, 0
    for name, start, dur in dev.ops:
        if (t0 is not None and start < t0) or \
                (t1 is not None and start + dur > t1):
            continue
        if keep(name):
            total += dur
            n += 1
    return total / 1e9, n


def ops_within(dev: DeviceTrace, module_prefix: str,
               keep: Callable[[str], bool]) -> List[List[Event]]:
    """For every run of the executable ``module_prefix``, the
    instructions ``keep`` picks that ran inside it."""
    runs = [m for m in dev.modules
            if module_name(m[0]) == module_prefix]
    picked = [o for o in dev.ops if keep(o[0])]
    out, i = [], 0
    for _, start, dur in runs:
        while i < len(picked) and picked[i][1] < start:
            i += 1
        j, inside = i, []
        while j < len(picked) and picked[j][1] < start + dur:
            inside.append(picked[j])
            j += 1
        i = j
        out.append(inside)
    return out


# -- idle gaps ----------------------------------------------------------------

def idle_gaps(trace: Trace, device: int = 0, t0=None, t1=None
              ) -> List[Tuple[str, float]]:
    """(what the host was doing, seconds) for every interval of the
    window in which nothing ran on the chip.  "What the host was doing"
    is the benchmark span that covers most of the gap, else ``host``."""
    dev = trace.devices[device]
    busy = busy_intervals(dev, t0, t1)
    if not busy:
        return []
    lo = busy[0][0] if t0 is None else t0
    hi = busy[-1][1] if t1 is None else t1
    gaps, at = [], lo
    for a, b in busy:
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    if hi > at:
        gaps.append((at, hi))
    out = []
    for a, b in gaps:
        best, cover = "host", 0.0
        for name, start, dur in trace.spans:
            if start >= b:
                break
            c = min(b, start + dur) - max(a, start)
            # the innermost span that covers the most wins
            if c > 0 and c >= cover:
                best, cover = name[len(SPAN_PREFIX):], c
        out.append((best, (b - a) / 1e9))
    return out


def breakdown(trace: Trace, t0=None, t1=None, top: int = 10) -> dict:
    """The result line's ``breakdown``: the device operations that took
    most time (self time, by label, chip 0) and the idle time by what
    the host was doing (summed per span name, longest first)."""
    if not trace.devices:
        return {"device_ops": [], "idle_gaps": []}
    dev = trace.devices[0]
    by_label: Dict[str, float] = {}
    inside = DeviceTrace(dev.name, dev.modules, [
        o for o in dev.ops if (t0 is None or o[1] >= t0)
        and (t1 is None or o[1] < t1)])
    for name, sec in op_self_seconds(inside).items():
        label = op_label(name)
        by_label[label] = by_label.get(label, 0.0) + sec
    by_span: Dict[str, float] = {}
    for what, sec in idle_gaps(trace, 0, t0, t1):
        by_span[what] = by_span.get(what, 0.0) + sec
    rank = lambda d: [[k, v] for k, v in sorted(
        d.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": rank(by_label), "idle_gaps": rank(by_span)}


# -- a small recorded trace ---------------------------------------------------

def to_text_proto(trace: Trace, max_ops: int = 400) -> str:
    """Write ``trace`` (cut to its first ``max_ops`` instructions per
    chip, and the modules and spans up to there) as the text form of
    an XSpace that :func:`load` reads back: how the recorded trace of
    the tests was made from a real one."""
    def esc(s: str) -> str:
        return s.replace("\\", "\\\\").replace('"', '\\"')

    planes = []

    def plane(pid, name, lines):
        meta, out_lines = {}, []
        for lid, (lname, events) in enumerate(lines, 1):
            evs = []
            for ename, start, dur in events:
                mid = meta.setdefault(ename, len(meta) + 1)
                evs.append(
                    f"    events {{ metadata_id: {mid} offset_ps: "
                    f"{int(start * 1000)} duration_ps: {int(dur * 1000)} }}")
            out_lines.append(
                f"  lines {{\n    id: {lid}\n    name: \"{esc(lname)}\"\n"
                + "\n".join(evs) + "\n  }")
        metas = [
            f"  event_metadata {{ key: {mid} value {{ id: {mid} "
            f"name: \"{esc(n)}\" }} }}" for n, mid in meta.items()]
        planes.append(
            f"planes {{\n  id: {pid}\n  name: \"{esc(name)}\"\n"
            + "\n".join(out_lines + metas) + "\n}")

    horizon = 0.0
    for i, dev in enumerate(trace.devices):
        ops = dev.ops[:max_ops]
        end = max((s + d for _, s, d in ops), default=0.0)
        horizon = max(horizon, end)
        mods = [m for m in dev.modules if m[1] < end]
        plane(i + 1, dev.name, [(MODULES_LINE, mods), (OPS_LINE, ops)])
    spans = [s for s in trace.spans if s[1] < horizon]
    plane(len(trace.devices) + 1, HOST_PLANE, [("python3", spans)])
    return "\n".join(planes) + "\n"
