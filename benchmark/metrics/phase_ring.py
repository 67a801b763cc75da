"""What the readers of the serving engine's step phases share (no
manifest entry names this file).

The program keeps one bounded in-memory ring of host-phase records,
``apex_tpu.telemetry.PHASE_RING`` (``name, id, parent, step,
t_start_ns, t_end_ns, attrs``; times are ``time.perf_counter_ns()``,
the clock of ``Result.window_start``).  It outlives the engine, so a
reader that runs after the window still finds it.  A reader imports the
ring and nothing else of the program, cuts the measured window out of
it, and returns ``None`` where there is nothing to read: an empty ring,
or a program that has no ring yet (the parent of the PR that added it).

The phases of one ``ServingEngine.step()``: ``engine.step`` (attrs
``cpu_ns``, ``admitted``, ``retired``, ``evicted``) around one
``engine.prefill`` per request (``rid``, real tokens ``C``, row width
``S``; children ``prefill.build/dispatch/scatter/fetch``),
``engine.grow`` and ``engine.decode`` (``rows``, ``rids``, ``committed``
per row where that is not one; children
``decode.build/dispatch/fetch/commit``).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple


def ring() -> list:
    """Every record the ring holds, oldest first; [] without a ring."""
    try:
        from apex_tpu.telemetry import PHASE_RING
    except ImportError:
        return []
    return PHASE_RING.snapshot()


def window_ns(result) -> Tuple[float, float]:
    t0 = result.window_start * 1e9
    return t0, t0 + result.window_s * 1e9


def ms(record) -> float:
    return (record.t_end_ns - record.t_start_ns) / 1e6


def steps(result) -> List[Tuple[object, Dict[str, list]]]:
    """(``engine.step`` record, its descendants by name) for every
    engine step that lies inside the measured window."""
    t0, t1 = window_ns(result)
    records = [r for r in ring()
               if r.t_start_ns >= t0 and r.t_end_ns <= t1]
    by_id = {r.id: r for r in records}
    out: Dict[int, Tuple[object, Dict[str, list]]] = {
        r.id: (r, {}) for r in records if r.name == "engine.step"}
    for r in records:
        top = r
        while top.parent in by_id:
            top = by_id[top.parent]
        if top is not r and top.id in out:
            out[top.id][1].setdefault(r.name, []).append(r)
    return list(out.values())


def mean(values) -> Optional[float]:
    values = list(values)
    return sum(values) / len(values) if values else None


def host_turn_ms_per_step(result) -> Optional[float]:
    """Over engine steps that decoded and prefilled nothing: mean of
    ``engine.step`` less ``decode.fetch`` (the wait for the device):
    what the host does between taking one step's tokens and having
    dispatched and begun to wait for the next."""
    return mean(
        ms(step) - sum(ms(f) for f in inside.get("decode.fetch", ()))
        for step, inside in steps(result)
        if "engine.decode" in inside and "engine.prefill" not in inside)


def prefill_stall_ms_per_step(result) -> Optional[float]:
    """Time inside ``engine.prefill`` per decode step of the window:
    how long, on average, a step's running rows wait for prefills."""
    found = steps(result)
    decodes = sum(len(inside.get("engine.decode", ()))
                  for _, inside in found)
    if not decodes:
        return None
    return sum(ms(p) for _, inside in found
               for p in inside.get("engine.prefill", ())) / decodes


def token_times(records) -> Dict[int, List[int]]:
    """rid -> the time (ns) of each token the engine gave it, in order:
    a ``prefill.fetch`` ends with one token of its ``engine.prefill``'s
    request on the host, an ``engine.decode`` with one token (or
    ``committed[i]``) of each of its rows."""
    by_id = {r.id: r for r in records}
    times: Dict[int, List[int]] = {}
    for r in records:
        if r.name == "prefill.fetch" and r.parent in by_id:
            rid = by_id[r.parent].attrs["rid"]
            times.setdefault(rid, []).append(r.t_end_ns)
        elif r.name == "engine.decode":
            rids = r.attrs["rids"]
            counts = r.attrs.get("committed", (1,) * len(rids))
            for rid, n in zip(rids, counts):
                times.setdefault(rid, []).extend([r.t_end_ns] * n)
    return times
