"""What the readers of device time by the program's named scopes share
(no manifest entry names this file).

The program registers every executable of its hot path with
``apex_tpu.telemetry`` at warm-up and, asked for ``scope_maps()``, turns
each into a map from optimized-HLO instruction (``%fusion.93``, what a
trace names an event by) to ``(scope, mixed)``: the ``named_scope`` path
the instruction came from, and whether a fusion's parts came from more
than one.  A reader imports ``scope_maps`` and nothing else of the
program, lays the map of one executable beside ``result.trace``, and
returns ``None`` where there is nothing to read: a program that has no
registry yet (the parent of the PR that added it), no map under that
name, or no run of the executable in the traced window.

The maps are resolved here, after the window and the comparison: each
costs a lowering and a load from the compile cache, which the first
reader pays and reports on standard error.

A group is a set of scope names; an instruction belongs to the FIRST
group of a list that holds any element of its path, so that groups
never share time (``layer/attn_latent/mla_absorb`` is ``mla_absorb``'s
where that group comes first), and to ``REST`` where none does.  Time is
self time: a ``while`` or ``call`` counts less what its body's events
cover, so the groups and ``REST`` add up to the run.
"""

from __future__ import annotations

import sys
from typing import Dict, List, Optional, Sequence, Tuple

import trace_reduce

REST = ""
Groups = Sequence[Tuple[str, frozenset]]

#: the train step: the optimizer's update, its copies in and out of the
#: flat buffer, forward and backward; REST is what no scope names
TRAIN_STEP: Groups = (
    ("zero_update", frozenset({"zero_update"})),
    ("zero_copy", frozenset({"zero_pack", "zero_unpack"})),
    ("fwd_bwd", frozenset({"fwd_bwd"})),
)
#: a deepseek_v2 decode step: the latent projections, the MLPs (dense
#: and expert), the latent kernel's own scope (append and kernel); REST
#: is norms, residuals, head, embedding, argmax, copies between them
DSV2_DECODE: Groups = (
    ("mla_proj", frozenset({"mla_q", "mla_kv_down", "mla_absorb",
                            "mla_out"})),
    ("experts", frozenset({"moe_router", "moe_experts", "moe_shared",
                           "mlp"})),
    ("attn_latent", frozenset({"attn_latent"})),
)

_cache: Dict[Tuple[int, str], Optional[Tuple[int, Dict[str, float]]]] = {}


def maps_of(executable: str) -> Optional[list]:
    """The program's scope maps of ``executable``, one a variant; None
    where the program has no registry or no such entry."""
    try:
        from apex_tpu.telemetry import scope_maps
    except ImportError:
        return None
    maps = scope_maps([executable]).get(executable)
    for m in maps or ():
        print(f"scope_time: {executable}"
              f"{'' if m.variant is None else ' [' + m.variant + ']'} "
              f"resolved in {m.seconds:.2f} s, as_text "
              f"{m.hlo_bytes} bytes, "
              f"{len(m.instructions)} instructions", file=sys.stderr)
    return maps or None


def runs_with_ops(result, executable: str) -> List[Tuple[tuple, list]]:
    """(run, the device events inside it) for every run of
    ``executable`` in the traced window, chip 0."""
    runs = trace_reduce.runs_between(result.trace, executable,
                                     result.trace_window_ns)
    ops = result.trace.devices[0].ops          # by (start, -duration)
    out, at = [], 0
    for run in runs:
        _, start, dur = run
        while at < len(ops) and ops[at][1] < start:
            at += 1
        end = at
        while end < len(ops) and ops[end][1] < start + dur:
            end += 1
        out.append((run, ops[at:end]))
        at = end
    return out


def pick(maps: list, ops: list):
    """Of an executable's maps, the variant whose instructions a run's
    events are."""
    if len(maps) == 1:
        return maps[0]
    heads = {trace_reduce.op_head(name) for name, _, _ in ops}
    return max(maps, key=lambda m: sum(h in m.instructions for h in heads))


def seconds_by_path(result, executable: Optional[str],
                    maps: Optional[list] = None
                    ) -> Optional[Tuple[int, Dict[str, float]]]:
    """(runs, {scope path: self seconds over all of them}) of
    ``executable`` in the traced window; an event whose instruction no
    map holds goes under ``"?"``.  ``maps`` stands in for the program's
    registry (the tests)."""
    if not executable or result.trace is None \
            or not result.trace.devices:
        return None
    key = (id(result.trace), executable)
    if maps is None and key in _cache:
        return _cache[key]
    found = maps if maps is not None else maps_of(executable)
    out = None
    if found:
        paths: Dict[str, float] = {}
        runs = joined = total = run_ns = 0
        for run, ops in runs_with_ops(result, executable):
            if not ops:
                continue
            runs += 1
            run_ns += run[2]
            which = pick(found, ops)
            inside = trace_reduce.DeviceTrace(executable, [], ops)
            for name, sec in trace_reduce.op_self_seconds(inside).items():
                scope, _ = which.instructions.get(
                    trace_reduce.op_head(name), ("?", False))
                paths[scope] = paths.get(scope, 0.0) + sec
                total += sec
                joined += sec * (scope != "?")
        if runs:
            out = (runs, paths)
            print(f"scope_time: {executable}: {runs} runs of "
                  f"{run_ns / 1e6 / runs:.3f} ms, "
                  f"{1e3 * total / runs:.3f} ms a run of self time, "
                  f"{100 * joined / total:.2f}% of it joined to the map",
                  file=sys.stderr)
    if maps is None:
        _cache.clear()                    # one trace a process
        _cache[key] = out
    return out


def group_of(path: str, groups: Groups) -> str:
    elements = set(path.split("/"))
    for label, names in groups:
        if elements & names:
            return label
    return REST


def ms_per_run(result, executable: Optional[str], groups: Groups,
               maps: Optional[list] = None) -> Optional[Dict[str, float]]:
    """Mean self milliseconds a run of ``executable`` by group of
    ``groups``, and under ``REST`` what no group holds."""
    found = seconds_by_path(result, executable, maps)
    if found is None:
        return None
    runs, paths = found
    out = {label: 0.0 for label, _ in groups}
    out[REST] = 0.0
    for path, sec in paths.items():
        out[group_of(path, groups)] += 1e3 * sec / runs
    return out


def read_group(result, ctx, executable_key: str, groups: Groups,
               label: str) -> Optional[float]:
    """What a reader returns: one group's milliseconds a run of the
    executable the configuration names under ``executable_key``."""
    by_group = ms_per_run(
        result, ctx.config.get("executables", {}).get(executable_key),
        groups)
    return None if by_group is None else by_group[label]
