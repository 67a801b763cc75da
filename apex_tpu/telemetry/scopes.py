"""Device time by the program's own named scopes.

The host half of the tracing (:mod:`apex_tpu.telemetry.phases`) stops at
the executable's door.  Inside it the program's ``jax.named_scope`` names
(``mla_absorb``, ``moe_experts``, ``zero_update`` ...) survive only as
``op_name`` metadata of the optimized HLO, which a profile read through
``jax.profiler.ProfileData`` does not show: there an event is named by
its instruction's text (``%fusion.93 = f32[...] fusion(...)``).  Only the
process that compiled an executable can say which scope ``%fusion.93``
came from.  This module is that join:

* :func:`register` — at warm-up the program hands over each jitted
  function it serves with and the shapes it serves it at.  A dict
  entry: nothing is lowered, compiled or kept on the device.
* :func:`scope_maps` — on request, and only then, every registered
  executable is lowered and compiled once more (a hit in the persistent
  compile cache: the same HLO, the same donation as served), its
  ``as_text()`` parsed into a :class:`ScopeMap` (instruction head ->
  ``(scope, mixed)``) and the executable dropped.  Tracing "off" is
  "nobody called :func:`scope_maps`".
* :func:`by_scope` — sums a list of traced device events by scope, a
  container's (``while``, ``call``) time counted less what its body's
  events cover.
* :func:`dump` / :func:`load` and ``python -m apex_tpu.telemetry scopes
  --maps scopes.json x.xplane.pb`` — the operator's use: a by-scope
  table from any profile of the process that wrote ``scopes.json``
  (docs/telemetry.md, "Device time by scope").

The parser of optimized-HLO text below (computation splitter, caller
and ``op_name`` patterns) is the tree's only one;
:mod:`apex_tpu.profiling.trace_report` imports it.
"""

from __future__ import annotations

import dataclasses
import json
import os
import re
import time
from typing import (Any, Dict, Iterable, Iterator, List, NamedTuple, Optional,
                    Sequence, Tuple)

import jax

# -- the parser of optimized-HLO text -----------------------------------------

#: ``%inst = ... calls=%comp`` (also ``to_apply=``, ``body=``)
CALLER_RE = re.compile(
    r"%([\w.-]+) = [^\n]*?(?:calls|to_apply|body)=%([\w.-]+)", re.M)
#: the first line of a computation: ``[ENTRY ]%name (params) -> type {``
COMP_DEF_RE = re.compile(
    r"^(?:ENTRY )?%?([\w.-]+) \(.*\) -> .+ \{$", re.M)
#: an instruction's ``metadata={op_name="..."}``
OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
#: one instruction with metadata: (name, op_name)
NAMED_INSTRUCTION_RE = re.compile(
    r'^\s*(?:ROOT )?%([\w.-]+) = [^\n]*?op_name="([^"]*)"', re.M)

_INSTRUCTION_RE = re.compile(r"^\s*(ROOT )?(%[\w.-]+) = (.*)$")
_OPCODE_RE = re.compile(r"[}\])] ([a-z][a-z\-]*)\(")
_FUSED_RE = re.compile(r"\bcalls=%([\w.-]+)")
_REF_RE = re.compile(r"%[\w.-]+")
#: instructions whose computations run as events of their own
_CALLING_OPCODES = ("while", "call", "conditional")
_CALLEE_RE = re.compile(
    r"(?:body|condition|to_apply|true_computation|false_computation)"
    r"=%([\w.-]+)")
_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_PALLAS_TARGET = 'custom_call_target="tpu_custom_call"'
#: what XLA appends to an instruction's name: ``.24``, ``.remat2``, ``.clone``
_NAME_SUFFIX_RE = re.compile(r"(\.(\d+|remat\d*|clone))+$")
#: opcodes that do a fusion's work, where it has one of them
_WORK_OPCODES = ("dot", "convolution", "custom-call")

#: transforms wrap the scope that follows them: ``transpose(jvp(fwd_bwd))``
_TRANSFORMS = frozenset((
    "jvp", "transpose", "vmap", "pmap", "linearize", "custom_jvp",
    "custom_vjp"))
#: path elements JAX's own machinery leaves: control flow, rematerialised
#: and called sub-computations
_MACHINERY = frozenset((
    "while", "body", "cond", "body_fun", "cond_fun", "scan", "checkpoint",
    "remat", "rematted_computation", "closed_call", "core_call", "pjit",
    "xla_call", "custom_jvp_call", "custom_vjp_call",
    "custom_vjp_call_jaxpr", "custom_lin", "shard_map", "shmap_body",
    "pallas_call", "run_state", "branch"))
_WRAPPED_RE = re.compile(r"^([\w.\-]*)\((.*)\)$")
_SCOPE_NAME_RE = re.compile(r"^[A-Za-z_][\w.\-]*$")
_BRANCH_RE = re.compile(r"^branch_\d+_fun$")


def computations(hlo_text: str) -> Dict[str, str]:
    """name -> text (first line to the next computation's) of every
    computation of a module's ``as_text()``."""
    found = list(COMP_DEF_RE.finditer(hlo_text))
    out: Dict[str, str] = {}
    for i, m in enumerate(found):
        end = found[i + 1].start() if i + 1 < len(found) else len(hlo_text)
        out[m.group(1)] = hlo_text[m.start():end]
    return out


def _split_path(op_name: str) -> List[str]:
    """``op_name`` cut at the slashes outside any parenthesis."""
    parts, depth, at = [], 0, 0
    for i, ch in enumerate(op_name):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        elif ch == "/" and depth == 0:
            parts.append(op_name[at:i])
            at = i + 1
    parts.append(op_name[at:])
    return parts


def scope_path(op_name: str) -> str:
    """The ``named_scope`` names of an instruction's ``op_name``,
    outermost first, joined by ``/``; ``""`` where it came from none.

    ``jit(_decode)/layer/mla_absorb/dot_general`` -> ``layer/mla_absorb``.
    Stripped: the primitive at the tail, ``jit(...)`` / ``pjit`` wrappers
    (what they hold is a function's name), transforms (``jvp(...)``,
    ``transpose(jvp(...))``: a backward instruction keeps the scope of
    its forward), ``checkpoint`` / ``rematted_computation``,
    ``while/body`` and the like, and anything that is no identifier (an
    einsum's ``bqd,bkd->bqk``)."""
    out: List[str] = []
    # XLA joins the names of instructions it merged with ";": the first
    for part in _split_path(op_name.split(";", 1)[0])[:-1]:
        unwrapped = False
        while True:
            m = _WRAPPED_RE.match(part)
            if m is None:
                break
            if m.group(1) not in _TRANSFORMS:
                part = ""              # jit(fn): a function, not a scope
                break
            part, unwrapped = m.group(2), True
        if not part or part in _MACHINERY or _BRANCH_RE.match(part) \
                or not _SCOPE_NAME_RE.match(part):
            continue
        # transpose(fwd_bwd) right under fwd_bwd names it twice
        if unwrapped and out and out[-1] == part:
            continue
        out.append(part)
    return "/".join(out)


def _instructions(body: str) -> Iterator[Tuple[str, bool, str, str]]:
    """(head, is root, opcode, line) of a computation's instructions."""
    for line in body.splitlines()[1:]:
        m = _INSTRUCTION_RE.match(line)
        if m is None:
            continue
        opcode = _OPCODE_RE.search(m.group(3))
        yield (m.group(2), bool(m.group(1)),
               opcode.group(1) if opcode else "", line)


def _scope_of_line(line: str) -> str:
    m = OP_NAME_RE.search(line)
    return scope_path(m.group(1)) if m else ""


def kernel_name(head: str) -> str:
    """``%flash_decode_latent.24`` -> ``flash_decode_latent``."""
    return _NAME_SUFFIX_RE.sub("", head.lstrip("%"))


def _one_path(scopes: Iterable[str]) -> str:
    """The most specific of ``scopes`` where they lie on one path
    (``layer``, ``layer/moe_experts``), else what they all start with."""
    paths = sorted({tuple(s.split("/")) for s in scopes}, key=len)
    if not paths:
        return ""
    longest = paths[-1]
    if all(longest[:len(p)] == p for p in paths):
        return "/".join(longest)
    return "/".join(os.path.commonprefix(paths))


def instruction_scopes(hlo_text: str) -> Dict[str, Tuple[str, bool]]:
    """head (``%fusion.93``, as a trace names the instruction) ->
    ``(scope, mixed)`` for every instruction of ``compiled.as_text()``
    that runs as an event of its own (the insides of fusions left out).

    A fusion takes the scope of the instruction of its fused
    computation that does its work: a ``dot`` / ``convolution`` /
    ``custom-call`` where it has one, else its root, else the scope most
    of them have; ``mixed`` says that they come from more than one.  A
    Pallas call takes the scope it was called in with its kernel's name
    as the last element (``layer/attn_latent/flash_decode_latent``).  An
    instruction the compiler made (no ``op_name``, or one that names no
    JAX function: ``ragged-dot-none``, a ``copy-start``) takes the scope
    its users share, or an operand's where that says the same more
    precisely (``layer/moe_experts`` under users in ``layer``), else the
    scope of the ``while`` / ``call`` whose computation it is in."""
    rows_of = {name: list(_instructions(body))
               for name, body in computations(hlo_text).items()}
    fused: Dict[str, Tuple[str, bool]] = {}

    def fusion_scope(comp: str, own: str) -> Tuple[str, bool]:
        if comp not in fused:
            work, root, seen = None, None, {}
            for _, is_root, opcode, line in rows_of.get(comp, ()):
                scope = _scope_of_line(line)
                if scope:
                    seen[scope] = seen.get(scope, 0) + 1
                if work is None and opcode in _WORK_OPCODES:
                    work = scope
                if is_root:
                    root = scope
            # a root the compiler made (a bitcast, a convert) has no
            # metadata: then the scope most of the fused instructions have
            most = max(seen, key=seen.get) if seen else ""
            fused[comp] = (work or root or most, len(seen) > 1)
        scope, mixed = fused[comp]
        return scope or own, mixed

    inside_fusions = {
        m.group(1) for rows in rows_of.values()
        for _, _, opcode, line in rows if opcode == "fusion"
        for m in [_FUSED_RE.search(line)] if m}
    out: Dict[str, Tuple[str, bool]] = {}
    handed_down: Dict[str, str] = {}      # computation -> its caller's scope
    # callers stand after what they call: from the entry backwards
    for name in reversed(list(rows_of)):
        if name in inside_fusions:
            continue
        rows = rows_of[name]
        made: List[str] = []              # heads of compiler-made instructions
        kernels: List[str] = []
        for head, _, opcode, line in rows:
            m = OP_NAME_RE.search(line)
            op_name = m.group(1) if m else ""
            scope, mixed = scope_path(op_name), False
            if opcode == "fusion":
                m = _FUSED_RE.search(line)
                if m:
                    scope, mixed = fusion_scope(m.group(1), scope)
            elif opcode == "custom-call" and _PALLAS_TARGET in line:
                kernels.append(head)
            if not scope and not _WRAPPED_RE.match(
                    _split_path(op_name)[0]):
                made.append(head)
            out[head] = (scope, mixed)
        if made:
            users: Dict[str, List[str]] = {}
            operands: Dict[str, List[str]] = {}
            for head, _, _, line in rows:
                for ref in set(_REF_RE.findall(line.split(" = ", 1)[1])):
                    if ref != head and ref in out:
                        users.setdefault(ref, []).append(head)
                        operands.setdefault(head, []).append(ref)
            # users stand after what they use: resolve from the end
            for head in reversed(made):
                scope = _one_path(out[u][0] for u in users.get(head, ())
                                  if out[u][0])
                if scope:       # an operand may say it more precisely
                    scope = _one_path([scope] + [
                        out[o][0] for o in operands.get(head, ())
                        if out[o][0].startswith(scope + "/")])
                out[head] = (scope or handed_down.get(name, ""),
                             out[head][1])
        for head in kernels:
            scope, kernel = out[head][0], kernel_name(head)
            last = scope.rsplit("/", 1)[-1]
            if not scope:
                scope = kernel
            elif last not in kernel and kernel not in last:
                scope = f"{scope}/{kernel}"
            out[head] = (scope, False)
        for head, _, opcode, line in rows:
            if opcode in _CALLING_OPCODES and out[head][0]:
                callees = _CALLEE_RE.findall(line)
                for branches in _BRANCHES_RE.findall(line):
                    callees += [b.strip().lstrip("%")
                                for b in branches.split(",")]
                for callee in callees:
                    handed_down.setdefault(callee, out[head][0])
    return out


# -- the registry --------------------------------------------------------------

@dataclasses.dataclass
class ScopeMap:
    """One compiled executable's instructions by scope.  ``variant``
    tells apart executables of one name (a prefill row's width);
    ``seconds`` and ``hlo_bytes`` are what resolving it cost."""

    variant: Optional[str]
    instructions: Dict[str, Tuple[str, bool]]
    seconds: float = 0.0
    hlo_bytes: int = 0


@dataclasses.dataclass
class _Entry:
    jitted: Any
    args: Tuple
    resolved: Optional[ScopeMap] = None


_REGISTRY: Dict[Tuple[str, Optional[str]], _Entry] = {}


def _struct(x):
    """A leaf of an argument as its shape, dtype and placement.  Only a
    committed array's sharding is kept, as ``jit`` itself only takes
    those for its input shardings: an uncommitted one with a sharding
    spelled out would lower to another module than the served one."""
    if isinstance(x, jax.ShapeDtypeStruct):
        return x
    if isinstance(x, jax.Array):
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, weak_type=x.weak_type,
            sharding=x.sharding if x.committed else None)
    return jax.ShapeDtypeStruct(jax.numpy.shape(x),
                                jax.numpy.result_type(x))


def executable_name(jitted) -> str:
    """What a trace calls ``jitted``'s executable, less the fingerprint:
    ``jit__decode`` for a jitted ``_decode``."""
    return "jit_" + re.sub(r"[^\w.\-]", "_", jitted.__name__)


def register(name: str, jitted, args: Sequence, variant=None) -> None:
    """Note that the program serves with ``jitted`` at ``args``.

    ``name`` is what a trace calls the executable without its
    fingerprint (``jit__decode``).  ``args`` may hold arrays: only their
    ``jax.ShapeDtypeStruct`` (with sharding) is kept, so an entry keeps
    no device memory alive; ``jitted`` must not close over any either.
    Nothing is lowered or compiled here.  The same name and variant
    registered again replaces the entry, unless it is the same function
    at the same shapes (a re-trace)."""
    key = (name, None if variant is None else str(variant))
    args = tuple(jax.tree_util.tree_map(_struct, tuple(args)))
    old = _REGISTRY.get(key)
    if old is not None and old.jitted is jitted and old.args == args:
        return
    _REGISTRY[key] = _Entry(jitted, args)


def registered() -> List[Tuple[str, Optional[str]]]:
    """(name, variant) of every entry."""
    return list(_REGISTRY)


def clear() -> None:
    _REGISTRY.clear()


def _resolve(entry: _Entry, variant: Optional[str]) -> ScopeMap:
    t0 = time.perf_counter()
    compiled = entry.jitted.lower(*entry.args).compile()
    text = compiled.as_text()
    del compiled
    return ScopeMap(variant, instruction_scopes(text),
                    seconds=time.perf_counter() - t0, hlo_bytes=len(text))


def scope_maps(names: Optional[Iterable[str]] = None
               ) -> Dict[str, List[ScopeMap]]:
    """``{name: [ScopeMap, ...]}`` of every registered executable (of
    ``names``), one map a variant.  An entry not yet resolved is lowered
    and compiled now (seconds each, a minute where the persistent
    compile cache misses): call it after a measured window, never in
    one."""
    want = None if names is None else set(names)
    out: Dict[str, List[ScopeMap]] = {}
    for (name, variant), entry in list(_REGISTRY.items()):
        if want is not None and name not in want:
            continue
        if entry.resolved is None:
            entry.resolved = _resolve(entry, variant)
        out.setdefault(name, []).append(entry.resolved)
    return out


def dump(path: str, names: Optional[Iterable[str]] = None) -> None:
    """Write :func:`scope_maps` as JSON for :func:`load` and the CLI."""
    doc = {name: [{"variant": m.variant,
                   "instructions": {h: [s, mixed] for h, (s, mixed)
                                    in m.instructions.items()}}
                  for m in maps]
           for name, maps in scope_maps(names).items()}
    with open(path, "w") as f:
        json.dump(doc, f)


def load(path: str) -> Dict[str, List[ScopeMap]]:
    with open(path) as f:
        doc = json.load(f)
    return {name: [ScopeMap(m["variant"],
                            {h: (s, bool(mixed)) for h, (s, mixed)
                             in m["instructions"].items()})
                   for m in maps]
            for name, maps in doc.items()}


# -- traced device time by scope ------------------------------------------------

#: the scope of an event whose instruction the map does not hold
UNKNOWN = "?"


class ScopeTime(NamedTuple):
    scope: str
    seconds: float                # self time
    runs: int                     # events
    mixed_seconds: float          # of it, in fusions marked ``mixed``


def op_head(text: str) -> str:
    """``%fusion.193 = bf16[...] fusion(...)`` -> ``%fusion.193``."""
    return text.split(" = ", 1)[0].strip()


def self_times(ops: Sequence[Tuple[str, float, float]]
               ) -> List[Tuple[str, float]]:
    """(instruction text, self ns) of every event of ``ops`` (text,
    start_ns, duration_ns): a container's duration less what the events
    inside it cover, so that nested events are counted once."""
    out: List[Tuple[str, float]] = []
    stack: List[List] = []            # [text, end, self_ns]
    for text, start, dur in sorted(ops, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            done = stack.pop()
            out.append((done[0], done[2]))
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([text, start + dur, dur])
    out.extend((text, self_ns) for text, _, self_ns in reversed(stack))
    return out


def by_scope(ops: Sequence[Tuple[str, float, float]], scope_map: ScopeMap,
             depth: Optional[int] = None) -> List[ScopeTime]:
    """Rows of self time by scope, longest first, for the device events
    ``ops`` (instruction text, start_ns, duration_ns) of an executable's
    run(s).  ``depth`` keeps that many leading elements of a scope's
    path; an event the map does not hold goes under :data:`UNKNOWN`."""
    sums: Dict[str, List[float]] = {}
    for text, self_ns in self_times(ops):
        scope, mixed = scope_map.instructions.get(op_head(text),
                                                  (UNKNOWN, False))
        if depth is not None and scope != UNKNOWN:
            scope = "/".join(scope.split("/")[:depth])
        row = sums.setdefault(scope, [0.0, 0, 0.0])
        row[0] += self_ns / 1e9
        row[1] += 1
        if mixed:
            row[2] += self_ns / 1e9
    return sorted((ScopeTime(s, sec, int(n), mixed)
                   for s, (sec, n, mixed) in sums.items()),
                  key=lambda r: -r.seconds)


def best_variant(maps: Sequence[ScopeMap], heads: Iterable[str]) -> ScopeMap:
    """Of the maps of one executable name, the one that holds the most
    of a run's instruction heads."""
    heads = set(heads)
    return max(maps, key=lambda m: sum(h in m.instructions for h in heads))


# -- the operator's table --------------------------------------------------------

_DEVICE_PLANE = "/device:TPU:"


def profile_tables(xplane_path: str, maps: Dict[str, List[ScopeMap]],
                   executable: Optional[str] = None,
                   depth: Optional[int] = None) -> List[dict]:
    """One table per executable (of ``maps``, or ``executable`` alone)
    and variant that ran in the profile: ``{"executable", "variant",
    "runs", "ms_per_run", "rows": [ScopeTime, ...]}`` from the first
    device plane's ``XLA Modules`` and ``XLA Ops`` lines."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    planes = sorted((p for p in data.planes
                     if p.name.startswith(_DEVICE_PLANE)),
                    key=lambda p: p.name)
    if not planes:
        raise ValueError(f"{xplane_path}: no {_DEVICE_PLANE}* plane")
    modules, ops = [], []
    for line in planes[0].lines:
        events = [(e.name, e.start_ns, e.duration_ns) for e in line.events]
        if line.name == "XLA Modules":
            modules = sorted(events, key=lambda e: e[1])
        elif line.name == "XLA Ops":
            ops = sorted(events, key=lambda e: (e[1], -e[2]))
    grouped: Dict[Tuple[str, Optional[str]], List] = {}
    at = 0
    for name, start, dur in modules:
        while at < len(ops) and ops[at][1] < start:
            at += 1
        end = at
        while end < len(ops) and ops[end][1] < start + dur:
            end += 1
        inside, at = ops[at:end], end
        name = name.split("(", 1)[0]          # less the fingerprint
        if name not in maps or (executable and name != executable) \
                or not inside:
            continue
        which = best_variant(maps[name], (op_head(o[0]) for o in inside))
        run = grouped.setdefault((name, which.variant), [which, 0, []])
        run[1] += 1
        run[2].extend(inside)
    return [{"executable": name, "variant": variant, "runs": runs,
             "ms_per_run": sum(r.seconds for r in rows) * 1e3 / runs,
             "rows": rows}
            for (name, variant), (which, runs, inside) in grouped.items()
            for rows in [by_scope(inside, which, depth)]]


def format_table(table: dict) -> str:
    runs, total = table["runs"], table["ms_per_run"]
    title = table["executable"] + (
        f" [{table['variant']}]" if table["variant"] else "")
    lines = [f"{title}: {runs} runs, {total:.3f} ms a run",
             f"  {'scope':<44} {'ms/run':>9} {'share':>7} {'events':>8} "
             f"{'mixed':>7}"]
    for row in table["rows"]:
        ms = row.seconds * 1e3 / runs
        lines.append(
            f"  {row.scope or '(no scope)':<44} {ms:9.3f} "
            f"{100 * ms / total if total else 0:6.1f}% "
            f"{row.runs // runs:8d} "
            f"{100 * row.mixed_seconds / row.seconds if row.seconds else 0:6.1f}%")
    return "\n".join(lines)


def run_scopes_cli(xplane_path: str, maps_path: str,
                   executable: Optional[str] = None,
                   depth: Optional[int] = None) -> int:
    tables = profile_tables(xplane_path, load(maps_path), executable, depth)
    if not tables:
        print("no run of a mapped executable in this profile")
        return 1
    print("\n\n".join(format_table(t) for t in tables))
    return 0
