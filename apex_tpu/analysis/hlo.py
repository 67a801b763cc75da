"""Compiled-artifact contract checker (ISSUE 13).

The PR 11 linter checks what the *source* promises (a ``jit`` call
names ``donate_argnums``, a hot function avoids host syncs).  This
module checks what the *compiler* delivered: it parses an executable's
optimized HLO (``compiled.as_text()``), buffer assignment
(``memory_analysis()``) and cost model (``cost_analysis()``) into a
structured :class:`ExecutableReport` —

- **verified donation** — the ``input_output_alias`` pairs the module
  header actually carries.  A ``donate_argnums`` that XLA dropped
  (shape-changing output, layout mismatch, a refactor that reordered
  arguments) leaves no alias pair, and at flagship scale the old
  buffers ARE the fit margin (the PR 8 768 MB lesson);
- **collective inventory** — per-opcode counts and result-shape bytes,
  under the trace_report anchored-opcode discipline (``all-gather-
  start.3`` counts, a compiler-pass-named row like ``reduce-scatter-
  decomposer`` does not; ``-start``/``-done`` async pairs count ONCE,
  at the start row).  This is the measured communication-per-step
  baseline ROADMAP item 3's overlap work gates against;
- **host interaction** — infeed/outfeed/send/recv and host custom
  calls (``xla_python_cpu_callback`` and friends): the ops that turn
  "zero host syncs after warmup" from prose into a checkable property;
- plus the optimized-HLO opcode histogram (shared with
  :func:`apex_tpu.profiling.opcode_histogram_from_text`) and
  argument/output/temp byte totals.

Reports are diffed against a committed ``hlo_contracts.json`` (per
executable: required aliasing pairs, max collectives per opcode,
allowed host ops, a temp-byte ceiling) by ``python -m
apex_tpu.analysis hlo`` — exit 0 clean, 1 violations (stale contract
entries included: a contract for a deleted executable fails loudly,
PR 11 baseline discipline), 2 missing-or-unparseable contract (the r4
``parsed:null`` lesson: an unreadable gate must not pass green).  The
registry of executables lives in :mod:`apex_tpu.analysis.registry`;
docs/analysis.md "Compiled-artifact contracts" documents the schema
and the ``--update`` workflow.

Counting caveat (same as the HLO flops parser): an instruction inside
a ``while`` body appears once in the HLO text, so a collective inside
a loop counts ONCE regardless of trip count — the inventory is
per-program structure, not per-execution.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

__all__ = [
    "AliasPair",
    "CheckResult",
    "ContractFileError",
    "ExecutableReport",
    "HostOp",
    "check_contract",
    "check_reports",
    "collective_inventory",
    "contract_from_report",
    "executable_report",
    "host_interaction_ops",
    "load_contracts",
    "parse_aliases",
    "parse_instructions",
    "save_contracts",
]

CONTRACTS_FORMAT = 1

#: Provenance stamp written into every contracts file: contract
#: byte/count numbers come from CPU-lowerable toy geometry and must
#: not be read as flagship-scale truth.
DEFAULT_GEOMETRY = "cpu-toy"


class ContractFileError(Exception):
    """The contracts file is missing, unparseable, or wrong-format —
    the CLI maps this to exit code 2: an unreadable gate must not
    pass green (the r4 ``parsed:null`` incident)."""


# ---------------------------------------------------------------------------
# HLO text parsing
# ---------------------------------------------------------------------------

#: One instruction line: ``[ROOT] %name = <shape> opcode(...)``.  The
#: non-greedy shape group stops at the first identifier followed by an
#: open paren, which is the opcode token (operand shapes live INSIDE
#: the parens).  Computation definitions (``%comp (p: f32[]) -> …``)
#: have no ``=`` and are skipped.
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*?)\s*([a-z][a-z0-9\-]*)\(")

#: ``{out,idx}: (param, {param,idx}[, kind])`` entries of the module
#: header's ``input_output_alias={ … }`` block.  The ``: (`` makes the
#: pattern specific to alias entries — layout braces (``{1,0}``) and
#: ``buffer_donor={ {2} }`` entries never match.
_ALIAS_ENTRY_RE = re.compile(
    r"\{([\d,\s]*)\}:\s*\((\d+),\s*\{([\d,\s]*)\}\s*"
    r"(?:,\s*(may-alias|must-alias))?\)")

_DTYPE_BYTES = {
    "pred": 1, "s2": 1, "u2": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1, "f8e4m3fnuz": 1,
    "f8e5m2fnuz": 1, "f8e3m4": 1, "f8e4m3": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"\b([a-z][a-z0-9]*)\[([\d,]*)\]")

#: Anchored collective-opcode matcher — the trace_report discipline
#: transplanted from trace rows to HLO opcode tokens: the opcode, an
#: optional ``-start``/``-done``, then NOTHING.  ``all-reduce`` and
#: ``all-gather-start`` match; ``all-reduce-promotion`` (a compiler
#: PASS name) does not.
COLLECTIVE_OPCODES = (
    "all-reduce", "all-gather", "reduce-scatter", "collective-permute",
    "all-to-all", "collective-broadcast", "ragged-all-to-all")
_COLLECTIVE_RE = re.compile(
    r"^(%s)(-start|-done)?$"
    % "|".join(re.escape(o) for o in COLLECTIVE_OPCODES))

#: Host-interaction opcodes.  send/recv are counted unconditionally:
#: in this project's programs they only appear as host transfers
#: (device-to-device send/recv would come from pipelining machinery
#: the repo does not emit) — being conservative here means a false
#: POSITIVE surfaces for a human to look at, never a silent pass.
_HOST_OPCODES = frozenset(
    ("infeed", "outfeed", "send", "recv", "send-done", "recv-done"))

#: ``custom_call_target`` substrings that mark a custom call as host
#: interaction: python callbacks (``xla_python_cpu_callback``,
#: ``xla_ffi_python_cpu_callback`` — jax.pure_callback/io_callback/
#: debug.print all lower to these) and host-memory offload moves.
#: Pallas (``tpu_custom_call``/``__gpu$…``) matches neither.
_HOST_TARGET_HINTS = ("callback", "host")

_TARGET_RE = re.compile(r'custom_call_target="([^"]+)"')


def _shape_bytes(shape: str) -> int:
    """Total bytes of an HLO shape string — ``f32[8,128]{1,0}`` or a
    tuple ``(f32[256]{0}, s32[])``; elements of unknown dtype (token,
    opaque) contribute 0."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape):
        nbytes = _DTYPE_BYTES.get(dtype)
        if nbytes is None:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * nbytes
    return total


def parse_instructions(hlo_text: str) -> Iterable[Tuple[str, str, str]]:
    """Yield ``(instruction_name, shape_str, opcode)`` for every
    instruction line of an HLO module dump."""
    for line in hlo_text.splitlines():
        m = _INSTR_RE.match(line)
        if m:
            yield m.group(1), m.group(2), m.group(3)


@dataclasses.dataclass(frozen=True)
class AliasPair:
    """One verified input→output buffer alias from the module header:
    entry parameter ``param_number`` (sub-index ``param_index`` when
    the parameter is a tuple, usually empty) aliases output tuple
    index ``output_index``."""

    output_index: str          # "0" or "1,0" — tuple index path
    param_number: int
    param_index: str = ""
    kind: str = "may-alias"

    def to_json(self) -> Dict[str, Any]:
        d = {"param": self.param_number, "output": self.output_index}
        if self.param_index:
            d["param_index"] = self.param_index
        return d


def parse_aliases(hlo_text: str) -> List[AliasPair]:
    """``input_output_alias`` pairs of the module header — the
    donation that actually SURVIVED compilation.  An empty list on a
    supposedly-donating executable is exactly the failure this checker
    exists to catch."""
    header = ""
    for line in hlo_text.splitlines():
        if line.startswith("HloModule"):
            header = line
            break
    if "input_output_alias" not in header:
        return []
    out = []
    for m in _ALIAS_ENTRY_RE.finditer(header):
        norm = lambda s: ",".join(t.strip() for t in s.split(",") if t.strip())  # noqa: E731
        out.append(AliasPair(
            output_index=norm(m.group(1)),
            param_number=int(m.group(2)),
            param_index=norm(m.group(3)),
            kind=m.group(4) or "may-alias"))
    return out


def collective_inventory(hlo_text: str) -> Dict[str, Dict[str, int]]:
    """Per-opcode collective counts + result-shape bytes.

    Anchored instruction opcodes only (``all-gather-start.3`` counts;
    a pass-named row like ``reduce-scatter-decomposer`` does not);
    async ``-start``/``-done`` pairs count ONCE, at the start row,
    under the base opcode; a collective inside a ``while`` body counts
    once regardless of trip count (module docstring caveat).  Bytes
    are the counted row's result-shape bytes — for an async start
    whose shape is an (operand, result) tuple this over-counts by the
    operand copy, which is the conservative direction."""
    inv: Dict[str, Dict[str, int]] = {}
    for _name, shape, opcode in parse_instructions(hlo_text):
        m = _COLLECTIVE_RE.match(opcode)
        if m is None or m.group(2) == "-done":
            continue
        slot = inv.setdefault(m.group(1), {"count": 0, "bytes": 0})
        slot["count"] += 1
        slot["bytes"] += _shape_bytes(shape)
    return inv


@dataclasses.dataclass(frozen=True)
class HostOp:
    """One host-interaction op: infeed/outfeed/send/recv, or a custom
    call whose target is a host callback."""

    opcode: str
    name: str
    target: str = ""

    def to_json(self) -> Dict[str, Any]:
        d = {"opcode": self.opcode, "name": self.name}
        if self.target:
            d["target"] = self.target
        return d


def host_interaction_ops(hlo_text: str) -> List[HostOp]:
    """Every host-interaction op in the program.  ``-done`` halves of
    send/recv pairs are skipped (the pair counts once, like the
    collective inventory's async rule)."""
    out: List[HostOp] = []
    for line in hlo_text.splitlines():
        m = _INSTR_RE.match(line)
        if m is None:
            continue
        name, _shape, opcode = m.group(1), m.group(2), m.group(3)
        if opcode in _HOST_OPCODES:
            if opcode.endswith("-done"):
                continue
            out.append(HostOp(opcode=opcode, name=name))
        elif opcode == "custom-call":
            t = _TARGET_RE.search(line)
            target = t.group(1) if t else ""
            if any(h in target.lower() for h in _HOST_TARGET_HINTS):
                out.append(HostOp(opcode=opcode, name=name, target=target))
    return out


# ---------------------------------------------------------------------------
# The report
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ExecutableReport:
    """Structured contract-relevant view of one compiled executable."""

    name: str
    aliasing: List[AliasPair]
    collectives: Dict[str, Dict[str, int]]
    host_ops: List[HostOp]
    opcode_histogram: Dict[str, int]
    argument_bytes: int
    output_bytes: int
    temp_bytes: int
    flops: float

    def to_json(self) -> Dict[str, Any]:
        return {
            "name": self.name,
            "aliasing": [a.to_json() for a in self.aliasing],
            "collectives": {k: dict(v)
                            for k, v in sorted(self.collectives.items())},
            "host_ops": [h.to_json() for h in self.host_ops],
            "opcode_histogram": dict(sorted(
                self.opcode_histogram.items())),
            "argument_bytes": self.argument_bytes,
            "output_bytes": self.output_bytes,
            "temp_bytes": self.temp_bytes,
            "flops": self.flops,
        }


def executable_report(name: str, compiled) -> ExecutableReport:
    """Build the report for one ``jax.stages.Compiled``.

    Unlike the degrade-tolerant profiling helpers, an unavailable
    ``as_text`` RAISES here — a contract checker that cannot read the
    artifact must fail loudly (exit 2 at the CLI), never report an
    empty-and-therefore-clean inventory."""
    from apex_tpu.profiling import opcode_histogram_from_text

    text = compiled.as_text()
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):   # single-element list on old jax
        cost = cost[0] if cost else {}
    mem = compiled.memory_analysis()
    return ExecutableReport(
        name=name,
        aliasing=parse_aliases(text),
        collectives=collective_inventory(text),
        host_ops=host_interaction_ops(text),
        opcode_histogram=opcode_histogram_from_text(text),
        argument_bytes=int(getattr(mem, "argument_size_in_bytes", 0) or 0),
        output_bytes=int(getattr(mem, "output_size_in_bytes", 0) or 0),
        temp_bytes=int(getattr(mem, "temp_size_in_bytes", 0) or 0),
        flops=float(cost.get("flops", 0.0)),
    )


# ---------------------------------------------------------------------------
# Contracts
# ---------------------------------------------------------------------------


def check_contract(report: ExecutableReport,
                   contract: Dict[str, Any]) -> List[str]:
    """Violations of one executable's contract entry (empty = clean).

    Directions are deliberately one-sided: FEWER collectives than the
    max, MORE aliasing than required, and SMALLER temp than the
    ceiling all pass — the contract pins the floor of the properties
    the hot paths rely on, not an exact fingerprint (run ``--update``
    after a deliberate improvement to ratchet the maxima down)."""
    v: List[str] = []
    have = {(a.param_number, a.output_index) for a in report.aliasing}
    for req in contract.get("required_aliases", []):
        key = (int(req["param"]), str(req["output"]))
        if key not in have:
            v.append(
                f"aliasing: param {key[0]} no longer aliases output "
                f"{{{key[1]}}} — donation did not survive compilation")
    maxc = contract.get("max_collectives", {})
    for op, stat in sorted(report.collectives.items()):
        cap = int(maxc.get(op, 0))
        if stat["count"] > cap:
            v.append(
                f"collectives: {op} x{stat['count']} exceeds the "
                f"contract max of {cap}")
    allow = contract.get("allow_host_ops", [])
    for h in report.host_ops:
        # an allow entry naming a host OPCODE matches only that exact
        # opcode; any other entry is a custom-call target pattern
        # (substring).  Without the split, a blessed `send` op would
        # silently whitelist any host callback whose target happens to
        # contain "send" — the opposite of surface-the-ambiguity.
        ok = any((a == h.opcode) if a in _HOST_OPCODES
                 else bool(h.target and a and a in h.target)
                 for a in allow)
        if not ok:
            extra = f" target={h.target!r}" if h.target else ""
            v.append(
                f"host interaction: {h.opcode} %{h.name}{extra} is not "
                "allowed by the contract")
    cap = contract.get("max_temp_bytes")
    if cap is not None and report.temp_bytes > int(cap):
        v.append(
            f"temp bytes {report.temp_bytes:,} exceed the contract "
            f"ceiling {int(cap):,}")
    return v


def contract_from_report(report: ExecutableReport, *,
                         temp_headroom: float = 1.25) -> Dict[str, Any]:
    """The ``--update`` generator: a contract entry pinning exactly
    what the current artifact delivers (observed aliases required,
    observed collective counts as maxima, observed host ops allowed —
    review the diff before committing), with ``temp_headroom`` slack
    on the temp-byte ceiling so layout jitter doesn't flap the gate.
    The ``inventory`` block is informational provenance (byte counts,
    flops) — the checker ignores it; ROADMAP item 3 reads it."""
    return {
        "required_aliases": [a.to_json() for a in report.aliasing],
        "max_collectives": {op: s["count"] for op, s in
                            sorted(report.collectives.items())},
        "allow_host_ops": sorted({h.target or h.opcode
                                  for h in report.host_ops}),
        "max_temp_bytes": int(math.ceil(report.temp_bytes * temp_headroom)),
        "inventory": {
            "collective_bytes": {op: s["bytes"] for op, s in
                                 sorted(report.collectives.items())},
            "argument_bytes": report.argument_bytes,
            "output_bytes": report.output_bytes,
            "temp_bytes": report.temp_bytes,
            "flops": report.flops,
        },
    }


def load_contracts(path: str) -> Dict[str, Any]:
    """Read + validate a contracts file; any problem raises
    :class:`ContractFileError` (CLI exit 2 — never a green pass)."""
    if not os.path.isfile(path):
        raise ContractFileError(f"contracts file not found: {path}")
    try:
        with open(path) as f:
            doc = json.load(f)
    except (json.JSONDecodeError, UnicodeDecodeError) as e:
        raise ContractFileError(f"unparseable contracts file {path}: {e}")
    if doc.get("format") != CONTRACTS_FORMAT:
        raise ContractFileError(
            f"unknown contracts format {doc.get('format')!r} in {path}")
    if not isinstance(doc.get("executables"), dict):
        raise ContractFileError(f"{path} has no 'executables' table")
    if not isinstance(doc.get("geometry"), str) or not doc["geometry"]:
        raise ContractFileError(
            f"{path} carries no geometry provenance stamp — contract "
            "numbers without a geometry read as flagship-scale truth")
    return doc


def save_contracts(path: str, reports: Dict[str, ExecutableReport], *,
                   geometry: str = DEFAULT_GEOMETRY,
                   previous: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Write contracts generated from ``reports``; entries of
    ``previous`` for executables NOT in ``reports`` are carried over
    (the ``--update --only`` merge path)."""
    execs: Dict[str, Any] = {}
    if previous is not None:
        execs.update(previous.get("executables", {}))
    for name, rep in reports.items():
        execs[name] = contract_from_report(rep)
    doc = {
        "format": CONTRACTS_FORMAT,
        "geometry": geometry,
        "comment": (
            "Machine-written by `python -m apex_tpu.analysis hlo "
            "--update` (docs/analysis.md, 'Compiled-artifact "
            "contracts'). Byte/count numbers are measured at the "
            f"'{geometry}' registry geometry — gate fixtures, not "
            "flagship-scale truth."),
        "executables": {k: execs[k] for k in sorted(execs)},
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=False)
        f.write("\n")
    return doc


@dataclasses.dataclass
class CheckResult:
    """Outcome of one full registry-vs-contracts check.

    ``violations`` maps executable name → its contract violations;
    ``missing`` are registered executables with no contract entry
    (exit 2 — an ungated executable must not pass green); ``stale``
    are contract entries naming no registered executable (exit 1 —
    the PR 11 stale-baseline discipline: a contract cannot outlive
    its executable)."""

    violations: Dict[str, List[str]]
    missing: List[str]
    stale: List[str]

    @property
    def exit_code(self) -> int:
        if self.missing:
            return 2
        if any(self.violations.values()) or self.stale:
            return 1
        return 0

    def to_json(self) -> Dict[str, Any]:
        return {
            "violations": {k: list(v)
                           for k, v in sorted(self.violations.items()) if v},
            "missing": list(self.missing),
            "stale": list(self.stale),
            "exit_code": self.exit_code,
        }


def check_reports(reports: Dict[str, ExecutableReport],
                  doc: Dict[str, Any], *,
                  registry_names: Sequence[str]) -> CheckResult:
    """Diff built reports against a loaded contracts doc.

    ``registry_names`` is the FULL registry (staleness is judged
    against every registered executable, so a ``--only``-restricted
    run cannot misread an unselected executable's entry as stale)."""
    execs = doc.get("executables", {})
    violations = {name: check_contract(rep, execs[name])
                  for name, rep in sorted(reports.items())
                  if name in execs}
    missing = sorted(n for n in reports if n not in execs)
    stale = sorted(n for n in execs if n not in registry_names)
    return CheckResult(violations=violations, missing=missing, stale=stale)
