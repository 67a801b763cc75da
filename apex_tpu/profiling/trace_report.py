"""Per-op measured-runtime attribution from a captured profiler trace.

The half of pyprof the static cost report can't do (VERDICT r2 item 7):
reference ``apex/pyprof/prof/prof.py`` post-processes an nvprof SQLite
dump into a per-op table of *measured* kernel time joined with derived
flop/byte counts.  The XLA-world equivalent: ``jax.profiler`` writes a
TensorBoard/Perfetto profile whose ``*.trace.json.gz`` is Chrome
trace-event JSON with one complete event per executed op on the device
timeline.  :func:`parse_trace_dir` aggregates those events per op name;
:func:`top_ops_report` runs a callable under the profiler and returns the
top-k table — measured milliseconds, call counts, and share of device
time — the regression-finding tool the r2 verdict asked for (it flags
"LayerNorm fusion slower than XLA" automatically, because the op *name*
carries the named_scope/fusion identity).

No tensorboard/profile-plugin dependency: the gzip'd JSON is parsed
directly.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import gzip
import json
import os
import re
import tempfile
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import numpy as np

from apex_tpu.telemetry import scopes

__all__ = ["OpTime", "parse_trace_dir", "top_ops_report",
           "format_top_ops", "device_time_ms", "hlo_fusion_flops",
           "join_roofline", "PHASES", "classify_op", "PhaseReport",
           "phase_report", "flash_attention_flops"]


@dataclasses.dataclass
class OpTime:
    """Aggregated measured time for one op (fusion) name."""

    name: str
    total_ms: float
    calls: int
    frac_of_device: float  # share of all attributed device time

    @property
    def mean_us(self) -> float:
        return self.total_ms * 1e3 / max(self.calls, 1)


_SKIP_NAMES = re.compile(
    r"^(\$|process_|thread_|MemcpyD2H|MemcpyH2D|Memset|"
    r"RunGraph|Stream|Compile|Execute|TransferTo|xla::|pjrt)", re.I)
# whole-module execution spans, e.g. "jit_step(123...)": they duplicate
# every op inside them but sit on their own lane, so containment
# filtering can't drop them — drop by name shape
_MODULE_SPAN = re.compile(r"^jit_.*\(\d+\)$")


def _device_pid_names(trace: dict) -> Dict[int, str]:
    """pid -> process name from trace metadata events."""
    names: Dict[int, str] = {}
    for ev in trace.get("traceEvents", []):
        if ev.get("ph") == "M" and ev.get("name") == "process_name":
            names[ev.get("pid", -1)] = ev.get("args", {}).get("name", "")
    return names


def _leaf_events(events):
    """Keep only LEAF complete-events per (pid, tid) lane: an event that
    contains another event's interval is a container (a trace group, the
    jit module span, a step lane) and would double-count its children."""
    by_lane: Dict[Any, list] = collections.defaultdict(list)
    for ev in events:
        by_lane[(ev.get("pid"), ev.get("tid"))].append(ev)
    leaves = []
    for lane in by_lane.values():
        lane.sort(key=lambda e: (float(e.get("ts", 0.0)),
                                 -float(e.get("dur", 0.0))))
        open_evs = []  # (end_ts, event, became_parent)
        for ev in lane:
            ts = float(ev.get("ts", 0.0))
            end = ts + float(ev.get("dur", 0.0))
            while open_evs and open_evs[-1][0] <= ts:
                e, parent = open_evs.pop()[1:]
                if not parent:
                    leaves.append(e)
            if open_evs:
                open_evs[-1] = (open_evs[-1][0], open_evs[-1][1], True)
            open_evs.append((end, ev, False))
        for _, e, parent in open_evs:
            if not parent:
                leaves.append(e)
    return leaves


def _trace_leaf_groups(logdir: str, *, device_only: bool = True):
    """Yield one list of LEAF complete-events per trace file under
    ``logdir`` (timestamps are only mutually comparable within a file,
    so overlap analysis must stay per-group).  A generator on purpose:
    a multi-host capture can hold many ~1M-event files, and only one
    file's events should be resident at a time.  Device timeline only
    (pids whose process name mentions a device) unless
    ``device_only=False`` or no device pids exist (then: every
    non-metadata timeline)."""
    paths = glob.glob(os.path.join(logdir, "**", "*.trace.json.gz"),
                      recursive=True)
    paths += glob.glob(os.path.join(logdir, "**", "*.trace.json"),
                       recursive=True)
    for path in paths:
        opener = gzip.open if path.endswith(".gz") else open
        try:
            with opener(path, "rt") as f:
                trace = json.load(f)
        except Exception:
            continue
        pid_names = _device_pid_names(trace)
        device_pids = {p for p, n in pid_names.items()
                       if re.search(r"TPU|GPU|Device|/device:|Chip",
                                    n, re.I)}
        use_filter = device_only and bool(device_pids)
        pool = []
        for ev in trace.get("traceEvents", []):
            if ev.get("ph") != "X":
                continue
            if use_filter and ev.get("pid") not in device_pids:
                continue
            name = ev.get("name", "")
            if (not name or _SKIP_NAMES.match(name)
                    or _MODULE_SPAN.match(name)
                    or name.isdigit()):  # bare step-number lanes
                continue
            pool.append(ev)
        leaves = _leaf_events(pool)
        if leaves:
            yield leaves


def parse_trace_dir(logdir: str, *, device_only: bool = True
                    ) -> List[OpTime]:
    """Aggregate complete ('X') events from every ``*.trace.json.gz``
    under ``logdir`` into per-name totals, device timeline only (pids
    whose process name mentions a device) unless ``device_only=False``
    or no device pids exist (then: every non-metadata timeline).  Only
    *leaf* events count — containers (step lanes, module spans) hold
    their children's time and would double-count."""
    totals: Dict[str, float] = collections.defaultdict(float)
    counts: Dict[str, int] = collections.defaultdict(int)
    for leaves in _trace_leaf_groups(logdir, device_only=device_only):
        for ev in leaves:
            name = ev["name"]
            totals[name] += float(ev.get("dur", 0.0)) / 1e3  # us -> ms
            counts[name] += 1
    grand = sum(totals.values()) or 1.0
    out = [OpTime(name=n, total_ms=t, calls=counts[n],
                  frac_of_device=t / grand)
           for n, t in totals.items()]
    out.sort(key=lambda o: -o.total_ms)
    return out


# ---------------------------------------------------------------------------
# Phase classification + exposed-collective overlap (ISSUE 9 tentpole)
# ---------------------------------------------------------------------------

#: The closed phase vocabulary :func:`classify_op` maps device ops into.
#: ``matmul`` — MXU contractions (dot/convolution, and fusions whose HLO
#: body contains contraction flops); ``vector`` — everything elementwise
#: / VPU (the default bucket); ``collective`` — inter-chip communication;
#: ``copy`` — on-chip copies and D2D moves; ``infeed`` — host<->device
#: transfer (infeed/outfeed/send/recv); ``custom`` — opaque custom calls,
#: i.e. the handwritten Pallas kernels.
PHASES = ("matmul", "vector", "collective", "copy", "infeed", "custom")

def _opcode_re(opcodes, *, async_pair: bool = False):
    """ANCHORED instruction-name matcher: the opcode, an optional
    ``-start``/``-done`` (async pairs), then nothing or an HLO
    ``.suffix``.  Anchoring matters: CPU traces without device lanes
    leak XLA *compiler pass* rows (``all-reduce-promotion``,
    ``reduce-scatter-decomposer``) whose names merely start with a
    collective opcode — a bare prefix match would manufacture fake
    collective (and thus exposed-collective) time out of compile
    passes."""
    alts = "|".join(re.escape(o) for o in opcodes)
    tail = r"(-start|-done)?" if async_pair else ""
    return re.compile(r"^(?:%s)%s(\.\S*)?$" % (alts, tail))


_COLLECTIVE_RE = _opcode_re(
    ("all-reduce", "all-gather", "reduce-scatter", "collective-permute",
     "all-to-all", "collective-broadcast", "ragged-all-to-all"),
    async_pair=True)
_MATMUL_RE = _opcode_re(("dot", "dot-general", "convolution"))
_COPY_RE = _opcode_re(("copy",), async_pair=True)
_INFEED_RE = _opcode_re(
    ("infeed", "outfeed", "send", "recv", "host-transfer"),
    async_pair=True)
_CUSTOM_RE = _opcode_re(("custom-call", "tpu_custom_call"))


def classify_op(name: str, *, flops_map: Optional[Dict[str, tuple]] = None
                ) -> str:
    """Phase of one device op by its HLO instruction name.

    Anchored opcode rules cover the unambiguous cases (an async
    ``-start``/``-done`` pair classifies with its opcode:
    ``all-gather-start.3`` is a collective; a compiler-pass row like
    ``all-reduce-promotion`` is NOT).  Fusions are the ambiguous case —
    ``fusion.12`` says nothing — so when ``flops_map`` (the output of
    :func:`hlo_fusion_flops` for the same program) is supplied, a fusion
    with contraction flops classifies ``matmul`` and a flopless one
    ``vector``; without HLO text every fusion is ``vector`` (the
    conservative read: unattributed compute never inflates the MXU
    share).  Unmatched names default to ``vector``."""
    n = name.lower()
    if n.startswith("%"):
        n = n[1:]
    if _COLLECTIVE_RE.match(n):
        return "collective"
    if _CUSTOM_RE.match(n) or "mosaic" in n or "pallas" in n:
        return "custom"
    if _MATMUL_RE.match(n):
        return "matmul"
    if _COPY_RE.match(n):
        return "copy"
    if _INFEED_RE.match(n):
        return "infeed"
    if flops_map:
        hit = flops_map.get(name) or flops_map.get(name.split("(")[0])
        if hit is not None and hit[0] > 0:
            return "matmul"
    return "vector"


def _merge_intervals(iv: List[tuple]) -> List[tuple]:
    """Union of [start, end) intervals as a sorted disjoint list."""
    out: List[tuple] = []
    for s, e in sorted(i for i in iv if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _uncovered_length(a: List[tuple], b: List[tuple]) -> float:
    """Total length of ``a`` NOT covered by ``b`` (both already merged
    disjoint sorted interval lists) — the exposed-collective core."""
    total = 0.0
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while cur < e:
            if k >= len(b) or b[k][0] >= e:
                total += e - cur
                break
            bs, be = b[k]
            if bs > cur:
                total += min(bs, e) - cur
            cur = max(cur, be)
            k += 1
    return total


@dataclasses.dataclass
class PhaseReport:
    """Where a captured window's device milliseconds went.

    ``phase_ms`` sums leaf-op durations per phase (lanes run
    concurrently, so the phases can sum past ``span_ms``).
    ``collective_ms`` is the *union* wall of all collective intervals;
    ``exposed_collective_ms`` is the part of that union during which NO
    compute (matmul/vector/custom) op was running anywhere on the
    device timeline — the serialization cost overlap-aware ZeRO
    (ROADMAP item 3) exists to remove, measured rather than inferred."""

    phase_ms: Dict[str, float]
    exposed_collective_ms: float
    collective_ms: float
    total_ms: float          # sum of all leaf-op durations
    span_ms: float           # timeline extent (first start -> last end)
    n_ops: int
    top_ops: List[OpTime]

    def to_payload(self) -> Dict[str, Any]:
        """JSON-ready payload for the telemetry ``profile`` event."""
        return {
            "phase_ms": {k: round(v, 3) for k, v in self.phase_ms.items()},
            "exposed_collective_ms": round(self.exposed_collective_ms, 3),
            "collective_ms": round(self.collective_ms, 3),
            "total_device_ms": round(self.total_ms, 3),
            "span_ms": round(self.span_ms, 3),
            "n_ops": self.n_ops,
            "top_ops": [{"name": o.name, "ms": round(o.total_ms, 3),
                         "calls": o.calls} for o in self.top_ops],
        }


def phase_report(logdir: str, *, hlo_text: Optional[str] = None,
                 top: int = 5, device_only: bool = True) -> PhaseReport:
    """Classify every leaf device op in a captured trace into
    :data:`PHASES` and run the timeline overlap analysis.

    ``hlo_text`` (``compiled.as_text()`` of the profiled program) lets
    fusions classify as matmul-vs-vector by their contraction content;
    without it fusions count as ``vector``.

    Exposed-collective method: merge all collective leaf intervals into
    a union, merge all compute (matmul/vector/custom) leaf intervals
    into a union — across every lane, since a collective on one lane is
    hidden by compute on any other — and measure the collective union
    length left uncovered.  Timestamps are only comparable within one
    trace file, so the analysis runs per file and sums."""
    flops_map = hlo_fusion_flops(hlo_text) if hlo_text else None
    phase_ms: Dict[str, float] = {p: 0.0 for p in PHASES}
    totals: Dict[str, float] = collections.defaultdict(float)
    counts: Dict[str, int] = collections.defaultdict(int)
    exposed_us = coll_us = span_us = 0.0
    n_ops = 0
    for leaves in _trace_leaf_groups(logdir, device_only=device_only):
        coll_iv, compute_iv = [], []
        lo = hi = None
        for ev in leaves:
            name = ev["name"]
            dur = float(ev.get("dur", 0.0))
            ts = float(ev.get("ts", 0.0))
            phase = classify_op(name, flops_map=flops_map)
            phase_ms[phase] += dur / 1e3
            totals[name] += dur / 1e3
            counts[name] += 1
            n_ops += 1
            lo = ts if lo is None else min(lo, ts)
            hi = ts + dur if hi is None else max(hi, ts + dur)
            if phase == "collective":
                coll_iv.append((ts, ts + dur))
            elif phase in ("matmul", "vector", "custom"):
                compute_iv.append((ts, ts + dur))
        coll_u = _merge_intervals(coll_iv)
        comp_u = _merge_intervals(compute_iv)
        coll_us += sum(e - s for s, e in coll_u)
        exposed_us += _uncovered_length(coll_u, comp_u)
        if lo is not None:
            span_us += hi - lo
    grand = sum(totals.values()) or 1.0
    ranked = sorted(totals, key=lambda n: -totals[n])[:top]
    top_ops = [OpTime(name=n, total_ms=totals[n], calls=counts[n],
                      frac_of_device=totals[n] / grand) for n in ranked]
    return PhaseReport(
        phase_ms={k: v for k, v in phase_ms.items() if v > 0},
        exposed_collective_ms=exposed_us / 1e3,
        collective_ms=coll_us / 1e3,
        total_ms=sum(totals.values()),
        span_ms=span_us / 1e3,
        n_ops=n_ops,
        top_ops=top_ops,
    )


def top_ops_report(fn: Callable, *args, steps: int = 3,
                   logdir: Optional[str] = None, top: Optional[int] = 10,
                   **kwargs) -> List[OpTime]:
    """Run ``fn(*args, **kwargs)`` ``steps`` times under the profiler and
    return the top-k ops by measured device time (pyprof prof.py's
    output table, TPU-native); ``top=None`` returns every parsed op.
    ``fn`` should already be jitted and warmed (compile inside the trace
    would dominate)."""
    owndir = logdir is None
    logdir = logdir or tempfile.mkdtemp(prefix="apex_tpu_prof_")
    try:
        # host tracer OFF: host activity can emit >1M events per step,
        # and the trace writer caps at ~1M events TOTAL — a host-spammed
        # window evicts the entire device timeline and the parse
        # silently returns zero ops (observed r5).  Only device events
        # are consumed here.
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 0
        opts.python_tracer_level = 0
        jax.profiler.start_trace(logdir, profiler_options=opts)
        try:
            out = None
            for _ in range(steps):
                out = fn(*args, **kwargs)
            jax.block_until_ready(out)
            # a value fetch as well: the capture ends on a result
            # the host has seen
            for leaf in jax.tree_util.tree_leaves(out):
                if hasattr(leaf, "astype"):
                    float(abs(leaf).max())
                    break
        finally:
            jax.profiler.stop_trace()
        return parse_trace_dir(logdir)[:top]
    finally:
        if owndir:
            import shutil

            shutil.rmtree(logdir, ignore_errors=True)


def device_time_ms(fn: Callable, *args, steps: int = 4,
                   exclude: Sequence[str] = ("copy",), **kwargs) -> float:
    """Total *device* milliseconds per invocation of ``fn`` — the sum of
    per-call leaf-op times from a profiler trace.  Immune to host-side
    dispatch noise (the multi-ms variable floor that poisoned the r3
    record): device timestamps come from the chip.  ``fn`` must be
    jitted and warmed.  Ops whose name starts with any ``exclude`` prefix
    (default: donation copies) are dropped.  Each op's TOTAL time is
    divided by the number of invocations (``steps``), NOT by its call
    count — an op inside a ``lax.scan``/remat body executes many times
    per invocation, and dividing by calls would count one body iteration
    instead of all of them.  Raises if the trace is empty, so callers
    can fall back to wall-clock timing."""
    # top=None: sum EVERY parsed op — a top-k cap here would silently
    # undercount device time for programs with many distinct fusions and
    # inflate speedups computed from the ratio
    ops = top_ops_report(fn, *args, steps=steps, top=None, **kwargs)
    tot = sum(o.total_ms for o in ops
              if not o.name.startswith(tuple(exclude))) / steps
    if tot <= 0:
        raise RuntimeError("profiler trace contained no device ops")
    return tot


def _body_flops(body: str) -> float:
    """Matmul/conv flops inside one HLO computation body.

    Estimator: ``2 * sqrt(|A| * |B| * |O|)`` over the element counts of
    the two operands and the output — EXACT for any contraction where
    each logical dim appears in exactly two of the three tensors (plain
    and transposed matmuls, and XLA's conv-formulated weight-gradients),
    approximate for batched dots (over by sqrt(batch)) and spatial convs
    (under by sqrt(window)).  The same class of shape-heuristic as
    pyprof's prof/blas.py; adequate for ranking ops by
    distance-from-roof."""
    # first pass: instruction name -> element count (operand shapes live
    # on their DEFINING lines, not on the consuming dot/conv line)
    sizes: Dict[str, float] = {}
    def_re = re.compile(r"^\s*(?:ROOT )?%([\w.-]+) = \w+\[([\d,]*)\]")
    for line in body.splitlines():
        m = def_re.match(line)
        if m:
            shape = m.group(2)
            sizes[m.group(1)] = float(np.prod(
                [int(x) for x in shape.split(",") if x])) if shape else 1.0
    flops = 0.0
    # anchor the operand scan on the OPCODE's paren, not the first paren
    # after "= ": tuple-typed results ("%f = (f32[..], f32[..]) fusion(")
    # put a paren in the type position and would hijack the scan
    op_re = re.compile(r"\s(?:dot|dot-general|convolution)\(")
    name_re = re.compile(r"^\s*(?:ROOT )?%([\w.-]+) = ")
    shape_re = re.compile(r"\[([\d,]*)\]")
    for line in body.splitlines():
        om = op_re.search(line)
        if om is None:
            continue
        nm = name_re.match(line)
        if nm is None:
            continue
        out_sz = sizes.get(nm.group(1))
        if out_sz is None:
            # tuple-typed result: size from the first shape literal in
            # the type position (before the opcode)
            sm = shape_re.search(line[:om.start()])
            if sm is None:
                continue
            shape = sm.group(1)
            out_sz = float(np.prod(
                [int(x) for x in shape.split(",") if x])) if shape else 1.0
        call = line[om.end() - 1:]
        operands = re.findall(r"%([\w.-]+)", call.split(")")[0])
        ops_sz = [sizes.get(o) for o in operands[:2]]
        if len(ops_sz) < 2 or None in ops_sz:
            continue
        flops += 2.0 * float(np.sqrt(out_sz * ops_sz[0] * ops_sz[1]))
    return flops


def hlo_fusion_flops(hlo_text: str) -> Dict[str, tuple]:
    """instruction/computation name -> (estimated matmul/conv flops,
    op_name metadata), parsed from compiled HLO text
    (``lowered.compile().as_text()``).  The op_name carries the
    jax-level trace path (module/op/source), turning anonymous
    ``fusion.NN`` trace rows into attributable ops — the identity the
    reference pyprof recovers from NVTX ranges.

    Flops are counted RECURSIVELY through called computations, so
    checkpoint/remat/call spans (the dominant rows of a remat'd step's
    profile) get their contained matmul flops too, not just leaf
    fusions.  A ``while`` body's flops are counted once (the static
    trip count is not recoverable from HLO text) — an undercount for
    loops, stated here rather than hidden."""
    # the computation splitter and the caller / op_name patterns are
    # telemetry.scopes's: the tree's one parser of optimized-HLO text
    bodies: Dict[str, str] = scopes.computations(hlo_text)

    _ITEM = {"f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
             "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
             "s8": 1, "u8": 1, "pred": 1, "f8": 1}

    def comp_bytes(comp: str) -> float:
        """HBM traffic estimate for one executed computation: its
        parameter + result tensors (each read/written once — the
        fusion boundary traffic; in-body temporaries stay in
        registers/VMEM)."""
        body = bodies.get(comp)
        if body is None:
            return 0.0
        sig = body[:body.find("{")]
        total = 0.0
        for t, shape in re.findall(r"(\w+)\[([\d,]*)\]", sig):
            n = float(np.prod([int(x) for x in shape.split(",") if x])) \
                if shape else 1.0
            total += n * _ITEM.get(t, 4)
        return total

    memo: Dict[str, float] = {}

    def comp_flops(comp: str, stack=()) -> float:
        if comp in memo:
            return memo[comp]
        if comp in stack:  # defensive: HLO call graphs are acyclic
            return 0.0
        body = bodies.get(comp)
        if body is None:
            return 0.0
        total = _body_flops(body)
        for m in scopes.CALLER_RE.finditer(body):
            total += comp_flops(m.group(2), stack + (comp,))
        memo[comp] = total
        return total

    out: Dict[str, tuple] = {}
    for m in scopes.CALLER_RE.finditer(hlo_text):
        inst, comp = m.group(1), m.group(2)
        line = hlo_text[m.start():hlo_text.find("\n", m.start())]
        nm = scopes.OP_NAME_RE.search(line)
        out.setdefault(inst, (comp_flops(comp), comp_bytes(comp),
                              nm.group(1) if nm else ""))
    for comp in bodies:  # trace rows sometimes carry the COMPUTATION name
        out.setdefault(comp, (comp_flops(comp), comp_bytes(comp), ""))
    # every remaining instruction still gets its op_name label — custom
    # calls (Pallas kernels) are opaque to flops parsing (est 0, like
    # XLA's own cost analysis) but their source identity matters most:
    # they ARE the handwritten kernels being judged
    for m in scopes.NAMED_INSTRUCTION_RE.finditer(hlo_text):
        out.setdefault(m.group(1), (0.0, 0.0, m.group(2)))
    return out


def flash_attention_flops(batch_heads: int, seq: int, head_dim: int, *,
                          causal: bool = False,
                          backward: bool = False) -> float:
    """Analytic matmul flops of one flash-attention invocation — the
    documented per-op override for the 5×-under-report caveat
    (docs/profiling.md): XLA cost analysis and the HLO flops parser both
    see a Pallas custom call as opaque (flops 0), but the kernel's
    contraction content is exactly two s×s×d matmuls (qkᵀ and pv) per
    (batch, head) row forward — 2.5× that fwd+bwd (dq, dk, dv plus the
    recomputed score matmuls).  ``causal`` halves the density."""
    f = 2 * 2 * batch_heads * seq * seq * head_dim
    if causal:
        f /= 2
    return f * 2.5 if backward else f


def _override_flops(name: str, op_name: str,
                    overrides: Optional[Dict[str, float]]) -> Optional[float]:
    """Per-call analytic flops for an op whose HLO content is opaque:
    the first ``overrides`` key found as a substring of the op_name
    metadata (the jax trace path — where kernel identity lives) or the
    instruction name wins."""
    if not overrides:
        return None
    for pat, fl in overrides.items():
        if pat in op_name or pat in name:
            return float(fl)
    return None


def join_roofline(ops: Sequence[OpTime], hlo_text: str,
                  roof_tflops: Optional[float] = None,
                  flop_overrides: Optional[Dict[str, float]] = None
                  ) -> List[dict]:
    """pyprof prof/output.py parity (measured time JOINED with derived
    flops): each measured op gains estimated flops, achieved TFLOPS, and
    fraction-of-roof.  Ops with no matmul/conv content get flops 0 —
    unless ``flop_overrides`` ({op_name substring: analytic flops per
    call}) supplies the number the HLO can't: Pallas custom calls are
    opaque to the flops parser, so a flash-attention row would otherwise
    read 0 flops and the 5× under-report caveat applies.  Overridden
    rows carry ``"flops_src": "override"`` so the provenance is in the
    record, not just the method."""
    fl = hlo_fusion_flops(hlo_text)
    rows = []
    for o in ops:
        f, nbytes, op_name = fl.get(o.name, (0.0, 0.0, ""))
        overridden = False
        if f == 0.0:
            ov = _override_flops(o.name, op_name, flop_overrides)
            if ov is not None:
                f, overridden = ov, True
        t = o.total_ms / max(o.calls, 1) / 1e3
        tf = f / t / 1e12 if t > 0 else 0.0
        row = {"name": o.name, "ms": round(o.total_ms / max(o.calls, 1), 3),
               "calls": o.calls, "frac_of_device": round(o.frac_of_device, 3),
               "est_gflops": round(f / 1e9, 2), "achieved_tflops": round(tf, 1)}
        if nbytes and t > 0:
            # boundary-traffic bandwidth: the roofline's other axis —
            # bandwidth-bound ops show GB/s near the HBM roof with low TF
            row["est_mb"] = round(nbytes / 1e6, 1)
            row["achieved_gb_s"] = round(nbytes / t / 1e9, 1)
        if op_name:
            # keep the informative tail (op + source), not the jit prefix
            row["op"] = op_name[-80:]
        if overridden:
            row["flops_src"] = "override"
        if roof_tflops:
            row["frac_of_roof"] = round(tf / roof_tflops, 3)
        rows.append(row)
    return rows


def format_top_ops(ops: Sequence[OpTime], *, top: int = 10) -> str:
    """pyprof prof/output.py-style table."""
    lines = [f"{'op (fusion) name':<56} {'ms':>9} {'calls':>6} {'%dev':>6}"]
    for o in list(ops)[:top]:
        name = o.name if len(o.name) <= 55 else o.name[:52] + "..."
        lines.append(
            f"{name:<56} {o.total_ms:9.3f} {o.calls:6d} "
            f"{100 * o.frac_of_device:5.1f}%")
    return "\n".join(lines)
