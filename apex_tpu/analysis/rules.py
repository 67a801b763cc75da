"""The rule catalog: this repo's hard-won invariants as lint rules.

Every rule encodes an incident this codebase actually paid for (the
catalog with full war stories is docs/analysis.md):

- **HS001** — host sync in a hot path.  PR 4's accountant exists
  because per-step scalar fetches serialize pipelined dispatch; the
  contract is ONE batched ``device_get`` per logging window.  A stray
  ``.item()`` / ``jax.device_get`` / ``block_until_ready`` /
  ``np.asarray`` inside a jitted function or one of the named hot
  loops (serving decode, resilient-training step loop) reintroduces
  exactly that stall.
- **ND001** — unseeded nondeterminism in a bitwise-contract module.
  ``serving/``, ``data/``, ``checkpoint/`` and ``multi_tensor/`` all
  pin bitwise reproducibility (batched==sequential decoding,
  exactly-once resume, reshard round trips); a bare ``random.*`` /
  ``np.random.*`` draw or a ``time.time()`` feeding logic breaks those
  contracts invisibly.  Seeded generators (``np.random.RandomState``,
  ``np.random.Philox``, ``jax.random.PRNGKey``) are the sanctioned
  forms.
- **DN001** — pool-sized jit call sites without donation.  PR 8's
  ``write_tokens`` lesson: an undonated scatter held old+new KV pool
  alive — ~768 MB of HBM per admission on the TTFT-critical path.
  Flag, don't guess: a ``jax.jit`` over a function with pool/state-
  sized parameters and no ``donate_argnums``/``donate`` is reported
  with the parameter names; the author decides (and a deliberate
  no-donate site says so with a kwarg or a baseline entry).
- **TL001** — telemetry emit sites are held to the single-sourced
  :data:`~apex_tpu.telemetry.schema.EVENT_FIELDS` table: unknown event
  types, literal field names outside the spec, and int-literals where
  the schema says bool (the PR 4 bool-not-int discipline) are all
  build-time errors now, not stream-validation surprises later.
- **TH001** — lock discipline around thread boundaries.  The
  prefetcher/watchdog/async-writer pattern shares attributes between a
  worker thread and the caller; an attribute assigned on both sides of
  the boundary with either side outside a lock is a data race waiting
  for a scheduler change.
- **EX001** — exception swallowing in run loops.  A broad ``except``
  whose body is just ``pass``/``continue`` inside a loop turns a hard
  fault into a silent skip-forever; sinks and teardown paths
  (``close``/``__exit__``/…) are the documented exception.

Rules are pure AST walkers — nothing here imports jax or the checked
modules.  TL001 imports :mod:`apex_tpu.telemetry.schema`, which is
deliberately stdlib-only.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, Iterable, List, Optional, Set, Tuple

from apex_tpu.analysis.framework import (Finding, Rule, call_attr,
                                         call_name, dotted_name,
                                         walk_functions)

# ---------------------------------------------------------------------------
# HS001 — host sync in a hot path
# ---------------------------------------------------------------------------

_JIT_NAMES = {"jax.jit", "jit"}
_PARTIAL_NAMES = {"functools.partial", "partial"}
_NP_ROOTS = ("np", "numpy", "onp")

#: Named hot loops that are not jit-decorated but ARE the steady-state
#: path (the serving decode loop, the resilient-training step loop, the
#: accountant's fetch seam).  Nested helpers inherit hotness.
HOT_PATH_FUNCTIONS: Dict[str, Set[str]] = {
    "apex_tpu/serving/engine.py": {
        "_decode_batch", "_prefill_request", "_step_body",
        "_step_phases",
        # ISSUE 34: the landing of the launch in flight, the one place
        # a decode step's tokens reach the host
        "_land",
        # ISSUE 12: the speculative verify step, the chunked-prefill
        # step, and the draft-proposal loop run at every decode
        # boundary — same steady-state heat as _decode_batch
        "_verify_batch", "_chunk_step", "_propose_drafts",
        # r19: span emission rides retirement and the decode loop —
        # tracing must stay pure host bookkeeping, never a device pull
        "_retire", "_emit_retire_spans"},
    "apex_tpu/serving/kv_cache.py": {"_page_digest"},
    # ISSUE 12: proposer lookup (per decode boundary per request) and
    # the chunk splitter (per boundary)
    "apex_tpu/serving/spec/proposer.py": {"propose", "_reindex"},
    "apex_tpu/serving/scheduler.py": {"schedule_prefill"},
    # ISSUE 16: the fleet round — placement, health probing, and the
    # migration hop all run between engine steps; a host sync or a
    # device pull here stalls EVERY replica, not one
    "apex_tpu/serving/fleet/router.py": {
        "route", "_migrate_requests", "_health_check"},
    # r18: every cross-replica payload serializes/delivers through the
    # transport, and the disaggregation pump drives page shipments
    # every fleet round — pure host json/zlib/base64 work; a device
    # pull here would stall the whole fleet per message
    "apex_tpu/serving/fleet/transport.py": {"call", "deliver"},
    # r19: the ship/import span emitters and the page handlers run per
    # wire message inside the pump — tracing overhead must stay host-
    # side (and sync-free) at the same heat as the pump itself
    "apex_tpu/serving/fleet/disagg.py": {
        "_pump_disagg", "_drive", "_emit_ship_span",
        "on_page", "on_commit"},
    "apex_tpu/transformer/testing/train_loop.py": {
        "run_resilient_training"},
    "apex_tpu/resilience/elastic.py": {"run_elastic_training"},
    "apex_tpu/telemetry/accounting.py": {"step_done", "fetch_scalars"},
    # ISSUE 15: the bucketed-overlap ZeRO data path — the per-bucket
    # scatter/update/gather walk and the flagship's fused inner step
    # run every training step; the planner runs at build time but its
    # output is closed over in jit, so it must stay host-sync-free too
    "apex_tpu/multi_tensor/buckets.py": {"plan_buckets"},
    "apex_tpu/contrib/optimizers/distributed_fused.py": {"step_buckets"},
    "apex_tpu/transformer/testing/flagship.py": {"_bucketed_zero_inner"},
}


def _decorator_is_jit(dec: ast.AST) -> bool:
    name = dotted_name(dec)
    if name in _JIT_NAMES:
        return True
    if isinstance(dec, ast.Call):
        fname = call_name(dec)
        if fname in _JIT_NAMES:
            return True
        if fname in _PARTIAL_NAMES and dec.args:
            return dotted_name(dec.args[0]) in _JIT_NAMES
    return False


def _jitted_function_names(tree: ast.AST) -> Set[str]:
    """Names X for every ``jax.jit(X, …)`` call site in the module —
    local defs later wrapped (``self._decode_fn = jax.jit(_decode,
    donate_argnums=…)``) are hot even though undecorated."""
    out: Set[str] = set()
    for node in ast.walk(tree):
        if (isinstance(node, ast.Call) and call_name(node) in _JIT_NAMES
                and node.args and isinstance(node.args[0], ast.Name)):
            out.add(node.args[0].id)
    return out


class HostSyncInHotPath(Rule):
    id = "HS001"
    title = "host sync in a hot path"
    rationale = (
        "PR 4 one-fetch-per-window: per-step device fetches serialize "
        "pipelined dispatch; inside @jax.jit they are trace-time bugs")

    SYNC_CALLS = {"jax.device_get", "device_get",
                  "jax.block_until_ready"}
    # attribute-matched forms catch aliased imports too (`import jax
    # as _jax; _jax.device_get(...)` — found the hard way in the train
    # loop's log path on this rule's first run)
    SYNC_ATTRS = {"block_until_ready", "device_get"}
    NP_PULLS = {f"{r}.{fn}" for r in _NP_ROOTS
                for fn in ("asarray", "ascontiguousarray", "array")}

    def check(self, tree: ast.AST, source: str,
              path: str) -> Iterable[Finding]:
        jitted = _jitted_function_names(tree)
        table = HOT_PATH_FUNCTIONS.get(path, set())
        findings: List[Finding] = []
        rule = self

        class V(ast.NodeVisitor):
            def __init__(self):
                self.hot: List[str] = []   # stack of hot function names

            def _is_hot_def(self, node) -> bool:
                if any(_decorator_is_jit(d) for d in node.decorator_list):
                    return True
                return node.name in jitted or node.name in table

            def visit_FunctionDef(self, node):
                entered = bool(self.hot) or self._is_hot_def(node)
                if entered:
                    self.hot.append(node.name)
                self.generic_visit(node)
                if entered:
                    self.hot.pop()

            visit_AsyncFunctionDef = visit_FunctionDef

            def visit_Call(self, node):
                if self.hot:
                    name = call_name(node)
                    attr = call_attr(node)
                    expr = name or (f"….{attr}" if attr else "?")
                    if (attr == "item" and not node.args) \
                            or name in rule.SYNC_CALLS \
                            or attr in rule.SYNC_ATTRS:
                        findings.append(rule.finding(
                            path, node,
                            f"host sync `{expr}()` inside hot path "
                            f"`{self.hot[0]}` — the contract is one "
                            "batched fetch per logging window "
                            "(StepAccountant), and inside @jax.jit a "
                            "host sync is a trace-time bug", source))
                    elif name in rule.NP_PULLS:
                        findings.append(rule.finding(
                            path, node,
                            f"`{name}(…)` inside hot path "
                            f"`{self.hot[0]}` forces a device→host "
                            "copy when fed a device value — fetch once "
                            "per window, or keep the value on device",
                            source))
                self.generic_visit(node)

        V().visit(tree)
        return findings


# ---------------------------------------------------------------------------
# ND001 — unseeded nondeterminism in bitwise-contract modules
# ---------------------------------------------------------------------------

#: Modules carrying a bitwise contract (batched==sequential serving,
#: exactly-once data resume, reshard round trips, flat-buffer math).
CONTRACT_DIRS = ("apex_tpu/serving/", "apex_tpu/data/",
                 "apex_tpu/checkpoint/", "apex_tpu/multi_tensor/")

#: Explicit-generator constructors: seeded at the call site, fine.
_SEEDED_NP = {"RandomState", "Generator", "Philox", "PCG64", "SFC64",
              "MT19937", "default_rng", "SeedSequence", "BitGenerator"}
_SEEDED_RANDOM = {"Random", "SystemRandom"}


class UnseededNondeterminism(Rule):
    id = "ND001"
    title = "unseeded nondeterminism in a bitwise-contract module"
    rationale = (
        "serving/data/checkpoint/multi_tensor pin bitwise claims "
        "(batched==sequential, exactly-once resume, reshard round "
        "trips); global RNG state or wall-clock-in-logic breaks them "
        "invisibly")

    def check(self, tree: ast.AST, source: str,
              path: str) -> Iterable[Finding]:
        if not any(d in path for d in CONTRACT_DIRS):
            return []
        findings: List[Finding] = []
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            name = call_name(node)
            if name is None:
                continue
            if name == "time.time":
                findings.append(self.finding(
                    path, node,
                    "`time.time()` in a bitwise-contract module — "
                    "wall clock in logic is unseeded nondeterminism; "
                    "use an injected clock (SimClock) or "
                    "`time.monotonic` for durations-only", source))
                continue
            parts = name.split(".")
            if parts[0] == "random" and len(parts) == 2 \
                    and parts[1] not in _SEEDED_RANDOM:
                findings.append(self.finding(
                    path, node,
                    f"global-state `{name}()` in a bitwise-contract "
                    "module — use an explicit seeded generator "
                    "(`random.Random(seed)`)", source))
            elif (len(parts) == 3 and parts[0] in _NP_ROOTS
                    and parts[1] == "random"
                    and parts[2] not in _SEEDED_NP):
                findings.append(self.finding(
                    path, node,
                    f"global-state `{name}()` in a bitwise-contract "
                    "module — use an explicit seeded generator "
                    "(`np.random.RandomState(seed)` / "
                    "`np.random.Generator(np.random.Philox(seed))`)",
                    source))
        return findings


# ---------------------------------------------------------------------------
# DN001 — pool-sized jit call sites without donation
# ---------------------------------------------------------------------------

_POOL_PARAM_RE = re.compile(r"pool|cache|buffer", re.IGNORECASE)
_POOL_PARAM_EXACT = {"opt_state"}
_DONATE_KWARGS = {"donate_argnums", "donate_argnames", "donate"}


class MissingDonation(Rule):
    id = "DN001"
    title = "pool/state-sized jit without buffer donation"
    rationale = (
        "PR 8 write_tokens: an undonated pool scatter held old+new "
        "pool alive (~768 MB at bench geometry) per admission on the "
        "TTFT-critical path")

    def _params_of(self, tree: ast.AST, arg0: ast.AST) -> Tuple[str, List[str]]:
        """(label, parameter names) of the jitted callable, when it is
        resolvable statically (a module-local def or a lambda)."""
        if isinstance(arg0, ast.Lambda):
            return "<lambda>", [a.arg for a in arg0.args.args]
        if isinstance(arg0, ast.Name):
            for node in ast.walk(tree):
                if isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)) \
                        and node.name == arg0.id:
                    return node.name, [a.arg for a in node.args.args]
        return "", []

    def check(self, tree: ast.AST, source: str,
              path: str) -> Iterable[Finding]:
        findings: List[Finding] = []
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and call_name(node) in _JIT_NAMES and node.args):
                continue
            if any(kw.arg in _DONATE_KWARGS for kw in node.keywords):
                continue  # the author decided — even donate=() on CPU
            label, params = self._params_of(tree, node.args[0])
            hits = [p for p in params
                    if _POOL_PARAM_RE.search(p)
                    or p in _POOL_PARAM_EXACT]
            if hits:
                findings.append(self.finding(
                    path, node,
                    f"jax.jit of `{label}` takes pool/state-sized "
                    f"buffer parameter(s) {hits} with no donate_argnums"
                    " — without donation the old and new buffers are "
                    "both live across the call (flag-don't-guess: say "
                    "`donate_argnums=()` if no-donate is deliberate)",
                    source))
        return findings


# ---------------------------------------------------------------------------
# TL001 — telemetry emit sites vs the single-sourced schema table
# ---------------------------------------------------------------------------


class TelemetrySchemaDrift(Rule):
    id = "TL001"
    title = "telemetry emit site drifts from the schema table"
    rationale = (
        "the PR 4 closed event set + bool-not-int discipline, enforced "
        "at lint time from telemetry/schema.py EVENT_FIELDS (the same "
        "table validate_event consumes — one source, no drift)")

    #: The stamp kwarg every emit may pass; not a payload field.
    STAMP_KWARGS = {"step"}

    def __init__(self, event_fields=None):
        if event_fields is None:
            from apex_tpu.telemetry.schema import EVENT_FIELDS

            event_fields = EVENT_FIELDS
        self.event_fields = event_fields

    def _check_literal(self, etype: str, field: str, value: ast.AST,
                       types: tuple) -> Optional[str]:
        if not isinstance(value, ast.Constant):
            return None
        v = value.value
        if isinstance(v, bool):
            if bool not in types:
                return (f"`{etype}.{field}` is "
                        f"{'/'.join(t.__name__ for t in types)} in the "
                        f"schema, got bool literal {v!r}")
            return None
        if v is None:
            if type(None) not in types:
                return (f"`{etype}.{field}` does not allow None in the "
                        "schema (optional means ABSENT, not null)")
            return None
        if isinstance(v, int) and bool in types and int not in types:
            return (f"int literal `{v}` for bool field "
                    f"`{etype}.{field}` — bool-not-int discipline: "
                    f"write {bool(v)}")
        if not isinstance(v, types):
            return (f"`{etype}.{field}` is "
                    f"{'/'.join(t.__name__ for t in types)} in the "
                    f"schema, got {type(v).__name__} literal {v!r}")
        return None

    def check(self, tree: ast.AST, source: str,
              path: str) -> Iterable[Finding]:
        findings: List[Finding] = []
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            attr = call_attr(node)
            name = call_name(node)
            is_emit = attr == "emit" or attr == "_emit" \
                or name in ("emit", "_emit")
            if not is_emit or not node.args:
                continue
            first = node.args[0]
            if not (isinstance(first, ast.Constant)
                    and isinstance(first.value, str)):
                continue  # dynamic type (a forwarding wrapper) — skip
            etype = first.value
            if etype not in self.event_fields:
                findings.append(self.finding(
                    path, node,
                    f"unknown telemetry event type {etype!r} — the "
                    "event set is closed; add a field spec to "
                    "telemetry/schema.py EVENT_FIELDS first", source))
                continue
            spec = self.event_fields[etype]
            for kw in node.keywords:
                if kw.arg is None or kw.arg in self.STAMP_KWARGS:
                    continue
                if kw.arg not in spec:
                    findings.append(self.finding(
                        path, node,
                        f"field `{kw.arg}` is not in the schema table "
                        f"for `{etype}` — add it to EVENT_FIELDS "
                        "(typed, required or optional) instead of "
                        "emitting untyped payload", source))
                    continue
                msg = self._check_literal(etype, kw.arg, kw.value,
                                          spec[kw.arg].types)
                if msg:
                    findings.append(self.finding(path, node, msg,
                                                 source))
        return findings


# ---------------------------------------------------------------------------
# TH001 — lock discipline across thread boundaries
# ---------------------------------------------------------------------------

_THREAD_NAMES = {"threading.Thread", "Thread"}
_LOCK_RE = re.compile(r"lock|mutex", re.IGNORECASE)


def _attr_store_target(target: ast.AST) -> Optional[str]:
    """``self.x = …`` -> ``x``; ``self.x[i] = …`` -> ``x``; else None."""
    if isinstance(target, ast.Subscript):
        target = target.value
    if isinstance(target, ast.Attribute) \
            and isinstance(target.value, ast.Name) \
            and target.value.id == "self":
        return target.attr
    return None


def _is_lock_ctx(expr: ast.AST) -> bool:
    """``with self._lock:`` / ``with lock:`` / ``with self._lock
    .acquire_timeout(…):`` — anything whose dotted name smells like a
    lock counts as holding one."""
    name = dotted_name(expr)
    if name is None and isinstance(expr, ast.Call):
        name = dotted_name(expr.func)
    return bool(name and _LOCK_RE.search(name))


def _self_attr_stores(fn: ast.AST) -> Dict[str, List[Tuple[ast.AST, bool]]]:
    """attr -> [(node, under_lock)] for every ``self.attr`` store in
    ``fn`` (nested defs included — they run on the same thread)."""
    out: Dict[str, List[Tuple[ast.AST, bool]]] = {}

    def rec(node: ast.AST, locked: bool):
        if isinstance(node, ast.With):
            item_locked = locked or any(_is_lock_ctx(i.context_expr)
                                        for i in node.items)
            for child in node.body:
                rec(child, item_locked)
            return
        targets: List[ast.AST] = []
        if isinstance(node, ast.Assign):
            targets = node.targets
        elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
            targets = [node.target]
        for t in targets:
            attr = _attr_store_target(t)
            if attr is not None:
                out.setdefault(attr, []).append((node, locked))
        for child in ast.iter_child_nodes(node):
            rec(child, locked)

    for stmt in fn.body:
        rec(stmt, False)
    return out


class LockDiscipline(Rule):
    id = "TH001"
    title = "attribute written on both sides of a thread boundary "\
            "without a lock"
    rationale = (
        "the prefetcher/watchdog/async-writer pattern: worker thread "
        "and caller share attributes — a store on either side outside "
        "the shared lock is a data race")

    def check(self, tree: ast.AST, source: str,
              path: str) -> Iterable[Finding]:
        findings: List[Finding] = []
        for cls in [n for n in ast.walk(tree)
                    if isinstance(n, ast.ClassDef)]:
            methods = {n.name: n for n in cls.body
                       if isinstance(n, (ast.FunctionDef,
                                         ast.AsyncFunctionDef))}
            workers: List[ast.AST] = []
            for m in methods.values():
                for node in ast.walk(m):
                    if not (isinstance(node, ast.Call)
                            and call_name(node) in _THREAD_NAMES):
                        continue
                    target = next((kw.value for kw in node.keywords
                                   if kw.arg == "target"), None)
                    if target is None:
                        continue
                    tname = dotted_name(target)
                    if tname and tname.startswith("self.") \
                            and tname[5:] in methods:
                        workers.append(methods[tname[5:]])
                    elif isinstance(target, ast.Name):
                        for sub in ast.walk(m):
                            if isinstance(sub, (ast.FunctionDef,
                                                ast.AsyncFunctionDef)) \
                                    and sub.name == target.id:
                                workers.append(sub)
            if not workers:
                continue
            # one level of self-method calls from each worker: the
            # thread body often delegates (`Watchdog._run -> _fire`)
            seen = {id(w) for w in workers}
            for w in list(workers):
                for node in ast.walk(w):
                    if isinstance(node, ast.Call):
                        nm = call_name(node)
                        if nm and nm.startswith("self.") \
                                and nm[5:] in methods \
                                and id(methods[nm[5:]]) not in seen:
                            workers.append(methods[nm[5:]])
                            seen.add(id(methods[nm[5:]]))
            worker_names = {w.name for w in workers}
            worker_stores: Dict[str, List[Tuple[ast.AST, bool]]] = {}
            for w in workers:
                for attr, stores in _self_attr_stores(w).items():
                    worker_stores.setdefault(attr, []).extend(stores)
            other_stores: Dict[str, List[Tuple[ast.AST, bool]]] = {}
            for name, m in methods.items():
                if name in worker_names or name == "__init__":
                    continue
                for attr, stores in _self_attr_stores(m).items():
                    other_stores.setdefault(attr, []).extend(stores)
            for attr in sorted(set(worker_stores) & set(other_stores)):
                unlocked = ([n for n, lk in worker_stores[attr]
                             if not lk]
                            + [n for n, lk in other_stores[attr]
                               if not lk])
                if unlocked:
                    findings.append(self.finding(
                        path, unlocked[0],
                        f"`self.{attr}` is written both inside thread "
                        f"target(s) {sorted(worker_names)} and outside "
                        "them, with at least one store not under a "
                        "shared lock — hold the lock on both sides or "
                        "hand the value over a Queue/Event", source))
        return findings


# ---------------------------------------------------------------------------
# EX001 — exception swallowing in run loops
# ---------------------------------------------------------------------------

_BROAD = {"Exception", "BaseException"}
#: Teardown paths where best-effort swallowing is the documented
#: exception ("sinks are the documented exception").
TEARDOWN_FUNCTIONS = {"close", "__exit__", "__del__", "shutdown",
                      "stop", "drain", "_halt", "_exit_fence"}


def _is_broad(handler: ast.ExceptHandler) -> bool:
    t = handler.type
    if t is None:
        return True
    if isinstance(t, ast.Name):
        return t.id in _BROAD
    if isinstance(t, ast.Tuple):
        return any(isinstance(e, ast.Name) and e.id in _BROAD
                   for e in t.elts)
    return False


def _swallows(handler: ast.ExceptHandler) -> bool:
    return all(isinstance(s, (ast.Pass, ast.Continue, ast.Break))
               for s in handler.body)


class ExceptionSwallowing(Rule):
    id = "EX001"
    title = "broad except swallowed inside a loop"
    rationale = (
        "a broad except whose body is pass/continue inside a run loop "
        "turns a hard fault into a silent skip-forever; log, narrow, "
        "or re-raise (teardown/sink paths are the documented "
        "exception)")

    def check(self, tree: ast.AST, source: str,
              path: str) -> Iterable[Finding]:
        findings: List[Finding] = []
        for fn, _stack in walk_functions(tree):
            if fn.name in TEARDOWN_FUNCTIONS:
                continue

            def scan(node: ast.AST, loop_depth: int):
                for child in ast.iter_child_nodes(node):
                    if isinstance(child, (ast.FunctionDef,
                                          ast.AsyncFunctionDef,
                                          ast.Lambda)):
                        continue  # its own scope; visited separately
                    d = loop_depth
                    if isinstance(child, (ast.For, ast.AsyncFor,
                                          ast.While)):
                        d += 1
                    if isinstance(child, ast.ExceptHandler) \
                            and loop_depth > 0 and _is_broad(child) \
                            and _swallows(child):
                        findings.append(self.finding(
                            path, child,
                            f"broad `except` swallowed inside a loop "
                            f"in `{fn.name}` — a hard fault becomes a "
                            "silent skip-forever; narrow the "
                            "exception, log it, or re-raise", source))
                    scan(child, d)

            scan(fn, 0)
        return findings


#: The catalog, in documentation order.
RULES = [HostSyncInHotPath, UnseededNondeterminism, MissingDonation,
         TelemetrySchemaDrift, LockDiscipline, ExceptionSwallowing]
