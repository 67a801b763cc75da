"""The serving engine: the device-facing half of continuous batching.

:class:`ServingEngine` turns the :class:`~apex_tpu.serving.scheduler.
ContinuousBatchingScheduler`'s host-side decisions into a fixed set of
five compiled executables (:data:`SERVING_EXECUTABLES`; the last two
only when :class:`~apex_tpu.serving.spec.SpecConfig` enables them),
each traced ONCE for the engine's lifetime — the table in
docs/serving.md "The compiled-shapes contract" is machine-checked
against this module.  The two workhorses:

* **prefill** — a packed row (``[1, S]`` tokens + segment ids +
  per-segment positions) of one of a fixed ladder of widths
  (:func:`prefill_ladder`: ``prefill_budget`` and its half, the
  narrowest that holds the request's own context) through
  :meth:`~apex_tpu.serving.model.PagedDecoder.prefill`, returning the
  greedy next-token per position and per-layer K/V, which the engine
  scatters into the request's freshly allocated pages (the
  **admission scatter**, ``PagedKVCache.write_tokens`` — executable
  #3).
* **decode** — a fixed-width ``[max_batch]`` step through
  :meth:`~apex_tpu.serving.model.PagedDecoder.decode`: append each
  row's newest token's K/V into its current page, attend over the
  row's page list via :func:`~apex_tpu.ops.flash_decode`, sample
  greedily.  Idle rows are pointed at the scratch page and ignored.

The ISSUE 12 draft–verify subsystem adds the **speculative verify**
step (``[max_batch, spec.k + 1]``) and the **chunked-prefill** step
(``[1, spec.chunk_size]``) — executables #4 and #5.

Admitting, retiring, growing or preempting requests between steps
never changes a device shape, so after :meth:`ServingEngine.warmup`
the serving lifetime sees ZERO further XLA compilations.  The warmup
compiles a FIXED, documented executable set (docs/serving.md "The
compiled-shapes contract"): the two step functions plus the pool-fill
scatter (``PagedKVCache.write_tokens``), and — with the ISSUE 12
draft–verify subsystem on — the speculative verify step
(``q_len = spec.k + 1``) and the ``[1, chunk_size]`` chunked-prefill
step.  The no-compile steady state is enforced by construction with
:func:`apex_tpu.analysis.hot_path_guard` (ISSUE 11 pin, extended over
a speculative + chunked trace in ISSUE 12).

**Speculative decoding (ISSUE 12, docs/serving.md).**  With
``spec=SpecConfig(k, proposer, chunk_size)`` the decode boundary asks
a host-side proposer for up to ``k`` draft tokens per request, scores
all of them in ONE ``flash_decode`` launch at ``q_len = k + 1``
(:meth:`_verify_batch`), commits the longest prefix the model's own
greedy argmax endorses plus the bonus token, and rolls rejected rows
back via plain ``kv_len``/page accounting — exact acceptance keeps
the bitwise batched==sequential contract intact.  Long prefills split
into fixed-width chunks (:meth:`_chunk_step`) that interleave with
decode boundaries under the existing prefill-token budget.

**The isolation contract (and why prefill is one request per row).**
The acceptance bar for this engine is bitwise: batched continuous
decoding must produce exactly the tokens sequential one-request-at-a-
time decoding produces.  Decode is row-wise by construction, but a
packed prefill row holding SEVERAL segments is not offset-invariant —
the attention contraction reduces over the packed axis, and XLA's
blocked reduction groups differently depending on where in the row a
segment starts (measured: a segment at offset 17 differs from offset 0
in the last ulp, enough to flip a greedy tie).  So the engine prefills
each admitted request in its OWN row at offset 0: the varlen packed
machinery (segment ids mask the padding) with exactly one segment per
row.  The row is one of a fixed ladder of widths, chosen by the
request's own context length and by nothing else (ISSUE 32): a row as
wide as the longest prompt allowed multiplies mostly padding, and a
width that follows only the request is the same whether the request is
served alone or in a batch, so the bitwise contract holds by
construction.  Packing several waiting prompts into one row would take
out more padding and stays refused, for the reason above.  Admission
still batches — the scheduler admits many requests per step — but each
prefill launch serves one request.  The multi-segment form of
:meth:`PagedDecoder.prefill` remains available for throughput-over-
isolation deployments; the engine does not use it (docs/serving.md,
"Prefill isolation").

Telemetry: every lifecycle edge lands on the PR 4 bus as one of the
serving event types — ``request_admit``, ``request_retire`` (with
per-request TTFT/TPOT and, when the request carried a deadline, a
``deadline_hit`` bool), ``decode_step`` (batch width, tokens,
page-pool occupancy), plus the ISSUE 10 resilience set
(``request_reject``, ``request_timeout``, ``serving_recovery``) — so
``python -m apex_tpu.telemetry summarize`` renders a serving line and
the stream is schema-validated by the existing ``validate`` CLI.

**Architectures and page lifetimes (ISSUE 29).**  The model half is a
:class:`~apex_tpu.serving.model.PagedDecoder` over whatever block the
configuration names; the pool loop carries and the page tables are
taken as the cache makes them (``_pool_state`` / ``_tables``), so a
model whose sliding-window layers live in a second pool
(``cache.window_pool``) runs through the same five executables, the
same scheduler and the same phases, with ``engine.release`` after
every decode step and chunk for the pages that slid out
(docs/serving.md "The block seam", "Two page lifetimes").  A model with
state-space layers (ISSUE 35) adds the recurrent-state pool to the same
carries (``cache.state_pool``) and each row's slot to the tables: a
request owns a slot beside its pages, a decode launch advances the
slots of its rows in place, and a prefill chunk starts from what the
chunk before left in the slot (docs/serving.md "Slots beside pages").

Step phases (ISSUE 27): bus or no bus, every step marks what the host
does with :func:`apex_tpu.telemetry.phase` — ``engine.step`` around
``engine.prefill`` (per request) / ``engine.grow`` /
``engine.decode`` and their ``prefill.*`` / ``decode.*`` children
— as ``apex:`` annotations in
any profiler session and as records in the one in-memory ring;
``decode_step.step_ms`` and ``phase_ms`` are those records
(docs/telemetry.md "Step phases").

One launch in flight (ISSUE 34): the plain decode launch is pipelined
one deep.  A step builds launch *n* from counts alone (a row's
position, ``kv_len`` and page are ``seq_len + in_flight``), dispatches
it with the token input put together on the device from launch
*n − 1*'s unfetched output, and only then fetches and commits launch
*n − 1* (:meth:`ServingEngine._land`), so the host's turn runs under
the device's step.  First tokens are fetched where they always were;
what needs landed tokens (a speculative boundary, preemption,
snapshot, export, adoption) lands the launch first (docs/serving.md
"One launch in flight").

**Failure semantics (ISSUE 10).** The engine degrades instead of
falling over: per-request deadlines shed/time out work that can no
longer meet its SLO, a bounded submit queue rejects overload loudly,
:meth:`ServingEngine.snapshot`/:meth:`~ServingEngine.restore` capture
the HOST-side serving state (queue order + per-request tokens — KV
pages deliberately excluded, they are rebuildable by deterministic
re-prefill), and a :class:`~apex_tpu.resilience.chaos.DeviceLossError`
or :class:`~apex_tpu.serving.kv_cache.PagePoolCorruption` raised
mid-decode triggers :meth:`~ServingEngine.recover` — fresh pool,
live requests back to the front of the queue, token streams bitwise
identical to an uninterrupted run.  See docs/serving.md "Failure
semantics".
"""

from __future__ import annotations

import bisect
import time
from typing import (Any, Callable, Dict, List, NamedTuple, Optional,
                    Sequence, Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.ops import flash_attention_route
from apex_tpu.serving.kv_cache import (PagedKVCache, PagePoolCorruption,
                                       PagePoolExhausted, PrefixIndex,
                                       StatePool, WindowPool,
                                       verify_page_payload)
from apex_tpu.serving.model import (PagedDecoder, ServingModelConfig,
                                    StateIO, WindowKV, init_params,
                                    shard_params_tp)
from apex_tpu.serving.scheduler import (FINISHED, RUNNING, WAITING,
                                        ContinuousBatchingScheduler,
                                        QueueFullError, Request)
from apex_tpu.serving.spec import (NgramProposer, SpecConfig,
                                   commit_tokens)
from apex_tpu.telemetry import scopes
from apex_tpu.telemetry.phases import phase

#: The compiled-shapes contract as code, in docs/serving.md table
#: order: every executable :meth:`ServingEngine.warmup` may build.
#: The doc-drift test pins the module docstring's "fixed set of five"
#: and the docs table row count to this tuple, and the ISSUE 13
#: registry (``apex_tpu.analysis.registry``) derives its serving
#: entries from it — docstring, docs, and contract checker cannot
#: disagree on the set.
SERVING_EXECUTABLES = ("prefill", "decode", "admission_scatter",
                       "verify", "chunk")


class AdmissionRefused(RuntimeError):
    """A shipped-prefill admission (:meth:`ServingEngine.
    adopt_prefilled`) was refused for CAPACITY — no decode batch slot
    or no pool pages.  Recoverable by construction, like
    :class:`~apex_tpu.serving.kv_cache.PagePoolExhausted`: the sender
    backs off and retries, or past its budget falls back to migrating
    the request for local prefill.  Validation failures (geometry, rid
    collision, CRC) raise ``ValueError`` instead — those are bugs or
    corruption, not capacity events."""


# -- chaos hook (ISSUE 10) ---------------------------------------------------
# The serving twin of checkpoint.set_fault_hook / data.set_read_hook:
# the chaos tier installs an injector here to raise DeviceLossError /
# sleep / corrupt a page at a named engine event ("decode" before each
# decode launch, "prefill" before each prefill launch).  Production
# never sets it; the slot costs one None-check per step.

_FAULT_HOOK: Optional[Callable[[str, int], None]] = None


def set_fault_hook(hook: Optional[Callable[[str, int], None]]):
    """Install (or clear) the serving fault hook; returns the previous
    hook so context-manager injectors can chain/restore."""
    global _FAULT_HOOK
    prev = _FAULT_HOOK
    _FAULT_HOOK = hook
    return prev


def _fault_point(event: str, info: int) -> None:
    if _FAULT_HOOK is not None:
        _FAULT_HOOK(event, info)


class _Flight(NamedTuple):
    """The decode launch in flight (ISSUE 34): dispatched, its tokens
    and the block's counters still on the device."""

    rows: List[Request]           # row i of the launch, in launch order
    next_tok: Any                 # int32[max_batch], on the device
    stats: Tuple                  # the block's counters, on the device


class SimClock:
    """Deterministic virtual clock for tests: ``now()`` returns the
    current virtual time; the engine's step advances it by a fixed
    tick, so a seeded arrival trace replays bit-identically with no
    wall-clock in the loop."""

    def __init__(self, tick: float = 1.0):
        self.t = 0.0
        self.tick = tick

    def __call__(self) -> float:
        return self.t

    def advance(self) -> None:
        self.t += self.tick


def poisson_trace(seed: int, n_requests: int, *, rate: float,
                  prompt_len: Tuple[int, int], max_new: Tuple[int, int],
                  vocab_size: int,
                  eos_id: Optional[int] = None,
                  deadline_s: Optional[Tuple[float, float]] = None,
                  rid_base: int = 0) -> List[Request]:
    """Seeded Poisson arrival trace: exponential inter-arrival gaps at
    ``rate`` requests/s, uniform prompt lengths and generation budgets.
    Deterministic in ``seed`` — ``chip_smoke.py``'s traffic and the
    scheduler determinism test share this generator.

    ``deadline_s`` — optional (lo, hi) uniform completion-deadline
    range (seconds after arrival; the overload/SLO arcs use this).
    The draw happens only when requested, so deadline-free traces are
    bit-identical to the pre-ISSUE-10 generator.  ``rid_base`` offsets
    request ids so a second trace can be served on the same engine
    (rids are unique per engine lifetime)."""
    rng = np.random.RandomState(seed)
    t = 0.0
    out: List[Request] = []
    for rid in range(n_requests):
        t += float(rng.exponential(1.0 / rate))
        plen = int(rng.randint(prompt_len[0], prompt_len[1] + 1))
        out.append(Request(
            rid=rid_base + rid,
            prompt=[int(x) for x in rng.randint(0, vocab_size, plen)],
            max_new_tokens=int(rng.randint(max_new[0], max_new[1] + 1)),
            eos_id=eos_id,
            arrival_t=t,
            deadline_s=(None if deadline_s is None else
                        float(rng.uniform(deadline_s[0], deadline_s[1]))),
        ))
    return out


def prefill_route(cfg: ServingModelConfig, tp: int, width: int) -> str:
    """The attention forward's kernel route for one shard of a
    ``[1, width]`` prefill row of ``cfg`` (``varlen`` on the TPU at the
    serving widths, ``xla`` off it).  ``cfg`` says what heads the
    forward sees: a latent model's are its expanded ones, at the padded
    width (:attr:`DeepseekV2Config.head_dim`)."""
    sds = jax.ShapeDtypeStruct
    return flash_attention_route(
        sds((cfg.num_heads // tp, width, cfg.head_dim), cfg.dtype),
        sds((max(1, cfg.kv_heads // tp), width, cfg.head_dim), cfg.dtype),
        segment_ids=True)["fwd"]


#: No rung of the prefill ladder is narrower: below about this many
#: tokens a row's time is the reading of the weights, whatever its width.
MIN_PREFILL_ROW = 128


def prefill_ladder(budget: int,
                   route: Callable[[int], str]) -> Tuple[int, ...]:
    """The widths a whole-row prefill is launched at, ascending, the
    last of them ``budget``: the widest row and its half.  The half is
    left out where it is narrower than :data:`MIN_PREFILL_ROW`, or
    where the attention forward would not take it the way it takes the
    ``budget`` row (``route(width)``, the kernel route of a row that
    wide: a rung that fell to another route would be slower, and
    rounded otherwise, than the row it replaces).

    Two rungs and not four, because a rung is paid for in every
    process's set-up: at 24 layers one more tracing, lowering and load
    of the cached executable is 1.8-2.5 s (``PERF.md``, PR 32), and
    the half alone takes out five sixths of the time the quarters
    would.  Derived from ``budget`` alone, so an engine and its
    rebuild after :meth:`ServingEngine.recover` launch the same rows."""
    half = -(-budget // 2)
    if half >= MIN_PREFILL_ROW and route(half) == route(budget):
        return (half, budget)
    return (budget,)


class ServingEngine:
    """Continuous-batching inference over a paged KV cache.

    ``cfg`` names the architecture (:class:`~apex_tpu.serving.model.
    ServingModelConfig`, a GPT; :class:`~apex_tpu.serving.model.
    AfmoeConfig`): the engine asks it for its block, its layers'
    windows and its K/V heads, never which it is, and refuses at
    construction the options its block does not carry
    (docs/serving.md "The block seam").
    ``num_pages``/``page_size`` size the shared pool, and
    ``window_pages`` the second pool of a model whose sliding-window
    layers keep only a window of tokens (docs/serving.md "Two page
    lifetimes"; as many as ``num_pages`` where not given), and
    ``state_slots`` the slots of recurrent state of a model with
    state-space layers, the scratch slot among them (docs/serving.md
    "Slots beside pages"; ``max_batch + 2`` where not given);
    ``prefill_budget`` is the widest packed prefill row (defaults to
    ``cfg.max_position``; a request's row is the narrowest rung of
    :attr:`prefill_widths` that holds its context) and the scheduler's
    prefill-token budget a boundary, and it bounds prompt+generation
    per request unless prefill is chunked (a model without a position
    table chunks by default and is bounded by :attr:`max_context`);
    ``max_batch`` fixes the decode batch width.  ``telemetry`` is an
    optional :class:`~apex_tpu.telemetry.TelemetryBus`; ``clock`` an
    optional ``() -> float`` (tests pass :class:`SimClock` for
    deterministic timing fields — timing never feeds scheduling
    decisions, only metrics and, when requests carry deadlines, the
    deadline policy).

    Resilience knobs (ISSUE 10 — docs/serving.md "Failure semantics"):
    ``max_queue`` bounds the submit queue (overflow → ``rejected``
    terminal state + ``request_reject`` event, never unbounded growth);
    ``preempt_cap`` is the anti-livelock aging cap on evict-newest
    preemption; ``shed_min_service_s`` is the SLO floor used to shed
    queued requests BEFORE their deadline expires; ``watchdog`` is an
    optional :class:`~apex_tpu.resilience.elastic.Watchdog` armed
    around every engine step (a wedged decode escalates instead of
    hanging the trace); ``validate_pages`` turns on per-page CRC
    read-back validation in the pool; ``recover_on_fault`` lets
    :meth:`serve`/:meth:`run` absorb a mid-decode
    ``DeviceLossError``/``PagePoolCorruption`` via :meth:`recover`
    (at most ``max_recoveries`` times, then the fault re-raises).
    """

    def __init__(self, cfg: ServingModelConfig, params=None, *,
                 num_pages: int, page_size: int = 64,
                 max_batch: int = 8,
                 max_pages_per_request: Optional[int] = None,
                 prefill_budget: Optional[int] = None,
                 telemetry=None,
                 clock: Optional[Callable[[], float]] = None,
                 seed: int = 0,
                 max_queue: Optional[int] = None,
                 preempt_cap: Optional[int] = 4,
                 shed_min_service_s: float = 0.0,
                 watchdog=None,
                 validate_pages: bool = False,
                 recover_on_fault: bool = True,
                 max_recoveries: int = 3,
                 reject_unservable: bool = False,
                 spec: Optional[SpecConfig] = None,
                 tp: int = 1,
                 kv_quant: Optional[str] = None,
                 prefix_sharing: bool = False,
                 prefix_entries: int = 8,
                 prefill_only: bool = False,
                 kv_import: bool = False,
                 window_pages: Optional[int] = None,
                 state_slots: Optional[int] = None):
        self.cfg = cfg
        self.decoder = PagedDecoder(cfg)
        # what this architecture's block cannot serve yet is refused
        # here, loudly: none of it may silently serve wrong tokens
        asked = {"tp": tp > 1, "kv_quant": kv_quant is not None,
                 "prefix_sharing": prefix_sharing,
                 "speculation": spec is not None and spec.k > 0,
                 "prefill_only": prefill_only, "kv_import": kv_import}
        for option in self.decoder.block.refuses:
            if asked[option]:
                raise ValueError(
                    f"{cfg.name}: the engine option {option!r} is not "
                    "supported for this model (docs/serving.md, "
                    f"\"{self.decoder.block.refuses_doc}\")")
        self.params = params if params is not None else init_params(cfg, seed)
        if prefill_budget is None and cfg.max_position is None:
            raise ValueError(f"{cfg.name}: no position table to take the "
                             "prefill row's width from; give prefill_budget")
        self.prefill_budget = (cfg.max_position if prefill_budget is None
                               else prefill_budget)
        # draft–verify subsystem (ISSUE 12, docs/serving.md
        # "Speculative decoding"): spec.k > 0 adds the verify
        # executable (q_len = k + 1) and a proposer; spec.chunk_size
        # adds chunked prefill.  spec=None is the pre-ISSUE-12 engine,
        # bit-for-bit.
        self.spec = spec
        self.spec_k = spec.k if spec is not None else 0
        self.chunk_size = spec.chunk_size if spec is not None else None
        if self.chunk_size is None and cfg.max_position is None:
            # no position table bounds a request to the prefill row, so
            # a longer prompt goes through chunked prefill by default,
            # in chunks of the row's width
            self.chunk_size = self.prefill_budget
        self.proposer = None
        if self.spec_k > 0:
            self.proposer = (spec.proposer if spec.proposer is not None
                             else NgramProposer())
        # r17 execution modes (docs/serving.md "Tensor-parallel
        # serving" / "Quantized KV pool" / "Prefix sharing"):
        # tp > 1 shards attention heads (and the page pool's head
        # axis) over the parallel_state tensor axis; kv_quant narrows
        # the pool to int8/fp8 codes + fp32 per-(page, slot, head)
        # scales; prefix_sharing admits repeated prompts onto
        # refcounted shared pages.
        self.tp = int(tp)
        self.kv_quant = kv_quant
        self.prefix_entries = int(prefix_entries)
        self._mesh = None
        self._tp_axis = None
        if self.tp > 1:
            from apex_tpu.transformer.parallel_state import (
                TENSOR_AXIS, tensor_parallel_mesh)
            if cfg.num_heads % self.tp:
                raise ValueError(
                    f"num_heads {cfg.num_heads} not divisible by "
                    f"tp={self.tp}")
            self._mesh = tensor_parallel_mesh(self.tp)
            self._tp_axis = TENSOR_AXIS
            self.params = shard_params_tp(self.params, self.tp)
        #: the ladder of row widths (ISSUE 32): a request's whole-row
        #: prefill takes the narrowest that holds its context
        self.prefill_widths = prefill_ladder(
            self.prefill_budget,
            lambda width: prefill_route(cfg, self.tp, width))
        if max_pages_per_request is None:
            # a chunked engine serves requests WIDER than the prefill
            # row (that is the point of chunking), so its page-table
            # width must default to the max_position ceiling, not the
            # row width — clamped to the allocatable pool so enabling
            # chunking never turns a valid construction into a
            # constructor error (an oversized request still fails
            # submit() with the pages_needed check, loudly)
            cap_tokens = (self.prefill_budget if self.chunk_size is None
                          else cfg.max_position
                          or (num_pages - 1) * page_size)
            max_pages_per_request = min(-(-cap_tokens // page_size),
                                        max(1, num_pages - 1))
        self._window_pages = window_pages
        self._state_slots = state_slots or max_batch + 2
        self.cache = self._new_cache(num_pages, page_size,
                                     max_pages_per_request, validate_pages)
        self.prefix_index = (
            PrefixIndex(self.cache, max_entries=self.prefix_entries)
            if prefix_sharing else None)
        self.max_batch = max_batch
        self.sched = self._new_scheduler(max_queue, preempt_cap)
        self.telemetry = telemetry
        self.clock = clock if clock is not None else time.monotonic
        self.shed_min_service_s = float(shed_min_service_s)
        self.watchdog = watchdog
        self.recover_on_fault = recover_on_fault
        self.max_recoveries = int(max_recoveries)
        # ISSUE 16: a router fronting many engines needs permanent
        # refusal as DATA (terminal `rejected` + request_reject
        # reason="unservable"), not a ValueError — default off keeps
        # the single-engine caller-bug contract
        self.reject_unservable = bool(reject_unservable)
        # r18 disaggregation roles (docs/serving.md "Disaggregated
        # prefill/decode"): a prefill_only engine admits and
        # (chunk-)prefills but never decodes — its requests leave via
        # export_request; kv_import warms the shipped-page import
        # executable so adopt_prefilled never compiles on the
        # admission path.  Both off is the colocated engine, bit-for-bit.
        self.prefill_only = bool(prefill_only)
        self.kv_import = bool(kv_import)
        self.recoveries = 0
        self._released_full = 0
        #: the decode launch in flight, if any (ISSUE 34)
        self._flight: Optional[_Flight] = None
        #: what landed since the last ``engine.decode`` span closed: the
        #: requests, a token each, and the counters of their launch
        self._landed: List[int] = []
        self._landed_stats: Dict[str, int] = {}
        self._retired = 0             # requests retired, ever
        # the token input of a launch with none in flight before it: an
        # array of the type, shape and placement of a launch's own
        # output, which is what every other launch takes (warmup()
        # replaces it with one)
        self._no_prev = jnp.zeros((max_batch,), jnp.int32)
        #: per request mid-prefill, its chunks' counters still on the device
        self._chunk_stats: Dict[int, list] = {}
        self.rejected: List[Request] = []
        self._next_rid = 0
        self.steps = 0
        self.decode_steps = 0
        decoder = self.decoder
        ax = self._tp_axis
        quant = self.kv_quant is not None

        # the pool loop carries, in executable order: (k, v), then the
        # quantized pool's scale planes (r17), then the window pool's
        # k and v (ISSUE 29), then the state pool's states and tails
        # (ISSUE 35); a window pool's two tables follow the other
        # operands, and a state pool's (slots, fresh) those.  The step
        # bodies take them as they come and hand the decoder what the
        # cache is made of.
        # The step bodies close over plain values only, never over the
        # engine: telemetry.scopes keeps the jitted functions beyond the
        # engine's life, and they must keep no pool or parameter alive.
        n_pool = len(self._pool_state())
        n_stat = 1 if decoder.stat_names else 0
        latent = decoder.latent
        has_state = self.cache.state_pool is not None
        has_window = self.cache.window_pool is not None

        def carries(args):
            """(pools, the other operands, decoder keywords)."""
            pools, rest = args[:n_pool], args[n_pool:]
            if latent:      # one operand: the decoder's v_pool is None
                pools = (pools[0], None)
            kw = {}
            if quant:
                kw.update(k_scale=pools[2], v_scale=pools[3])
            if has_state:
                kw["state"] = StateIO(pools[-2], pools[-1],
                                      rest[-2], rest[-1])
                pools, rest = pools[:-2], rest[:-2]
            if has_window:
                kw["window"] = WindowKV(pools[-2], pools[-1],
                                        rest[-2], rest[-1])
                rest = rest[:-2]
            return pools, rest, kw

        def _prefill(params, tokens, seg, positions, last_index):
            # logits for the last context position only: admission
            # needs one next-token distribution, not S of them
            logits, *kv = decoder.prefill(params, tokens, seg,
                                          positions, last_index,
                                          tp_axis=ax)
            n_kv = len(kv) - n_stat      # K/V stacks, then the counters
            return (jnp.argmax(logits[0, 0], axis=-1),
                    *(a[:, 0] for a in kv[:n_kv]), *kv[n_kv:])

        def _decode(params, *args):
            pools, rest, kw = carries(args)
            # the token input is put together here, on the device: row
            # i takes the previous launch's output at src[i], which the
            # host has not fetched yet, or the host's token where
            # src[i] < 0 (a row fresh from prefill)
            tokens, prev, src, *rest = rest
            tokens = jnp.where(src >= 0, prev[jnp.maximum(src, 0)], tokens)
            logits, *out = decoder.decode(
                params, pools[0], pools[1], tokens, *rest, tp_axis=ax,
                **kw)
            return (jnp.argmax(logits, axis=-1), *out)

        def _verify(params, *args):
            # all k+1 positions scored in ONE flash_decode launch;
            # only the argmax ids leave the device
            pools, rest, kw = carries(args)
            logits, *out = decoder.extend(
                params, pools[0], pools[1], *rest, tp_axis=ax, **kw)
            return (jnp.argmax(logits, axis=-1), *out)

        def _chunk(params, *args):
            # one chunk of a long context; front-padding pins the
            # chunk's last valid token to the final row, so
            # last_only projects exactly one position through the
            # LM head
            pools, rest, kw = carries(args)
            logits, *out = decoder.extend(
                params, pools[0], pools[1], *rest, last_only=True,
                tp_axis=ax, **kw)
            return (jnp.argmax(logits[:, 0], axis=-1), *out)

        pool_donate = tuple(range(1, 1 + n_pool))

        if self._mesh is not None:
            # place params and pools with their tensor-axis shardings
            # BEFORE anything launches: shard_map pins input shardings,
            # so an unplaced operand would be resharded INSIDE the
            # compiled step — a collective the HLO contract forbids on
            # the decode hot path
            self.params = jax.device_put(self.params,
                                         self._param_shardings())
            self._shard_pools()
            _prefill, _decode, _verify, _chunk = self._shard_map_execs(
                _prefill, _decode, _verify, _chunk)

        # raw step functions + the donation each SHIPS with on TPU,
        # keyed by compiled-shapes-contract name: the ISSUE 13 checker
        # (analysis_executables) re-lowers these with the TPU donation
        # spec forced on, so the committed hlo_contracts.json verifies
        # the contract the production backend actually runs under
        self._exec_defs = {"prefill": (_prefill, ()),
                           "decode": (_decode, pool_donate),
                           "verify": (_verify, pool_donate),
                           "chunk": (_chunk, pool_donate)}
        self._prefill_fn = jax.jit(_prefill)
        # donate the pool buffers on TPU: the decode step would
        # otherwise hold old + new pool alive across every step (the
        # CPU backend doesn't implement donation — gating avoids a
        # warning per test run).  The engine rebinds cache.k/v (and,
        # quantized, the scale planes) to the returned pools
        # immediately, so nothing aliases the donated buffers.
        donate = pool_donate if jax.default_backend() == "tpu" else ()
        self._decode_fn = jax.jit(_decode, donate_argnums=donate)
        self._verify_fn = (jax.jit(_verify, donate_argnums=donate)
                           if self.spec_k > 0 else None)
        self._chunk_fn = (jax.jit(_chunk, donate_argnums=donate)
                          if self.chunk_size is not None else None)

    # -- tensor-parallel plumbing (r17) ------------------------------------

    def _param_specs(self):
        """``PartitionSpec`` pytree mirroring the params pytree:
        wqkv/w1 column-sharded over the tensor axis (each shard owns a
        head slice — see :func:`~apex_tpu.serving.model.
        shard_params_tp` for the wqkv column reorder that makes this
        correct), wo/w2 row-sharded, embeddings / positions / layer
        norms replicated — the Megatron layout, one ``psum`` per
        block."""
        from jax.sharding import PartitionSpec as P
        ax = self._tp_axis
        rep = P()
        ln = {"g": rep, "b": rep}
        layer = {"ln1": dict(ln), "wqkv": P(None, ax),
                 "wo": P(ax, None), "ln2": dict(ln),
                 "w1": P(None, ax), "w2": P(ax, None)}
        return {"embed": rep, "pos": rep, "ln_f": dict(ln),
                "layers": [dict(layer)
                           for _ in range(self.cfg.num_layers)]}

    def _param_shardings(self):
        from jax.sharding import NamedSharding, PartitionSpec
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(self._mesh, s), self._param_specs(),
            is_leaf=lambda x: isinstance(x, PartitionSpec))

    def _shard_pools(self) -> None:
        """Place the pool (and scale) arrays on the mesh, sharded on
        their head axis — fresh pools (init / :meth:`recover`) must be
        re-placed or the next step would compile a second, resharding
        executable."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        ax = self._tp_axis
        pool = NamedSharding(self._mesh, P(None, None, None, ax, None))
        self.cache.k = jax.device_put(self.cache.k, pool)
        self.cache.v = jax.device_put(self.cache.v, pool)
        if self.kv_quant is not None:
            sc = NamedSharding(self._mesh, P(None, None, None, ax))
            self.cache.k_scale = jax.device_put(self.cache.k_scale, sc)
            self.cache.v_scale = jax.device_put(self.cache.v_scale, sc)

    def _shard_map_execs(self, _prefill, _decode, _verify, _chunk):
        """Wrap the four step bodies in ``shard_map`` over the tensor
        mesh: pools/scales arrive pre-sharded on their head axis,
        params per :meth:`_param_specs`, everything else replicated.
        The bodies derive their head count from the LOCAL shapes and
        contribute residuals via ``psum`` — the only hot-path
        collectives, pinned per-executable by the HLO contract."""
        from jax.experimental.shard_map import shard_map
        from jax.sharding import PartitionSpec as P
        ax = self._tp_axis
        pool = P(None, None, None, ax, None)
        r = P()
        kv_row = P(None, None, ax, None)
        pspec = self._param_specs()

        def sm(fn, in_specs, out_specs):
            return shard_map(fn, mesh=self._mesh, in_specs=in_specs,
                             out_specs=out_specs, check_rep=False)

        pools = ((pool, pool, P(None, None, None, ax),
                  P(None, None, None, ax))
                 if self.kv_quant is not None else (pool, pool))
        outs = (r,) + pools
        _prefill = sm(_prefill, (pspec, r, r, r, r), (r, kv_row, kv_row))
        _decode = sm(_decode, (pspec,) + pools + (r,) * 6, outs)
        _verify = sm(_verify, (pspec,) + pools + (r,) * 6, outs)
        _chunk = sm(_chunk, (pspec,) + pools + (r,) * 6, outs)
        return _prefill, _decode, _verify, _chunk

    # -- quantized-pool plumbing (r17) -------------------------------------

    def _new_cache(self, num_pages: int, page_size: int,
                   max_pages_per_request: int,
                   crc_pages: bool) -> PagedKVCache:
        """The pool of the layers that keep every token and, where the
        model has layers with a window, the :class:`WindowPool` of
        those beside it (``cache.window_pool``; ``window_pages`` of
        them, as many as the full pool where not given); where it has
        state-space layers, the :class:`StatePool` of their recurrent
        state (``cache.state_pool``, ``state_slots`` slots): one
        manager for what a request owns.  ``__init__`` and
        :meth:`recover` build the same."""
        cfg, dec = self.cfg, self.decoder
        if not dec.full_layers:
            raise ValueError(f"{cfg.name}: a model needs at least one "
                             "layer that keeps every token")
        geometry = dict(page_size=page_size, num_heads=cfg.kv_heads,
                        head_dim=dec.page_head_dim, dtype=cfg.dtype,
                        crc_pages=crc_pages, latent_dim=cfg.latent_dim)
        cache = PagedKVCache(
            num_layers=dec.full_layers, num_pages=num_pages,
            max_pages_per_request=max_pages_per_request,
            quantize=self.kv_quant, **geometry)
        if dec.window_layers:
            window = max(w for w in dec.windows if w is not None)
            n = self._window_pages or num_pages
            cache.window_pool = WindowPool(
                window=window, num_layers=dec.window_layers, num_pages=n,
                max_pages_per_request=min(n - 1, WindowPool.pages_per_request(
                    window, self.chunk_size or self.prefill_budget,
                    page_size)),
                **geometry)
        if dec.n_state_layers:
            cache.state_pool = StatePool(
                num_layers=dec.n_state_layers, num_slots=self._state_slots,
                state_shape=cfg.state_shape, tail_shape=cfg.tail_shape,
                dtype=cfg.dtype, state_dtype=cfg.state_dtype)
        return cache

    def _new_scheduler(self, max_queue: Optional[int],
                       preempt_cap: Optional[int]
                       ) -> ContinuousBatchingScheduler:
        """The scheduler over the cache as it stands; ``__init__`` and
        :meth:`recover` build the same.  It keeps chunking (ISSUE 12): a
        chunk-less rebuild would strand any live request whose context
        exceeds the prefill row — schedule_prefill could never re-admit
        it, and FIFO admission would starve everything queued behind it
        (review-found, pinned)."""
        sched = ContinuousBatchingScheduler(
            self.cache, max_batch=self.max_batch,
            prefill_budget=self.prefill_budget,
            max_position=self.max_context,
            max_queue=max_queue, preempt_cap=preempt_cap,
            chunk_size=self.chunk_size,
            prefix_index=self.prefix_index)
        sched.land = self._land_and_retire
        return sched

    @property
    def max_context(self) -> int:
        """The most tokens (prompt + generated) a request may reach:
        the position table's length where the model has one, else what
        its page table can address."""
        return (self.cfg.max_position
                or self.cache.max_pages_per_request * self.cache.page_size)

    # -- the pool loop carries ---------------------------------------------

    def _pool_state(self) -> Tuple:
        """The pool loop-carry operands in executable order —
        ``(k, v)`` (a latent pool: ``k`` alone), then, quantized,
        ``(k_scale, v_scale)``, then the window pool's ``(k, v)`` where
        there is one, then the state pool's ``(ssm, conv)``."""
        cache = self.cache
        pools = tuple(getattr(cache, name) for name in cache.operands)
        wpool, spool = cache.window_pool, cache.state_pool
        if wpool is not None:
            pools += (wpool.k, wpool.v)
        if spool is not None:
            pools += (spool.ssm, spool.conv)
        return pools

    def _bind_pools(self, out: Tuple) -> Tuple:
        """Rebind the cache to a step's returned pool carries (the
        donated-buffer hand-back); returns what follows them, the
        block's counters."""
        n = len(self._pool_state())
        pools, rest = out[:n], out[n:]
        wpool, spool = self.cache.window_pool, self.cache.state_pool
        if spool is not None:
            spool.ssm, spool.conv = pools[-2:]
            pools = pools[:-2]
        if wpool is not None:
            wpool.k, wpool.v = pools[-2:]
        for name, pool in zip(self.cache.operands, pools):
            setattr(self.cache, name, pool)
        return rest

    def _tables(self, reqs: Sequence[Request], rows: int,
                fresh: bool = False) -> Tuple:
        """The page tables of ``reqs`` as the executables take them:
        the full pool's, and after the other operands the window
        pool's compact table and its first positions, then the state
        pool's slots and whether each row starts from zero (``fresh``:
        a request's first chunk) instead of from its slot."""
        table = self.cache.page_table([r.pages for r in reqs], rows=rows)
        wpool, spool = self.cache.window_pool, self.cache.state_pool
        extra = ()
        if wpool is not None:
            extra += wpool.tables([r.window for r in reqs], rows=rows)
        if spool is not None:
            extra += (spool.table([r.slot for r in reqs], rows=rows),
                      jnp.asarray(np.full((rows,), int(fresh), np.int32)))
        return table, extra

    def _stats(self, stats: Tuple) -> Dict[str, int]:
        """A launch's counters by name (one small fetch; the caller is
        at a point where it waits for the device anyway)."""
        if not stats:
            return {}
        return dict(zip(self.decoder.stat_names,
                        np.asarray(stats[0]).tolist()))

    # -- compiled-artifact exposure (ISSUE 13) -----------------------------

    def _executable_arg_structs(self, prefill_width: Optional[int] = None
                                ) -> Dict[str, Tuple]:
        """``jax.ShapeDtypeStruct`` argument tuples per enabled
        executable of the compiled-shapes contract (minus the
        admission scatter, which :class:`PagedKVCache` owns) — the
        same shapes :meth:`warmup` launches, pinned against it by the
        no-drift regression so the analyzed artifacts are the served
        artifacts.  The prefill row is ``prefill_width`` wide (a rung
        of :attr:`prefill_widths`; the widest where not given).  One
        source of shapes for the HLO contracts, :meth:`warmup`'s rows
        and the scope maps (:meth:`_register_scopes`)."""
        sds = jax.ShapeDtypeStruct
        i32 = jnp.int32
        params = jax.tree_util.tree_map(
            lambda a: sds(jnp.shape(a), a.dtype), self.params)
        pools = tuple(sds(a.shape, a.dtype) for a in self._pool_state())
        S, b = prefill_width or self.prefill_budget, self.max_batch
        p_max = self.cache.max_pages_per_request
        wpool = self.cache.window_pool

        def tables(rows):
            """Page table, kv_len, then the window pool's two tables."""
            t = (sds((rows, p_max), i32), sds((rows,), i32))
            if wpool is not None:
                t += (sds((rows, wpool.max_pages_per_request), i32),
                      sds((rows,), i32))
            if self.cache.state_pool is not None:
                t += (sds((rows,), i32),) * 2
            return t

        row = sds((1, S), i32)
        out = {
            "prefill": (params, row, row, row, sds((), i32)),
            # tokens, the previous launch's, their source rows, positions
            "decode": ((params,) + pools + (sds((b,), i32),) * 4
                       + tables(b)),
        }
        if self._verify_fn is not None:
            q = sds((b, self.spec_k + 1), i32)
            out["verify"] = (params,) + pools + (q, q, q, q) + tables(b)
        if self._chunk_fn is not None:
            c = sds((1, self.chunk_size), i32)
            out["chunk"] = (params,) + pools + (c, c, c, c) + tables(1)
        return out

    def analysis_executables(self, *, donate: bool = True) -> Dict[str, Any]:
        """name → ``jax.stages.Lowered`` for every executable of the
        compiled-shapes contract this configuration enables, at the
        engine's exact shapes, with the TPU donation spec FORCED on
        regardless of backend (``__init__`` gates donation off on CPU
        only to avoid the backend-unsupported warning; the shipped
        contract is the TPU one, and that is what the ISSUE 13 checker
        verifies — pool donation machine-checked end-to-end, the PR 8
        768 MB lesson made structural).  ``donate=False`` is the
        checker's own negative control: the donate-stripped artifact
        must FAIL the committed aliasing contract."""
        structs = self._executable_arg_structs()
        lowered: Dict[str, Any] = {}
        for name, (fn, tpu_donate) in self._exec_defs.items():
            if name not in structs:
                continue
            jitted = jax.jit(fn, donate_argnums=tpu_donate if donate else ())
            lowered[name] = jitted.lower(*structs[name])
        lowered["admission_scatter"] = self.cache.analysis_executable(
            self.prefill_budget, donate=donate)
        return {n: lowered[n] for n in SERVING_EXECUTABLES if n in lowered}

    # -- intake ------------------------------------------------------------

    def submit(self, prompt: Sequence[int], max_new_tokens: int, *,
               eos_id: Optional[int] = None,
               arrival_t: Optional[float] = None,
               deadline_s: Optional[float] = None) -> Request:
        """Create and queue a request; returns its :class:`Request`
        handle (tokens accumulate on ``.generated``).  ``deadline_s``
        is the completion SLO in seconds after arrival.  A full
        bounded queue does NOT raise: the returned request is already
        terminal (``finish_reason == "rejected"``) and a
        ``request_reject`` event is emitted — the caller checks the
        handle, the trace keeps flowing."""
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if not prompt:
            raise ValueError("empty prompt")
        req = Request(rid=self._next_rid, prompt=list(prompt),
                      max_new_tokens=max_new_tokens, eos_id=eos_id,
                      arrival_t=(self.clock() if arrival_t is None
                                 else arrival_t),
                      deadline_s=deadline_s)
        self._next_rid += 1
        return self._try_submit(req)

    def submit_request(self, req: Request) -> Request:
        """Queue a pre-built request (trace replay); rids must be
        unique per engine.  Same reject semantics as :meth:`submit`."""
        self._next_rid = max(self._next_rid, req.rid + 1)
        return self._try_submit(req)

    def _try_submit(self, req: Request) -> Request:
        """Queue ``req`` or reject it explicitly.  Never-servable
        requests raise ``ValueError`` (caller bug) — unless
        ``reject_unservable`` is set, in which case they finish as
        ``rejected`` with ``reason="unservable"`` so a fleet router
        can tell permanent refusal from backpressure.  A full bounded
        queue is an OVERLOAD signal: the request finishes as
        ``rejected`` with ``reason="queue_full"``, and the engine
        keeps serving what it already accepted."""
        try:
            self.sched.submit(req)
        except QueueFullError:
            self._reject(req, "queue_full")
        except ValueError:
            if not self.reject_unservable:
                raise
            self._reject(req, "unservable")
        return req

    def _reject(self, req: Request, reason: str) -> None:
        req.state = FINISHED
        req.finish_t = self.clock()
        req.finish_reason = "rejected"
        self.rejected.append(req)
        self._emit("request_reject", rid=req.rid, reason=reason,
                   queue_depth=len(self.sched.waiting))

    # -- device steps ------------------------------------------------------

    def warmup(self) -> float:
        """Compile every device executable before any request arrives
        (so TTFT never carries jit-compile wall); returns the seconds
        spent.

        The compiled set is FIXED and documented (docs/serving.md
        "The compiled-shapes contract"): the prefill row at every
        width of :attr:`prefill_widths`, the decode step, the admission
        scatter at the same widths (``PagedKVCache.write_tokens`` —
        the one warmup originally missed, surfacing as a hidden ~70 ms
        compile on the first admission's TTFT; caught by the
        hot_path_guard serving-lifetime pin, ISSUE 11), plus — when
        the draft–verify subsystem is on (ISSUE 12) — the verify step
        at ``q_len = spec.k + 1`` and the ``[1, chunk_size]`` chunked-
        prefill step.  Every warmup launch writes only into scratch
        page 0 (and, of a state pool, scratch slot 0), which no reader
        ever sees; the zero-compiles-after-warmup pin runs a
        speculative + chunked trace too."""
        t0 = time.perf_counter()
        self._register_scopes()
        wpool = self.cache.window_pool
        for S in self.prefill_widths:
            z = jnp.zeros((1, S), jnp.int32)
            _, *kv0 = self._prefill_fn(self.params, z, z, z, np.int32(0))
            # warm the admission scatter with its real shapes: the
            # warmup prefill's K/V row scattered into the scratch page
            zs = np.zeros((S,), np.int32)
            self._scatter_row(kv0, zs, zs, zs)
        b = self.max_batch
        p_max = self.cache.max_pages_per_request

        def tables(rows):
            t = ()
            if wpool is not None:
                t += (jnp.zeros((rows, wpool.max_pages_per_request),
                                jnp.int32), jnp.zeros((rows,), jnp.int32))
            if self.cache.state_pool is not None:
                t += (jnp.zeros((rows,), jnp.int32),) * 2
            return t

        # twice: the second launch takes the first's tokens as its
        # token input, as every launch of the serving loop takes the
        # one before it (ISSUE 34), and the last leaves the array that
        # stands in where no launch went before
        for _ in range(2):
            out = self._decode_fn(
                self.params, *self._pool_state(),
                jnp.zeros((b,), jnp.int32), self._no_prev,
                jnp.full((b,), -1, jnp.int32), jnp.zeros((b,), jnp.int32),
                jnp.zeros((b, p_max), jnp.int32), jnp.ones((b,), jnp.int32),
                *tables(b))
            self._no_prev = out[0]
            self._stats(self._bind_pools(out[1:]))
        if self._verify_fn is not None:
            qw = self.spec_k + 1
            zq = jnp.zeros((b, qw), jnp.int32)
            out = self._verify_fn(
                self.params, *self._pool_state(), zq, zq, zq, zq,
                jnp.zeros((b, p_max), jnp.int32),
                jnp.full((b,), qw, jnp.int32), *tables(b))
            self._bind_pools(out[1:])
        if self._chunk_fn is not None:
            cs = self.chunk_size
            zc = jnp.zeros((1, cs), jnp.int32)
            out = self._chunk_fn(
                self.params, *self._pool_state(), zc, zc, zc, zc,
                jnp.zeros((1, p_max), jnp.int32),
                jnp.full((1,), cs, jnp.int32), *tables(1))
            self._bind_pools(out[1:])
        if self.prefix_index is not None:
            # r17: the prefix-sharing engine runs one more executable —
            # the COW page copy — on the admission path; warm it too so
            # the first shared-prefix hit compiles nothing
            self.cache.warm_copy()
        if self.kv_import:
            # r18: a decode replica lands shipped pages through one
            # more executable — warm it so the first inbound shipment
            # compiles nothing (the chaos_disagg zero-recompile pin)
            self.cache.warm_import()
        if self.prefill_only:
            # ... and a prefill replica reads pages OUT through a
            # device-side page-slice gather; warm that too, for the
            # same zero-recompile pin on the export side
            self.cache.warm_export()
        jax.block_until_ready(self.cache.k)
        return time.perf_counter() - t0

    def _register_scopes(self) -> None:
        """Hand every executable this engine launches to
        :mod:`apex_tpu.telemetry.scopes`, at the shapes and placements
        it is launched with: a dict entry each, nothing lowered or
        compiled (``scope_maps()`` does that, on request)."""
        state = (self.params,) + self._pool_state()
        for S in self.prefill_widths:
            structs = self._executable_arg_structs(S)
            scopes.register(scopes.executable_name(self._prefill_fn),
                            self._prefill_fn,
                            (self.params,) + structs["prefill"][1:],
                            variant=S)
        # only the prefill row depends on S: any width's structs serve
        for name, fn in (("decode", self._decode_fn),
                         ("verify", self._verify_fn),
                         ("chunk", self._chunk_fn)):
            if fn is not None:
                scopes.register(scopes.executable_name(fn), fn,
                                state + structs[name][len(state):])

    def _scatter_row(self, kv, pages, offsets, wpages, slot=0) -> None:
        """A prefill row's stacks into the pool(s) they are for: K and V
        (a latent model: its one stack), then the window layers' into
        the window pool at ``wpages``, then the state-space layers'
        final states and tails into ``slot``."""
        self.cache.write_tokens(
            kv[0], None if self.decoder.latent else kv[1], pages, offsets)
        if self.cache.window_pool is not None:
            self.cache.window_pool.write_tokens(kv[2], kv[3], wpages,
                                                offsets)
        if self.cache.state_pool is not None:
            n = self.decoder.kv_stacks
            self.cache.state_pool.write(slot, kv[n - 2], kv[n - 1])

    def prefill_width(self, context_len: int) -> int:
        """The row a context of ``context_len`` tokens is prefilled in:
        the narrowest rung of :attr:`prefill_widths` that holds it.  A
        function of the request's own length and of nothing else, so
        the row is the same served alone or in a batch."""
        return self.prefill_widths[
            bisect.bisect_left(self.prefill_widths, context_len)]

    def _prefill_request(self, req: Request) -> None:
        """One prefill row for one request: compute K/V for the whole
        context (prompt + pre-preemption tokens), scatter it into the
        request's pages, sample the next token."""
        ctx = req.context
        C = len(ctx)
        S = self.prefill_width(C)
        ps = self.cache.page_size
        # reserve-at-admit invariant (ISSUE 10 satellite): admission
        # allocated this request's context pages; prefill must never
        # find the reservation gone (the admit-then-exhaust window the
        # regression test closes) — a violation here is a scheduler
        # bug, not a capacity event
        need = self.cache.pages_needed(C)
        if len(req.pages) < need:
            raise RuntimeError(
                f"request {req.rid}: prefill found {len(req.pages)} "
                f"reserved pages, context needs {need} — pages must be "
                "reserved at admission")
        _fault_point("prefill", req.rid)
        prefill_t0 = self.clock()
        wpool = self.cache.window_pool
        with phase("engine.prefill", rid=req.rid, C=C, S=S) as span:
            with phase("prefill.build"):
                tokens = np.zeros((1, S), np.int32)
                tokens[0, :C] = ctx
                seg = np.zeros((1, S), np.int32)
                seg[0, :C] = 1
                positions = np.zeros((1, S), np.int32)
                positions[0, :C] = np.arange(C)
            with phase("prefill.dispatch"):
                # np.int32 scalar, NOT jnp.asarray(C - 1): converting a
                # python int eagerly compiles a tiny convert executable
                # the warmup never built — a hidden ~60 ms stall on the
                # first admission's TTFT (caught by hot_path_guard's
                # serving-lifetime pin)
                next_tok, *kv = self._prefill_fn(
                    self.params, jnp.asarray(tokens), jnp.asarray(seg),
                    jnp.asarray(positions), np.int32(C - 1))
            with phase("prefill.scatter"):
                # packed position t -> (page, in-page offset); padding
                # -> scratch
                pages = np.zeros((S,), np.int32)
                offsets = np.zeros((S,), np.int32)
                idx = np.arange(C)
                pages[:C] = np.asarray(req.pages, np.int32)[idx // ps]
                offsets[:C] = idx % ps
                wpages = None
                if wpool is not None:
                    # the window layers' K/V, into the pages of the
                    # window's tail; what lies before it is never read
                    wpages = np.zeros((S,), np.int32)
                    wpages[:C], _ = wpool.write_targets(req.window, idx)
                self._scatter_row(kv, pages, offsets, wpages, req.slot)
            req.kv_len = C
            if req.slot is not None:
                # a whole row starts from zero and overwrites the slot
                span.attrs["state_in"] = 0
            self._register_prefix(ctx, req.pages)
            if self.prefix_index is not None:
                # a whole row computes every token of its context
                span.attrs.update(shared=0, ctx=C)
            with phase("prefill.fetch"):
                # the wait for the device
                first = int(next_tok)
                # the block's counters follow the K/V of the pool(s)
                span.attrs.update(self._stats(
                    kv[self.decoder.kv_stacks:]))
            req.generated.append(first)
            if req.first_token_t is None:
                req.first_token_t = self.clock()
                # colocated path: the token is streamable the instant
                # it is sampled (a shipped request's stream_t is stamped
                # at adoption instead — r19 shipping-aware TTFT)
                req.stream_t = req.first_token_t
        if self.telemetry is None:
            return
        # single-shot prefill = one prefill_chunk span covering the
        # whole context (the chunked path emits one per chunk)
        life = self._life(req)
        self._emit("span", rid=req.rid,
                   span_id=f"{req.rid}:prefill_chunk:{life}:0",
                   parent_id=f"{req.rid}:admit:{life}",
                   kind="prefill_chunk", t_start=prefill_t0,
                   t_end=self.clock())

    def _register_prefix(self, ctx: Sequence[int],
                         pages: List[int]) -> None:
        """Register the PAGE-ALIGNED prefix of a freshly prefilled
        context in the prefix index.  Alignment is deliberate: a
        partial tail page would be shared while its owner's next
        decode append still writes into it, forcing a COW on the
        owner's own hot path — the aligned prefix is immutable by
        construction (every later write lands at positions
        ``>= len(ctx) > aligned``)."""
        if self.prefix_index is None:
            return
        ps = self.cache.page_size
        aligned = (len(ctx) // ps) * ps
        if aligned >= ps:
            self.prefix_index.register(ctx[:aligned],
                                       pages[:aligned // ps])

    def _check_private(self, pages, what: str) -> None:
        """Write-path guard (r17): a device write targeting a page
        with refcount > 1 would corrupt another reader's prefix — COW
        must have swapped in a private copy before the launch.  By
        construction (aligned registration + admission-time COW) this
        never fires; it is the cheap host-side proof."""
        if self.prefix_index is None:
            return
        for p in pages:
            if self.cache.is_shared(int(p)):
                raise RuntimeError(
                    f"{what} would write shared page {int(p)} "
                    "(refcount > 1) — copy-on-write missing")

    def _decode_batch(self, rows: List[Request]) -> int:
        """Launch one decode step for ``rows`` (≤ max_batch, idle-padded
        to the fixed batch width), THEN land the launch before it
        (ISSUE 34): build from host state alone (a row's position,
        ``kv_len`` and page follow from ``seq_len + in_flight``),
        dispatch with the token input put together on the device from
        the previous launch's unfetched output, and only then fetch and
        commit that previous launch (:meth:`_land`).  The device queue
        holds this launch when the previous one ends; the host's turn
        runs under the device's step.  Returns 1 if a launch was in
        flight when this one was dispatched, else 0."""
        _fault_point("decode", self.decode_steps)
        flight = self._flight
        with phase("decode.build"):
            # opt-in read-back validation: the pages this step is about
            # to attend over must still match their recorded CRCs
            self.cache.verify_pages([req.pages for req in rows])
            b = self.max_batch
            ps = self.cache.page_size
            tokens = np.zeros((b,), np.int32)
            src = np.full((b,), -1, np.int32)
            positions = np.zeros((b,), np.int32)
            kv_len = np.ones((b,), np.int32)
            written: List[int] = []   # the page each row's new K/V lands in
            was = ({req.rid: i for i, req in enumerate(flight.rows)}
                   if flight is not None else {})
            for i, req in enumerate(rows):
                if req.in_flight:
                    src[i] = was[req.rid]   # its last token: on the device
                else:
                    tokens[i] = req.generated[-1]
                n = req.seq_len + req.in_flight
                positions[i] = n - 1
                kv_len[i] = n
                written.append(req.pages[(n - 1) // ps])
            self._check_private(written, "decode append")
            page_table, wtables = self._tables(rows, b)
        with phase("decode.dispatch"):
            out = self._decode_fn(
                self.params, *self._pool_state(), jnp.asarray(tokens),
                self._no_prev if flight is None else flight.next_tok,
                jnp.asarray(src), jnp.asarray(positions), page_table,
                jnp.asarray(kv_len), *wtables)
            stats = self._bind_pools(out[1:])
            # (opt-in) read back what the launch wrote, so the records
            # match the pool as every later launch finds it
            self.cache.refresh_page_crcs(written)
        self._land()
        self._flight = _Flight(rows, out[0], stats)
        self.sched.in_flight = True
        for req, n in zip(rows, kv_len.tolist()):
            # the K/V is in the pool for every launch that follows
            req.kv_len = n
            req.in_flight = 1
        return int(flight is not None)

    def _land(self) -> None:
        """Bring the launch in flight to the host, if there is one:
        ``decode.fetch`` is the wait for what is left of it, and
        ``decode.commit`` appends each row's token to ``generated``.  A
        token whose request finished meanwhile is dropped: the one
        launched after an EOS that had not landed yet (its K/V went to
        a page the request then still owned), or a row that timed out.
        What landed waits in ``_landed`` for the ``engine.decode`` span
        that reports it."""
        flight, self._flight = self._flight, None
        self.sched.in_flight = False
        if flight is None:
            return
        with phase("decode.fetch"):
            # the wait for the device
            next_tok = np.asarray(flight.next_tok)
            self._landed_stats = self._stats(flight.stats)
        with phase("decode.commit"):
            for req, tok in zip(flight.rows, next_tok.tolist()):
                req.in_flight = 0
                if req.state == RUNNING and not req.done:
                    req.generated.append(tok)
                    self._landed.append(req.rid)

    def _land_and_retire(self) -> None:
        """:meth:`_land`, and retire what the landed tokens finished:
        what the scheduler calls before it preempts a row that has a
        token in flight."""
        self._land()
        self._retire(self.clock())

    def _verify_batch(self, rows: List[Request],
                      drafts: Dict[int, List[int]]
                      ) -> Tuple[int, int, List[int]]:
        """One speculative decode boundary: score every row's last
        committed token + draft in ONE verify launch
        (``q_len = spec.k + 1``), commit each row's longest matching
        prefix + bonus token, roll rejected rows back.

        Rows are FRONT-padded to the fixed window (pad rows scatter
        into scratch and their outputs are discarded), so a row with a
        ``j``-token draft occupies the last ``j + 1`` query rows and
        ``kv_len = seq_len + j`` keeps flash_decode's causal alignment
        exact — a draft-less row (``j = 0``) is literally a plain
        decode step computed through the verify shape.  Rollback is
        plain accounting: ``kv_len`` advances only over committed
        draft rows (stale K/V past it is unreachable and overwritten
        when the sequence grows back), and surplus tail pages return
        to the pool via ``free_tail``.  Returns the ``drafted`` and
        ``accepted`` token counts for the ``decode_step`` telemetry
        fields and the tokens ``committed`` per row (the
        ``engine.decode`` phase's token times)."""
        _fault_point("decode", self.decode_steps)
        with phase("decode.build"):
            self.cache.verify_pages([req.pages for req in rows])
            b, qw = self.max_batch, self.spec_k + 1
            ps = self.cache.page_size
            tokens = np.zeros((b, qw), np.int32)
            positions = np.zeros((b, qw), np.int32)
            wpages = np.zeros((b, qw), np.int32)
            woffs = np.zeros((b, qw), np.int32)
            # idle rows: kv_len == q_len
            kv_len = np.full((b,), qw, np.int32)
            row_draft: List[List[int]] = []
            written: List[int] = []
            for i, req in enumerate(rows):
                d = drafts.get(req.rid, [])
                row_draft.append(d)
                S, j = req.seq_len, len(d)
                pad = qw - (j + 1)
                pos = np.arange(S - 1, S + j)
                tokens[i, pad:] = [req.generated[-1]] + d
                positions[i, pad:] = pos
                pg = np.asarray(req.pages, np.int32)[pos // ps]
                wpages[i, pad:] = pg
                woffs[i, pad:] = pos % ps
                kv_len[i] = S + j
                written.extend(int(p) for p in pg)
            self._check_private(written, "verify append")
            page_table, wtables = self._tables(rows, b)
        with phase("decode.dispatch"):
            out = self._verify_fn(
                self.params, *self._pool_state(),
                jnp.asarray(tokens), jnp.asarray(positions),
                jnp.asarray(wpages), jnp.asarray(woffs), page_table,
                jnp.asarray(kv_len), *wtables)
            next_tok = out[0]
            self._bind_pools(out[1:])
        with phase("decode.fetch"):
            next_tok = np.asarray(next_tok)
        committed: List[int] = []
        with phase("decode.commit"):
            self.cache.refresh_page_crcs(written)
            drafted = accepted = 0
            for i, req in enumerate(rows):
                d = row_draft[i]
                S, j = req.seq_len, len(d)
                pad = qw - (j + 1)
                out, n_draft_kv, a = commit_tokens(
                    d, next_tok[i, pad:].tolist(), eos_id=req.eos_id,
                    remaining=req.max_new_tokens - len(req.generated))
                req.generated.extend(out)
                req.kv_len = S + n_draft_kv
                # rollback: pages grown for rejected draft rows go back
                # to the pool (the next boundary's growth re-takes what
                # the committed tokens actually need — lowest-first, so
                # the SAME pages come back, deterministically)
                keep = self.cache.pages_needed(
                    max(req.seq_len, req.kv_len))
                self.cache.free_tail(req.pages, keep)
                drafted += j
                accepted += a
                committed.append(len(out))
        if self.proposer is not None:
            self.proposer.observe(drafted, accepted)
        return drafted, accepted, committed

    def _chunk_step(self, req: Request, start: int, n: int) -> None:
        """Advance one chunked prefill by ``n <= chunk_size`` tokens:
        compute K/V for context positions ``[start, start + n)``
        against the pages earlier chunks already filled, through the
        fixed ``[1, chunk_size]`` executable (front-padded; pad rows
        scatter into scratch).  The FINAL chunk's last-position argmax
        is the request's first sampled token — earlier chunks never
        pull anything to the host, so a long prefill stays one async
        dispatch per boundary."""
        _fault_point("prefill", req.rid)
        t0 = self.clock()
        cs = self.chunk_size
        with phase("engine.prefill", rid=req.rid, C=n, S=cs) as span:
            with phase("prefill.build"):
                # opt-in CRC read-back, like every other pool-reading
                # step: this chunk attends over the pages earlier
                # chunks filled — a corrupted earlier page must raise
                # HERE, before the final chunk could sample the
                # request's first token from damaged K/V and commit it
                # into the stream (review-found, pinned; pages past the
                # filled prefix have no CRC record and are skipped by
                # verify_pages)
                self.cache.verify_pages([req.pages])
                ps = self.cache.page_size
                ctx = req.context
                need = self.cache.pages_needed(start + n)
                if len(req.pages) < need:
                    raise RuntimeError(
                        f"request {req.rid}: chunk [{start}, {start + n}) "
                        f"found {len(req.pages)} reserved pages, needs "
                        f"{need} — pages must be reserved at admission")
                pad = cs - n
                tokens = np.zeros((1, cs), np.int32)
                positions = np.zeros((1, cs), np.int32)
                wpages = np.zeros((1, cs), np.int32)
                woffs = np.zeros((1, cs), np.int32)
                pos = np.arange(start, start + n)
                tokens[0, pad:] = ctx[start:start + n]
                positions[0, pad:] = pos
                pg = np.asarray(req.pages, np.int32)[pos // ps]
                wpages[0, pad:] = pg
                woffs[0, pad:] = pos % ps
                self._check_private(pg, "chunk scatter")
                # a request's first chunk starts from zero, every later
                # one from what the chunk before left in the slot
                page_table, wtables = self._tables([req], 1,
                                                   fresh=start == 0)
                if req.slot is not None:
                    span.attrs["state_in"] = int(start != 0)
            with phase("prefill.dispatch"):
                out = self._chunk_fn(
                    self.params, *self._pool_state(),
                    jnp.asarray(tokens), jnp.asarray(positions),
                    jnp.asarray(wpages), jnp.asarray(woffs), page_table,
                    jnp.asarray(np.full((1,), start + n, np.int32)),
                    *wtables)
                next_tok = out[0]
                # the counters of a chunk wait, like its token, for the
                # request's last chunk: nothing is fetched before it
                pending = self._chunk_stats.setdefault(req.rid, [])
                if start == 0:
                    pending.clear()
                pending.extend(self._bind_pools(out[1:]))
            self.cache.refresh_page_crcs(int(p) for p in pg)
            req.kv_len = start + n
            req.prefill_pos = start + n
            self._release_windows([req])
            if self.prefix_index is not None:
                # the tokens of the context that rode in on shared pages
                span.attrs["shared"] = req.prefix_tokens
            if req.prefill_pos >= len(ctx):
                # prefill complete: sample the first token and leave
                # chunked mode — the request decodes from the next
                # boundary
                req.prefill_pos = None
                self._register_prefix(ctx, req.pages)
                if self.prefix_index is not None:
                    span.attrs["ctx"] = len(ctx)    # the prompt is finished
                with phase("prefill.fetch"):
                    first = int(np.asarray(next_tok)[0])
                    per_chunk = [self._stats((a,)) for a in
                                 self._chunk_stats.pop(req.rid)]
                for name in self.decoder.stat_names:
                    # a count adds up over the chunks, a maximum does not
                    fold = max if name.endswith("_max") else sum
                    span.attrs[name] = fold(c[name] for c in per_chunk)
                req.generated.append(first)
                if req.first_token_t is None:
                    req.first_token_t = self.clock()
                    req.stream_t = req.first_token_t
        if self.telemetry is None:
            return
        life = self._life(req)
        self._emit("span", rid=req.rid,
                   span_id=f"{req.rid}:prefill_chunk:{life}:{start}",
                   parent_id=f"{req.rid}:admit:{life}",
                   kind="prefill_chunk", t_start=t0,
                   t_end=self.clock())

    # -- the engine step ---------------------------------------------------

    def _emit(self, type_: str, **payload) -> None:
        if self.telemetry is not None:
            self.telemetry.emit(type_, step=self.steps, **payload)

    @staticmethod
    def _life(req: Request) -> str:
        """The r19 admission-life discriminator shared by every span
        of one (re)admission — ``preemptions`` alone is not unique
        across a fallback re-admission, ``admit_t`` on the shared
        clock makes it so (docs/tracing.md, "Span identity")."""
        return f"{req.preemptions}:{req.admit_t:.6f}"

    def _retire(self, now: float) -> List[Request]:
        self._released_full += sum(
            len(r.pages) for r in self.sched.running if r.done)
        done = self.sched.retire_finished(now)
        self._retired += len(done)
        for req in done:
            if self.proposer is not None:
                self.proposer.release(req.rid)
            if self.telemetry is None:
                continue
            n = len(req.generated)
            ev = dict(rid=req.rid, reason=req.finish_reason,
                      new_tokens=n, preemptions=req.preemptions)
            # r19 shipping-aware TTFT (the PR 18 open item): measure
            # to stream_t — when the first token became STREAMABLE —
            # so a disaggregated request's kv_ship wall lands in TTFT
            # (where the SLO feels it), not hidden inside TPOT.
            # Colocated paths have stream_t == first_token_t; a
            # migrated re-prefill keeps the original first-token time
            # (the client already held those tokens).
            stream_t = (req.stream_t if req.stream_t is not None
                        else req.first_token_t)
            if req.first_token_t is not None:
                ev["ttft_ms"] = round(
                    (stream_t - req.arrival_t) * 1e3, 3)
                if req.ship_s > 0.0:
                    ev["ship_ms"] = round(req.ship_s * 1e3, 3)
                if n > 1 and req.finish_t is not None:
                    ev["tpot_ms"] = round(
                        (req.finish_t - stream_t) / (n - 1) * 1e3,
                        3)
            if req.deadline_t is not None and req.finish_t is not None:
                # a real bool, present only when a deadline existed —
                # optionality explicit, never a sentinel
                ev["deadline_hit"] = bool(req.finish_t <= req.deadline_t)
            self._emit("request_retire", **ev)
            self._emit_retire_spans(req, stream_t, now)
        return done

    def _emit_retire_spans(self, req: Request, stream_t, now: float
                           ) -> None:
        """The decode-side tail of the request's trace (r19), emitted
        once at retirement — spans buffer host-side state only, no
        device fetches, so the decode loop stays host-sync-free:
        ``decode_wait`` (prefill done -> streamable: the export-pump
        wait plus the kv_ship wall on a disaggregated path, ~0
        colocated), ``decode_steps`` (stream -> finish), and the
        ``stream_emit`` point span the TTFT decomposition ends at."""
        if self.telemetry is None or stream_t is None \
                or req.admit_t is None:
            return
        life = self._life(req)
        dw = f"{req.rid}:decode_wait:{life}"
        self._emit("span", rid=req.rid, span_id=dw,
                   parent_id=f"{req.rid}:admit:{life}",
                   kind="decode_wait", t_start=req.first_token_t,
                   t_end=stream_t)
        self._emit("span", rid=req.rid,
                   span_id=f"{req.rid}:decode_steps:{life}",
                   parent_id=dw, kind="decode_steps",
                   t_start=stream_t, t_end=now)
        self._emit("span", rid=req.rid,
                   span_id=f"{req.rid}:stream_emit:{life}",
                   parent_id=dw, kind="stream_emit",
                   t_start=stream_t, t_end=stream_t)

    def _expire(self, now: float) -> bool:
        """Deadline enforcement for this step boundary: shed queued
        requests that can no longer meet their SLO, retire in-flight
        expirations with a ``timeout`` status (pages freed
        immediately).  Each drop is a ``request_timeout`` event saying
        WHERE the request was when its deadline died."""
        shed, timed_out = self.sched.expire_deadlines(
            now, min_service_s=self.shed_min_service_s)
        if self.proposer is not None:
            # deadline deaths are retirements too — every terminal
            # transition must drop per-rid proposer state (the timeout
            # path leaked the suffix cache; review-found, pinned)
            for req in shed + timed_out:
                self.proposer.release(req.rid)
        for req in shed:
            self._emit("request_timeout", rid=req.rid, where="queued",
                       overshoot_ms=round((now - req.deadline_t) * 1e3, 3))
        for req in timed_out:
            self._emit("request_timeout", rid=req.rid, where="running",
                       overshoot_ms=round((now - req.deadline_t) * 1e3, 3))
        return bool(shed or timed_out)

    def step(self) -> bool:
        """One engine iteration: expire deadlines → retire →
        admit+prefill → retire → grow/preempt → launch decode step
        *n* → land decode step *n − 1* (ISSUE 34: one launch stays in
        flight, so a token is in ``generated`` one step after its
        launch; docs/serving.md "One launch in flight").  Returns True
        if any work was done.  With a ``watchdog``, the whole step
        (prefill + decode device work included) runs under an armed
        deadline, so a wedged device step escalates instead of
        hanging the trace."""
        if self.watchdog is None:
            return self._step_body()
        with self.watchdog.step(self.steps):
            return self._step_body()

    def _propose_drafts(self) -> Dict[int, List[int]]:
        """Ask the proposer for each decode-ready row's draft, clamped
        so the commit can never overshoot ``max_new_tokens`` (which
        also bounds every written position under ``max_position`` —
        the submit-time ``prompt + max_new <= max_position`` check
        makes the clamp transitive).  Empty drafts mean plain decode."""
        drafts: Dict[int, List[int]] = {}
        for req in self.sched.running:
            if req.prefill_pos is not None:
                continue   # mid-chunk: nothing to decode yet
            k_eff = min(self.spec_k,
                        req.max_new_tokens - len(req.generated) - 1)
            if k_eff <= 0:
                continue
            d = self.proposer.propose(req.rid, req.context, k_eff)
            if d:
                drafts[req.rid] = [int(t) for t in d[:k_eff]]
        return drafts

    def _step_body(self) -> bool:
        """One step as phases (docs/telemetry.md, "Step phases"):
        ``engine.step`` around one ``engine.prefill`` per admitted
        request, ``engine.grow`` and ``engine.decode`` (``decode.build``
        and ``decode.dispatch`` of this step's launch, then
        ``decode.fetch`` and ``decode.commit`` of the launch before
        it); retirement and admission are its own time and its
        counters."""
        cpu0_ns = time.process_time_ns()
        with phase("engine.step", step=self.steps) as span:
            progress = self._step_phases(span.attrs)
            span.attrs["cpu_ns"] = time.process_time_ns() - cpu0_ns
        self.steps += 1
        if isinstance(self.clock, SimClock):
            self.clock.advance()
        return progress

    def _release_windows(self, reqs: Sequence[Request]) -> None:
        """After a decode step or a chunk: the window pool takes back
        the pages that slid out of ``reqs``' windows (``engine.release``
        with ``released_window``).  The full pool gives nothing back
        before a request retires: ``engine.step`` counts those pages as
        ``released_full``."""
        if self.cache.window_pool is None:
            return
        with phase("engine.release") as span:
            span.attrs["released_window"] = self.sched.slide_windows(reqs)

    def _held_pages(self, counters: Dict[str, int]) -> None:
        """Once a step, where pages have two lifetimes: the pages the
        running requests hold in each pool (``held_full``,
        ``held_window``), a layer's worth each, and what ONE lifetime
        for every layer would hold for the same requests
        (``held_uniform``: every layer would keep what the full layers
        keep); and ``released_full``, the pages of the full pool that
        retirements gave back since the last step's count."""
        if self.cache.window_pool is None:
            return
        running = self.sched.running
        counters["released_full"] = self._released_full
        self._released_full = 0
        counters["held_full"] = sum(len(r.pages) for r in running)
        counters["held_window"] = sum(
            len(r.window.pages) for r in running if r.window is not None)
        counters["held_uniform"] = counters["held_full"]

    def _step_phases(self, counters: Dict[str, int]) -> bool:
        if self.proposer is not None:
            # a speculative boundary reads committed tokens (the
            # proposer) and commits by their values: nothing runs ahead
            self._land()
        retired0 = self._retired
        now = self.clock()
        progress = self._expire(now)
        progress = bool(self._retire(now)) or progress
        if self.chunk_size is not None:
            chunk_plan, admitted = self.sched.schedule_prefill()
        else:
            chunk_plan, admitted = [], self.sched.admit()
        counters["admitted"] = len(admitted)
        for req in admitted:
            req.admit_t = now
            ctx_tokens = req.seq_len   # == len(context), O(1)
            if req.prefill_pos is None:
                self._prefill_request(req)
            progress = True
            if self.telemetry is None:
                continue
            ev = dict(rid=req.rid, context_tokens=ctx_tokens,
                      pages=len(req.pages), preemptions=req.preemptions)
            if req.prefill_pos is not None:
                ev["chunked"] = True
            if self.prefix_index is not None:
                # a real bool on EVERY admission while sharing is on
                # (hits and misses both) — the summarize hit-rate needs
                # the denominator, and optional-means-absent would make
                # a miss indistinguishable from a sharing-off engine
                ev["prefix_hit"] = bool(req.prefix_hit)
            self._emit("request_admit", **ev)
            # r19 trace: every (re)admission opens a new life —
            # queue_wait is root-level (arrival -> admission), admit
            # covers the admission itself plus a whole-row prefill
            # (a chunked admission's prefill wall rides its
            # prefill_chunk child spans instead)
            life = self._life(req)
            qid = f"{req.rid}:queue_wait:{life}"
            self._emit("span", rid=req.rid, span_id=qid,
                       kind="queue_wait", t_start=req.arrival_t,
                       t_end=now)
            self._emit("span", rid=req.rid,
                       span_id=f"{req.rid}:admit:{life}",
                       parent_id=qid, kind="admit", t_start=now,
                       t_end=self.clock())
        for req, start, n in chunk_plan:
            self._chunk_step(req, start, n)
            progress = True
        # a request whose budget was a single token is done at prefill
        progress = bool(self._retire(now)) or progress
        self._held_pages(counters)
        spool = self.cache.state_pool
        if spool is not None:
            counters["state_slots_held"] = spool.slots_used
            counters["state_slots"] = spool.num_slots
        if self.prefix_index is not None:
            counters["prefix_entries"] = len(self.prefix_index)
            counters["prefix_pages_shared"] = self.cache.pages_shared
        evicted: List[Request] = []
        drafts: Dict[int, List[int]] = {}
        if self.sched.running and not self.prefill_only:
            with phase("engine.grow"):
                if self.proposer is not None:
                    drafts = self._propose_drafts()
                # growth covers each drafted row's verify footprint too
                # (seq_len + draft); a row preempted while growing
                # simply drops out of this boundary, draft unused — the
                # proposer is stateless over committed tokens, so
                # nothing leaks
                evicted = self.sched.ensure_decode_capacity(
                    extra={rid: len(d) for rid, d in drafts.items()}
                    or None)
        counters["evicted"] = len(evicted)
        # growth may have landed the launch in flight for a preemption,
        # and retired what that finished
        counters["retired"] = self._retired - retired0
        # a prefill_only engine never decodes: finished prefills hold
        # their first token and wait for export_request to ship them.
        # A row whose budget the token in flight spends is not launched
        # again: it waits for that token and retires on it
        rows = ([] if self.prefill_only else
                [r for r in self.sched.running if r.launchable])
        if rows or self._flight is not None or self._landed:
            spec_fields = {}
            with phase("engine.decode", rows=len(rows)) as span:
                if spool is not None:
                    # the rows whose slot is not the scratch slot
                    span.attrs["state_rows"] = len(rows)
                # rids: the requests whose token LANDED in this span;
                # committed: how many each, where that is not one
                committed: Optional[Tuple[int, ...]] = None
                in_flight = 0
                if any(r.rid in drafts for r in rows):
                    drafted, accepted, per_row = self._verify_batch(
                        rows, drafts)
                    committed = (1,) * len(self._landed) + tuple(per_row)
                    self._landed.extend(r.rid for r in rows)
                    span.attrs["committed"] = committed
                    spec_fields = {"spec_verify": True,
                                   "spec_drafted": drafted,
                                   "spec_accepted": accepted}
                elif rows:
                    # every draft came back empty (or speculation is
                    # off): the plain q_len=1 decode executable is
                    # cheaper, and runs one launch ahead of the host
                    in_flight = self._decode_batch(rows)
                else:
                    # nothing to launch: the launch in flight is all
                    # that is left, and its tokens end the step
                    self._land()
                new_tokens = (len(self._landed) if committed is None
                              else sum(committed))
                span.attrs.update(self._landed_stats, in_flight=in_flight,
                                  rids=tuple(self._landed))
                self._landed, self._landed_stats = [], {}
            if rows:
                self._release_windows(rows)
                self.decode_steps += 1
            if self.telemetry is not None:
                if self.prefix_index is not None:
                    # pages with refcount > 1 right now — the live
                    # measure of how much pool the sharing is actually
                    # saving
                    spec_fields["pool_shared_pages"] = \
                        self.cache.pages_shared
                # evictions ride the decode_step payload (a preempted
                # request is also visible later: its re-admission's
                # request_admit carries preemptions > 0).  step_ms and
                # phase_ms are the engine.decode phase and its children
                # as the ring holds them: one measurement for the
                # operator's stream and the benchmark's readers.
                # batch is what was launched, new_tokens what landed
                self._emit("decode_step", batch=len(rows),
                           new_tokens=new_tokens, in_flight=in_flight,
                           pool_used=self.cache.pages_used,
                           pool_pages=self.cache.num_pages - 1,
                           evicted=[r.rid for r in evicted],
                           step_ms=span.record.ms,
                           phase_ms={c.name: c.ms
                                     for c in span.children},
                           **spec_fields)
            progress = True
        elif evicted or chunk_plan:
            progress = True
        return progress

    # -- crash recovery (ISSUE 10) -----------------------------------------

    def snapshot(self) -> Dict[str, Any]:
        """Serializable capture of the HOST-side serving state: queue
        order (running first, in admission order, then waiting) plus
        each live request's token state and counters.

        KV pages are DELIBERATELY excluded: the PR 8 preemption
        contract makes re-prefill from the kept tokens regenerate a
        request's KV deterministically, so the pool never needs to be
        checkpointed — the snapshot is a few KB of tokens, not
        gigabytes of HBM.  ``restore`` re-prefills live requests
        through that existing path.  JSON-serializable by construction
        (pinned in the round-trip test).  The launch in flight lands
        first: the capture holds every token the device has produced."""
        self._land()

        def rec(req: Request, was_running: bool) -> Dict[str, Any]:
            return {
                "rid": req.rid,
                "prompt": list(req.prompt),
                "max_new_tokens": req.max_new_tokens,
                "eos_id": req.eos_id,
                "arrival_t": req.arrival_t,
                "deadline_s": req.deadline_s,
                "generated": list(req.generated),
                "preemptions": req.preemptions,
                "admit_t": req.admit_t,
                "first_token_t": req.first_token_t,
                "was_running": was_running,
            }

        return {
            "format": 1,
            "next_rid": self._next_rid,
            "steps": self.steps,
            "decode_steps": self.decode_steps,
            "requests": ([rec(r, True) for r in self.sched.running]
                         + [rec(r, False) for r in self.sched.waiting]),
        }

    def restore(self, snap: Dict[str, Any]) -> List[Request]:
        """Rebuild serving state from a :meth:`snapshot` into THIS
        (idle, freshly constructed) engine.  Every snapshotted request
        — running or waiting at capture — enters the waiting queue in
        snapshot order with no pages; previously-running requests are
        re-admitted first and re-prefilled through the deterministic
        preemption path, so the continued token streams are bitwise
        the uninterrupted run's.  Returns the restored request
        handles."""
        self._land()
        if self.sched.running or self.sched.waiting:
            raise RuntimeError(
                "restore into a busy engine — serving state would be "
                "interleaved; restore only into a fresh engine")
        if snap.get("format") != 1:
            raise ValueError(
                f"unknown serving snapshot format {snap.get('format')!r}")
        restored: List[Request] = []
        for r in snap["requests"]:
            req = Request(
                rid=int(r["rid"]), prompt=list(r["prompt"]),
                max_new_tokens=int(r["max_new_tokens"]),
                eos_id=r["eos_id"], arrival_t=float(r["arrival_t"]),
                deadline_s=r["deadline_s"])
            req.generated = list(r["generated"])
            req.preemptions = int(r["preemptions"])
            req.admit_t = r["admit_t"]
            req.first_token_t = r["first_token_t"]
            restored.append(req)
        # validate BEFORE mutating anything, so a refused restore is
        # atomic (no half-queued engine, no duplicated retire events
        # on a retry into a fresh engine): every live request must be
        # servable by THIS engine's geometry — a chunked engine's
        # snapshot restored into a chunk-less one would otherwise
        # queue a beyond-the-row request admission can never take,
        # starving the whole FIFO forever (review-found, pinned; the
        # twin of recover()'s chunk_size-preserving rebuild)
        live = [req for req in restored if not req.done]
        for req in live:
            self.sched.check_servable(req)
        if self.sched.max_queue is not None and \
                len(live) > self.sched.max_queue:
            # capacity mismatch is refused with the same atomicity as
            # geometry mismatch (ISSUE 16): a migration target that
            # cannot QUEUE the batch must refuse before mutating, so
            # the caller can pick another target with the snapshot
            # intact
            raise ValueError(
                f"snapshot holds {len(live)} live requests > "
                f"max_queue {self.sched.max_queue}")
        for req in restored:
            if req.done:
                # captured between its last decode and its retirement:
                # already complete — re-admitting would overshoot
                # max_new_tokens by re-prefilling + sampling again
                self._finish_restored(req)
            else:
                req.state = WAITING
                self.sched.waiting.append(req)
        self._next_rid = max(self._next_rid, int(snap["next_rid"]))
        self.steps = int(snap["steps"])
        self.decode_steps = int(snap["decode_steps"])
        return restored

    def adopt(self, records: Sequence[Dict[str, Any]]) -> List[Request]:
        """Admit snapshot-format request records into THIS possibly
        BUSY engine — the fleet migration path (ISSUE 16).
        :meth:`restore` refuses a busy target by design; a healthy
        replica receiving a fenced peer's requests is mid-service, so
        migration needs an entry point that merges into live state.

        Validation is ATOMIC: every record must be servable by this
        engine's geometry, must not collide with a live rid, and the
        whole batch must fit the remaining ``max_queue`` headroom —
        all checked before any state mutates, so a refused adopt
        leaves the engine exactly as it was and the caller can try
        another target.  Live records enter the waiting queue pageless
        (the deterministic re-prefill path rebuilds their KV, exactly
        as restore/recover do); already-done records retire
        immediately.  Returns this engine's new request handles — the
        source replica's old handles are dead."""
        self._land()
        adopted: List[Request] = []
        for r in records:
            req = Request(
                rid=int(r["rid"]), prompt=list(r["prompt"]),
                max_new_tokens=int(r["max_new_tokens"]),
                eos_id=r["eos_id"], arrival_t=float(r["arrival_t"]),
                deadline_s=r["deadline_s"])
            req.generated = list(r["generated"])
            req.preemptions = int(r["preemptions"])
            req.admit_t = r["admit_t"]
            req.first_token_t = r["first_token_t"]
            adopted.append(req)
        live = [req for req in adopted if not req.done]
        live_rids = ({q.rid for q in self.sched.running}
                     | {q.rid for q in self.sched.waiting})
        for req in live:
            self.sched.check_servable(req)
            if req.rid in live_rids:
                raise ValueError(
                    f"adopt: rid {req.rid} collides with a live "
                    "request — migration requires a fleet-global rid "
                    "namespace")
        if self.sched.max_queue is not None and \
                len(self.sched.waiting) + len(live) > self.sched.max_queue:
            raise ValueError(
                f"adopt: {len(live)} live records exceed queue "
                f"headroom ({len(self.sched.waiting)}/"
                f"{self.sched.max_queue} waiting)")
        for req in adopted:
            self._next_rid = max(self._next_rid, req.rid + 1)
            if req.done:
                self._finish_restored(req)
            else:
                req.state = WAITING
                self.sched.waiting.append(req)
        return adopted

    # -- disaggregated prefill/decode (r18) --------------------------------

    def _no_window_pages(self, what: str) -> None:
        """Shipping pages is for one page lifetime and for pages alone:
        a window pool's compact page lists and a state pool's slots
        have no wire format yet."""
        for pool, held in ((self.cache.window_pool, "pages of a window"),
                           (self.cache.state_pool, "slots of a state")):
            if pool is not None:
                raise ValueError(
                    f"{self.cfg.name}: {what} cannot ship the {held} pool "
                    f"(docs/serving.md, "
                    f"\"{self.decoder.block.refuses_doc}\")")

    def export_request(self, rid: int):
        """Detach a freshly prefilled request for shipping (the
        prefill-replica side of r18 disaggregation): serialize its KV
        pages (:meth:`PagedKVCache.export_page_bytes` — per-page CRC
        stamped at export), capture its snapshot-format record
        (first token included in ``generated``), then release its
        local footprint.  Returns ``(record, pages_payload, kv_len)``.

        The request must be RUNNING with prefill complete
        (``prefill_pos is None``) and hold its first token — i.e. it
        is exactly at the point where a colocated engine would start
        decoding.  Locally it finishes as ``"shipped"`` (NOT counted
        in ``sched.finished`` — it retires for real on the decode
        replica); the caller's handle on the DECODE replica is the
        live one after adoption."""
        self._no_window_pages("export_request")
        self._land()
        req = next((r for r in self.sched.running if r.rid == rid), None)
        if req is None:
            raise ValueError(f"export_request: rid {rid} is not running")
        if req.prefill_pos is not None or not req.generated:
            raise ValueError(
                f"export_request: rid {rid} has not finished prefill")
        t0 = self.clock()
        pages_payload = [self.cache.export_page_bytes(p)
                         for p in req.pages]
        # r19 trace: the export span opens the ship segment of the
        # TTFT decomposition (kv_export.start -> kv_import.end);
        # export_t/export_span ride the record so the decode side can
        # account the ship wall and parent its spans without parsing
        # ids (adopt ignores unknown record keys by construction)
        life = self._life(req)
        export_span = f"{req.rid}:kv_export:{life}"
        self._emit("span", rid=req.rid, span_id=export_span,
                   parent_id=f"{req.rid}:admit:{life}",
                   kind="kv_export", t_start=t0, t_end=self.clock())
        record = {
            "rid": req.rid,
            "prompt": list(req.prompt),
            "max_new_tokens": req.max_new_tokens,
            "eos_id": req.eos_id,
            "arrival_t": req.arrival_t,
            "deadline_s": req.deadline_s,
            "generated": list(req.generated),
            "preemptions": req.preemptions,
            "admit_t": req.admit_t,
            "first_token_t": req.first_token_t,
            "was_running": True,
            "export_t": t0,
            "export_span": export_span,
        }
        kv_len = req.kv_len
        self.sched.running.remove(req)
        self.cache.free(req.pages)
        req.pages = []
        req.kv_len = 0
        req.state = FINISHED
        req.finish_reason = "shipped"
        if self.proposer is not None:
            self.proposer.release(req.rid)
        return record, pages_payload, kv_len

    def adopt_prefilled(self, record: Dict[str, Any],
                        pages_payload: Sequence[Dict[str, Any]],
                        kv_len: int) -> Request:
        """Admit one SHIPPED prefill straight into the decode batch
        (the decode-replica side of r18): re-verify each page payload
        host-side, allocate local pages, land the bytes verbatim, and
        enter RUNNING with the source's token state — decode proceeds
        as if this engine had prefilled locally, bitwise.

        Validation is atomic, in the :meth:`adopt` discipline —
        geometry, rid collision, page-count arithmetic, and per-page
        CRC all checked before any state mutates (a corrupted page is
        NEVER adopted; the sender re-ships it).  Capacity refusals
        (no decode batch slot, no pool pages) raise
        :class:`AdmissionRefused` — retryable, leaving the engine
        untouched."""
        self._no_window_pages("adopt_prefilled")
        self._land()
        kv_len = int(kv_len)
        req = Request(
            rid=int(record["rid"]), prompt=list(record["prompt"]),
            max_new_tokens=int(record["max_new_tokens"]),
            eos_id=record["eos_id"], arrival_t=float(record["arrival_t"]),
            deadline_s=record["deadline_s"])
        req.generated = list(record["generated"])
        req.preemptions = int(record["preemptions"])
        req.admit_t = record["admit_t"]
        req.first_token_t = record["first_token_t"]
        self.sched.check_servable(req)
        live_rids = ({q.rid for q in self.sched.running}
                     | {q.rid for q in self.sched.waiting})
        if req.rid in live_rids:
            raise ValueError(
                f"adopt_prefilled: rid {req.rid} collides with a live "
                "request — shipping requires a fleet-global rid "
                "namespace")
        need = self.cache.pages_needed(kv_len)
        if len(pages_payload) != need:
            raise ValueError(
                f"adopt_prefilled: rid {req.rid} shipped "
                f"{len(pages_payload)} pages for kv_len {kv_len} "
                f"(expected {need})")
        for i, data in enumerate(pages_payload):
            if not verify_page_payload(data):
                raise ValueError(
                    f"adopt_prefilled: rid {req.rid} page {i} failed "
                    "CRC verification — corrupted in flight, refusing "
                    "to adopt")
        if self.sched.slots_used >= self.max_batch:
            raise AdmissionRefused(
                f"adopt_prefilled: decode batch full "
                f"({self.sched.slots_used}/{self.max_batch})")
        try:
            pages = self.cache.allocate(need, req.rid)
        except PagePoolExhausted as e:
            raise AdmissionRefused(str(e)) from e
        for page, data in zip(pages, pages_payload):
            self.cache.import_page_bytes(page, data)
        req.pages = pages
        req.kv_len = kv_len
        req.state = RUNNING
        self.sched.running.append(req)
        self._next_rid = max(self._next_rid, req.rid + 1)
        # r19 shipping-aware SLO accounting: the first token was
        # sampled at export but is only STREAMABLE now that its KV
        # landed here — stamp adoption as stream_t and book the
        # export->adopt wall as the request's kv_ship cost (== its
        # kv_export.start -> kv_import.end span segment); _retire
        # moves that wall into TTFT instead of hiding it in TPOT
        now = self.clock()
        req.stream_t = now
        export_t = record.get("export_t")
        if export_t is not None:
            req.ship_s = max(0.0, now - float(export_t))
        self._emit("request_admit", rid=req.rid,
                   context_tokens=kv_len, pages=len(pages),
                   preemptions=req.preemptions)
        return req

    def _finish_restored(self, req: Request) -> None:
        """Retire a request that was already done when the crash hit
        (its last decode ran, retirement hadn't).  The retire event
        carries no finish timing — the crashed run took those
        measurements down with it; optional means absent."""
        req.state = FINISHED
        req.finish_reason = (
            "eos" if req.eos_id is not None and req.generated
            and req.generated[-1] == req.eos_id else "length")
        self.sched.finished.append(req)
        if self.proposer is not None:
            # every retirement path must drop per-rid proposer state —
            # recovery-path retirements leaked the suffix cache
            self.proposer.release(req.rid)
        self._emit("request_retire", rid=req.rid, reason=req.finish_reason,
                   new_tokens=len(req.generated),
                   preemptions=req.preemptions)

    def recover(self, cause: str) -> None:
        """In-process crash recovery after a device loss / pool
        corruption: discard the device pool (its content is garbage or
        gone), rebuild a fresh one, and put every live request back on
        the waiting queue — running requests first, in admission
        order, tokens kept.  Re-admission re-prefills them through the
        deterministic path, so recovery is output-invisible (the
        acceptance pin: per-request token streams bitwise identical to
        an uninterrupted control).  The caller's :class:`Request`
        handles stay live — this is the in-process twin of
        :meth:`snapshot`/:meth:`restore`."""
        # a launch in flight is lost with the pool: its rows decode
        # that token again from what they had committed
        if self._flight is not None:
            for req in self._flight.rows:
                req.in_flight = 0
            self._flight = None
        running = list(self.sched.running)
        waiting = list(self.sched.waiting)
        old = self.cache
        # the rebuilt pool keeps its quantization mode: re-prefill
        # re-quantizes deterministically (per-(token, head) scales
        # are order-independent), so recovery stays output-
        # invisible at the documented quantized parity bar
        self.cache = self._new_cache(old.num_pages, old.page_size,
                                     old.max_pages_per_request,
                                     old.crc_pages)
        if self._mesh is not None:
            self._shard_pools()
        if self.prefix_index is not None:
            # the index pointed into the dead pool; rebuild it EMPTY —
            # shared prefixes re-register as re-admissions complete
            # (warm-cache opportunism is rebuildable, like KV)
            self.prefix_index = PrefixIndex(
                self.cache, max_entries=self.prefix_entries)
        sched = self._new_scheduler(self.sched.max_queue,
                                    self.sched.preempt_cap)
        sched.finished = self.sched.finished   # history survives
        self.sched = sched
        for req in running:
            req.pages = []
            req.window = None
            req.slot = None
            req.kv_len = 0
            # a mid-chunk request restarts its chunked prefill after
            # the rebuild — chunk progress is as rebuildable as KV
            req.prefill_pos = None
            if req.done:
                # complete-but-unretired at the fault boundary: finish
                # it here rather than re-prefill past max_new_tokens
                self._finish_restored(req)
            else:
                req.state = WAITING
                sched.waiting.append(req)
        sched.waiting.extend(waiting)
        # re-place the params on the (rebuilt) device; the jitted
        # executables are shape-keyed and survive as-is.  Under tp the
        # re-placement must restore the tensor-axis shardings, or the
        # next step would compile a resharding variant.
        if self._mesh is not None:
            self.params = jax.device_put(self.params,
                                         self._param_shardings())
        else:
            self.params = jax.device_put(self.params)
        self.recoveries += 1
        self._emit("serving_recovery", cause=cause, pool_rebuilt=True,
                   running_restored=len(running),
                   waiting_restored=len(waiting))

    def _handle_fault(self, exc: BaseException) -> None:
        """Absorb a recoverable mid-decode fault via :meth:`recover`,
        or re-raise when recovery is disabled/exhausted — exhaustion
        first dumps the flight-recorder ring as a trace bundle (r19):
        the chaos outcome ships its own post-mortem."""
        if not self.recover_on_fault or self.recoveries >= self.max_recoveries:
            if self.recover_on_fault and self.telemetry is not None:
                from apex_tpu.telemetry.tracing import \
                    maybe_dump_flight_record

                maybe_dump_flight_record(
                    self.telemetry,
                    f"recovery_exhausted:{type(exc).__name__}",
                    step=self.steps)
            raise exc
        device_ids = getattr(exc, "device_ids", None)
        if device_ids is not None:
            self._emit("device_loss", device_ids=list(device_ids))
        cause = ("device_loss" if device_ids is not None
                 else "page_corruption")
        self.recover(cause=cause)

    # -- drivers -----------------------------------------------------------

    def _guarded_step(self) -> None:
        """One step with the ISSUE 10 recovery net: a mid-decode
        device loss or CRC-caught page corruption triggers rebuild +
        restore + continue instead of killing the trace."""
        from apex_tpu.resilience.chaos import DeviceLossError

        try:
            self.step()
        except (DeviceLossError, PagePoolCorruption) as e:
            self._handle_fault(e)

    def run(self, max_steps: int = 100_000, *,
            raise_on_stall: bool = True) -> List[Request]:
        """Step until every queued request has finished; returns the
        finished list (scheduler order).  Exhausting ``max_steps``
        with live requests still queued is a STALL: a
        ``serving_stall`` event is emitted either way (a wedged fleet
        member must be observable, not quietly partial — ISSUE 16),
        then the engine raises, or returns the partial finished list
        under ``raise_on_stall=False``."""
        for _ in range(max_steps):
            if self.sched.idle:
                break
            self._guarded_step()
        else:
            self._emit("serving_stall",
                       waiting=len(self.sched.waiting),
                       running=len(self.sched.running),
                       budget=max_steps)
            if raise_on_stall:
                raise RuntimeError(
                    f"engine did not drain in {max_steps} steps")
        self._land()
        self._retire(self.clock())
        return self.sched.finished

    def serve(self, trace: Sequence[Request], *,
              max_steps: int = 1_000_000,
              raise_on_stall: bool = True) -> List[Request]:
        """Run an arrival trace (requests sorted by ``arrival_t``):
        each request is submitted once the clock passes its arrival
        time; with a real clock the engine sleeps through idle gaps,
        with a :class:`SimClock` it advances virtual time.  Trace
        arrival times are RELATIVE to the start of the call — they are
        rebased in place onto the engine clock, so TTFT (first token
        minus arrival) is measured on one time base.  Requests are
        therefore SINGLE-USE: re-serving a trace object would
        double-rebase its arrivals (and replay half-mutated request
        state), so a non-fresh request is rejected up front —
        regenerate the trace instead."""
        pending = sorted(trace, key=lambda r: (r.arrival_t, r.rid))
        for req in pending:
            if req.state != WAITING or req.generated or req.pages \
                    or req.kv_len:
                raise ValueError(
                    f"request {req.rid} is not fresh "
                    f"(state={req.state!r}) — trace requests are "
                    "single-use; regenerate the trace")
        t_base = self.clock()
        for req in pending:
            req.arrival_t += t_base
        i = 0
        for _ in range(max_steps):
            now = self.clock()
            while i < len(pending) and pending[i].arrival_t <= now:
                self.submit_request(pending[i])
                i += 1
            if not self.sched.idle:
                self._guarded_step()
            elif i < len(pending):
                gap = pending[i].arrival_t - now
                if isinstance(self.clock, SimClock):
                    self.clock.advance()
                elif gap > 0:
                    time.sleep(min(gap, 0.05))
            else:
                break
        else:
            self._emit("serving_stall",
                       waiting=len(self.sched.waiting),
                       running=len(self.sched.running),
                       budget=max_steps)
            if raise_on_stall:
                raise RuntimeError(
                    f"trace did not drain in {max_steps} steps")
        self._land()
        self._retire(self.clock())
        return self.sched.finished
