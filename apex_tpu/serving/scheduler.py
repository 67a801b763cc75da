"""Continuous-batching scheduler: host-side admission / growth /
preemption / retirement policy.

Static-batching serving (pad every request to the longest, decode until
ALL finish) wastes most of the chip on retired-or-absent rows; the
continuous-batching answer (Orca/vLLM lineage) re-forms the batch
BETWEEN decode steps: finished requests leave immediately, waiting
requests join whenever a batch slot, prefill-token budget, and KV pages
are available.  This module is the pure-python policy half — it owns
request lifecycles and the page accounting, and never touches device
state (the :class:`~apex_tpu.serving.engine.ServingEngine` turns its
decisions into prefill/decode calls).

Policy (all deterministic — FIFO queues, lowest-first page allocation —
so a seeded arrival trace replays bit-identically):

* **admission**: FIFO over the waiting queue while (a) a batch slot is
  open, (b) this step's prefill-token budget has room for the
  request's context, and (c) the pool can supply its context pages.
  ``prefill_budget`` plays two roles: per REQUEST it is the widest
  prefill row (``submit`` rejects contexts that could outgrow it; the
  engine launches the narrowest of its ladder of widths that holds the
  request), and per STEP it caps the total prefill tokens admitted
  between two decode steps — each admission is its own launch (the
  engine's isolation contract), so the step cap is not a packing
  constraint but head-of-line-latency control: admitting unbounded
  prefill work in one step would stall every running request's next
  token.  First failure stops admission for this step (no out-of-order
  admission — fairness over packing efficiency).
* **growth**: before each decode step every running request crossing a
  page boundary gets one page.  The engine keeps one decode launch in
  flight (ISSUE 34), so a row's next position is ``seq_len +
  in_flight - 1``: the token in flight is counted, never read.
* **preemption**: when growth (or nothing-running admission) finds the
  pool empty, the MOST-RECENTLY-admitted running request is evicted —
  its pages are freed, its generated-so-far TOKENS are kept, and it
  rejoins the FRONT of the waiting queue; on re-admission its context
  (prompt + generated) is re-prefilled, deterministically regenerating
  its KV from the kept tokens, so preemption is invisible in the
  output stream (pinned token-for-token by
  ``test_preemption_is_output_invisible``; the regenerated KV is the
  same computation, not byte-for-byte the same buffers —
  docs/serving.md "Preemption").
* **retirement**: EOS or ``max_new_tokens`` reached → pages freed (and
  immediately reusable), terminal state recorded; only on tokens that
  have landed.  A row whose last token is in flight
  (:attr:`Request.spent`) holds its pages and no batch slot.
* **two page lifetimes** (ISSUE 29): where the cache has a
  ``window_pool`` a request holds pages of both kinds and every
  decision above counts both.  The full pool is reserved for the whole
  context at admission, as ever; the window pool only for the launch
  at hand (a whole-row prefill's tail, one chunk, one decode token),
  because what slid out of the window is given back after every
  launch (:meth:`ContinuousBatchingScheduler.slide_windows`) and a
  long prompt never holds more than a window and a chunk there.  A
  chunk or a decode step that finds the window pool dry preempts from
  the back, like growth in the full pool.
* **slots beside pages** (ISSUE 35): where the cache has a
  ``state_pool`` (a model with state-space layers) a request owns one
  SLOT of recurrent state beside its pages.  Admission asks the cache
  for both: a request with pages to be had and no slot free waits (at
  short contexts the slot is what runs out first).  A slot never
  grows; retirement, a deadline and preemption give it back with the
  pages, and the preempted request re-prefills into whatever slot its
  re-admission takes.

Resilience policy (ISSUE 10 — docs/serving.md "Failure semantics"):

* **deadlines**: a request may carry ``deadline_s`` (seconds after
  arrival by which it must FINISH).  :meth:`expire_deadlines` sheds
  queued requests that can no longer meet it (``now + min_service_s``
  already past the deadline — the SLO-aware part: shedding *before*
  expiry refuses work that would only burn pool pages to miss anyway)
  and retires in-flight expirations with a ``timeout`` status and
  immediate page free.
* **bounded queue**: ``max_queue`` caps the waiting queue; ``submit``
  raises :class:`QueueFullError` instead of growing without bound
  under overload (the engine converts it into an explicit
  ``request_reject`` event — load is refused loudly, never absorbed
  into an hours-deep queue every entry of which will time out).
* **anti-livelock aging**: evict-newest preemption skips requests that
  have already been preempted ``preempt_cap`` times — a long request
  under sustained short-request pressure is hit at most ``preempt_cap``
  times and then becomes senior to fresh admissions, so it provably
  completes (pinned by the livelock regression test).  When EVERY
  running request is at the cap the plain newest is evicted anyway
  (progress must never deadlock on the aging rule).
"""

from __future__ import annotations

import dataclasses
from collections import deque
from typing import Callable, Deque, Dict, List, Optional

from apex_tpu.serving.kv_cache import (
    PagedKVCache,
    PagePoolExhausted,
    PrefixIndex,
    WindowPages,
)

WAITING = "waiting"
RUNNING = "running"
FINISHED = "finished"


class QueueFullError(RuntimeError):
    """The bounded submit queue is full — the overload reject signal,
    not an error in the request itself (a retry later may succeed).
    The engine converts it into a ``request_reject`` telemetry event
    and a ``rejected`` terminal state."""


@dataclasses.dataclass
class Request:
    """One serving request and its runtime state."""

    rid: int
    prompt: List[int]
    max_new_tokens: int
    eos_id: Optional[int] = None
    arrival_t: float = 0.0
    # completion deadline, seconds after arrival (None = no SLO).
    # Stored relative so serve()'s arrival rebase moves it too.
    deadline_s: Optional[float] = None
    # runtime
    state: str = WAITING
    generated: List[int] = dataclasses.field(default_factory=list)
    pages: List[int] = dataclasses.field(default_factory=list)
    # what the request holds in the window pool, where the model has
    # layers that keep only a window of tokens (ISSUE 29); None until
    # such a pool admits it
    window: Optional[WindowPages] = None
    # the slot of recurrent state it owns, where the model has
    # state-space layers (ISSUE 35); None until admission takes one
    slot: Optional[int] = None
    kv_len: int = 0               # tokens whose K/V sit in the pool
    # tokens launched and not yet on the host (ISSUE 34): 1 while the
    # decode launch that holds this row is in flight, else 0.  The
    # row's next position, ``kv_len`` and page follow from ``seq_len +
    # in_flight``; ``generated`` holds only what has landed.
    in_flight: int = 0
    # chunked-prefill cursor (ISSUE 12): tokens of the admission
    # context already computed into pages; None = not mid-chunk (the
    # whole-row path, or prefill complete).  DELIBERATELY not part of
    # any checkpoint: chunk progress is rebuildable by deterministic
    # re-prefill, so preemption/restore reset it to start over (the
    # same contract that keeps KV pages out of engine snapshots).
    prefill_pos: Optional[int] = None
    # r17 prefix sharing: True when the CURRENT admission covered a
    # context prefix with shared pages (reset on preemption — a
    # re-admission does its own lookup).  Telemetry-visible as the
    # request_admit event's ``prefix_hit`` bool.
    prefix_hit: bool = False
    # ... and how many of the context's tokens they were (the
    # ``engine.prefill`` phase's ``shared``)
    prefix_tokens: int = 0
    preemptions: int = 0
    admit_t: Optional[float] = None
    first_token_t: Optional[float] = None
    # r19 shipping-aware SLO accounting: when the first sampled token
    # became STREAMABLE — equal to first_token_t on a colocated path,
    # but a disaggregated request's first token is not client-visible
    # until its KV pages land on the decode replica, so adopt_prefilled
    # stamps adoption time here and the kv_ship wall below.  TTFT is
    # measured against stream_t; the ship wall moves into TTFT (where
    # the SLO feels it), not TPOT.
    stream_t: Optional[float] = None
    ship_s: float = 0.0
    finish_t: Optional[float] = None
    finish_reason: Optional[str] = None

    # memoized `context` backing store (not part of the request state:
    # excluded from repr and from any comparison semantics)
    _ctx: Optional[List[int]] = dataclasses.field(
        default=None, repr=False, compare=False)
    _ctx_key: tuple = dataclasses.field(
        default=(-1, -1), repr=False, compare=False)

    @property
    def deadline_t(self) -> Optional[float]:
        """Absolute deadline on the engine clock (None = no SLO)."""
        if self.deadline_s is None:
            return None
        return self.arrival_t + self.deadline_s

    @property
    def context(self) -> List[int]:
        """Tokens whose K/V must be cached at (re-)admission: the
        prompt plus everything generated before a preemption.

        Memoized on ``(len(prompt), len(generated))``: both lists are
        append-only for a live request, so the concat is rebuilt only
        when tokens were committed — a chunked prefill (context frozen
        across its chunks) and the per-boundary proposer lookup read
        the SAME list instead of copying O(seq_len) per access
        (review-found; the hot-path cost was O(C²/chunk) over a long
        prefill).  Callers must treat the returned list as read-only.
        """
        key = (len(self.prompt), len(self.generated))
        if self._ctx_key != key:
            self._ctx = self.prompt + self.generated
            self._ctx_key = key
        return self._ctx

    @property
    def seq_len(self) -> int:
        return len(self.prompt) + len(self.generated)

    @property
    def done(self) -> bool:
        if self.eos_id is not None and self.generated \
                and self.generated[-1] == self.eos_id:
            return True
        return len(self.generated) >= self.max_new_tokens

    @property
    def spent(self) -> bool:
        """The token in flight is the last its budget allows: the row
        is not launched again, it only waits for that token to land."""
        return (self.in_flight > 0 and len(self.generated)
                + self.in_flight >= self.max_new_tokens)

    @property
    def launchable(self) -> bool:
        """May ride the next decode launch: its prefill is complete, no
        landed token finished it, and the tokens it has, on the host or
        in flight, leave its budget room for one more.  (An EOS still
        in flight cannot be seen from here: such a row is launched once
        more, and that token is dropped when it lands.)"""
        return (self.prefill_pos is None and not self.done
                and not self.spent)


class ContinuousBatchingScheduler:
    """Admission/growth/preemption/retirement over a shared page pool."""

    def __init__(self, cache: PagedKVCache, *, max_batch: int,
                 prefill_budget: int, max_position: int,
                 max_queue: Optional[int] = None,
                 preempt_cap: Optional[int] = 4,
                 chunk_size: Optional[int] = None,
                 prefix_index: Optional[PrefixIndex] = None):
        if chunk_size is not None and chunk_size > prefill_budget:
            raise ValueError(
                f"chunk_size {chunk_size} exceeds the per-step prefill "
                f"budget {prefill_budget} — a chunk could never launch")
        if prefix_index is not None and chunk_size is None:
            # a prefix hit admits the request mid-context — its suffix
            # prefills through the fixed [1, chunk_size] extend
            # executable, attending over the shared pages.  Without a
            # chunk path there is no way to compute a suffix's K/V
            # against an existing cache.
            raise ValueError(
                "prefix sharing requires chunked prefill "
                "(chunk_size=None)")
        self.cache = cache
        self.max_batch = max_batch
        self.prefill_budget = prefill_budget
        self.max_position = max_position
        # overload policy (ISSUE 10): bounded submit queue + aging cap
        # on evict-newest preemption (None disables either)
        self.max_queue = max_queue
        self.preempt_cap = preempt_cap
        # chunked prefill (ISSUE 12): contexts longer than chunk_size
        # admit into chunked prefill — one fixed-width chunk per
        # boundary under the shared prefill-token budget — instead of
        # one whole-row launch (None = every prefill is whole-row)
        self.chunk_size = chunk_size
        # prefix sharing (r17): admission consults the index for a
        # shared prefix (pages refcounted, prefill skipped for the
        # covered tokens); allocation pressure evicts index entries
        # BEFORE preempting a running request — dropping warm-cache
        # opportunism is always cheaper than killing live work
        self.prefix_index = prefix_index
        #: the pool of the window layers' pages, where there is one
        self.wpool = cache.window_pool
        self.waiting: Deque[Request] = deque()
        self.running: List[Request] = []   # admission order
        self.finished: List[Request] = []
        # the engine's decode launch in flight (ISSUE 34): the engine
        # says here whether it has one, and gives ``land`` to bring its
        # tokens to the host (and retire what they finish) before a row
        # of it is preempted
        self.in_flight = False
        self.land: Optional[Callable[[], None]] = None

    # -- intake ----------------------------------------------------------

    def check_servable(self, req: Request) -> None:
        """Raise ``ValueError`` if ``req`` could NEVER be served by
        THIS scheduler's geometry (so capacity failures later are
        always transient).  Shared by :meth:`submit` and the engine's
        ``restore`` — a snapshot taken on a differently-configured
        engine (e.g. chunked → chunk-less) must fail here, loudly,
        instead of queueing a request admission can never take."""
        worst = len(req.prompt) + req.max_new_tokens
        if worst > self.max_position:
            raise ValueError(
                f"request {req.rid}: prompt+max_new {worst} exceeds "
                f"max_position {self.max_position}")
        if self.cache.pages_needed(worst) > \
                self.cache.max_pages_per_request:
            raise ValueError(
                f"request {req.rid}: needs up to "
                f"{self.cache.pages_needed(worst)} pages > "
                f"max_pages_per_request "
                f"{self.cache.max_pages_per_request}")
        if worst > self.prefill_budget and self.chunk_size is None:
            # the PREEMPTION contract needs the whole worst-case
            # context (prompt + everything it may generate) to fit the
            # fixed prefill row width, or an evicted request could
            # never be re-admitted.  A CHUNKED scheduler lifts this
            # bound (ISSUE 12): any context past chunk_size — original
            # or regrown by re-admission — prefills through the fixed
            # [1, chunk_size] executable, so the row width no longer
            # caps request size (max_position still does, above)
            raise ValueError(
                f"request {req.rid}: prompt+max_new {worst} exceeds "
                f"prefill budget {self.prefill_budget}")

    def submit(self, req: Request) -> None:
        """Queue a request; rejects up front what could NEVER be
        served (:meth:`check_servable`)."""
        self.check_servable(req)
        if self.max_queue is not None and len(self.waiting) >= self.max_queue:
            # overload: refuse loudly rather than queue work that will
            # only time out.  Only NEW submissions are bounded —
            # preemption requeues bypass submit() by design (an evicted
            # request must always be able to come back)
            raise QueueFullError(
                f"request {req.rid}: submit queue full "
                f"({len(self.waiting)}/{self.max_queue})")
        self.waiting.append(req)

    # -- admission -------------------------------------------------------

    def admit(self) -> List[Request]:
        """Admit FIFO-eligible requests for this step (each gets its
        own prefill launch; the shared ``prefill_budget`` decrement
        caps this STEP's total prefill work — see the module
        docstring).  Returns the admitted list (pages allocated, state
        RUNNING); never raises on capacity — a full pool just admits
        fewer.  The whole-row-only entry point: a chunked scheduler
        must go through :meth:`schedule_prefill`, which also plans the
        in-flight chunk launches this call would silently drop."""
        if self.chunk_size is not None:
            raise RuntimeError(
                "admit() on a chunked scheduler — use schedule_prefill()")
        _, admitted = self.schedule_prefill()
        return admitted

    def schedule_prefill(self) -> tuple:
        """Plan this boundary's prefill work under the shared
        prefill-token budget; returns ``(chunks, admitted)``.

        ``chunks`` — ``(request, start, n_tokens)`` launches, in
        execution order: first one chunk for every in-flight chunked
        request (admission order — a long prefill advances by AT MOST
        one chunk per boundary, which is the head-of-line-latency
        point: decode steps interleave between its chunks instead of
        stalling behind a whole-row launch), then the first chunk of
        each newly admitted long request.  ``admitted`` — requests
        admitted this boundary (pages for the FULL context reserved at
        admission — the ISSUE 10 reserve-at-admit invariant is
        unchanged; a context at or under ``chunk_size``, or any
        context when chunking is off, takes the whole-row prefill
        path and appears only in ``admitted``).

        Budget accounting: an in-flight chunk consumes its token
        count; a whole-row admission consumes its context length; a
        chunked admission consumes ``chunk_size`` (its first chunk —
        the rest of the context is later boundaries' budget, which is
        exactly how a 2k-token arrival stops monopolizing a boundary).
        First failure stops each phase (no out-of-order work — the
        FIFO fairness rule).
        """
        budget = self.prefill_budget
        chunks: List[tuple] = []
        if self.chunk_size is not None:
            for req in list(self.running):
                if req.prefill_pos is None or req.state != RUNNING:
                    continue
                # seq_len == len(context) during prefill, without
                # materializing the prompt+generated list per boundary
                n = min(self.chunk_size, req.seq_len - req.prefill_pos)
                if n > budget:
                    break
                self._grow_window(req, req.prefill_pos, req.prefill_pos + n)
                if req.state != RUNNING:
                    continue   # preempted for its own chunk's pages
                chunks.append((req, req.prefill_pos, n))
                budget -= n
            # a chunk planned before a later one's growth evicted it
            chunks = [c for c in chunks if c[0].state == RUNNING]
        admitted: List[Request] = []
        slots = self.slots_used
        while self.waiting and slots + len(admitted) < self.max_batch:
            req = self.waiting[0]
            ctx = req.seq_len
            # prefix sharing: the longest indexed prefix of the context
            # rides in on shared pages; only the suffix [m, ctx) is
            # prefilled, always through the chunk path (it must attend
            # over the shared pages)
            m, shared = (0, [])
            if self.prefix_index is not None:
                m, shared = self.prefix_index.lookup(req.context)
            if m:
                chunked = True
                need = min(self.chunk_size, ctx - m)
            else:
                chunked = (self.chunk_size is not None
                           and ctx > self.chunk_size)
                need = self.chunk_size if chunked else ctx
            if need > budget:
                break
            if self.cache.slots_free == 0:
                # pages or not, a request needs a slot for its
                # recurrent state: it waits for a retirement
                if not self.running and not admitted:
                    raise PagePoolExhausted(
                        "no state slot free and nothing running")
                break
            if shared:
                # pin the shared pages FIRST: index eviction inside
                # the allocation retry below may otherwise free them
                self.cache.share(shared)
            try:
                fresh = self._allocate_evicting(
                    self.cache.pages_needed(ctx) - len(shared), req.rid)
            except PagePoolExhausted:
                if shared:
                    self.cache.free(shared)
                if not self.running and not admitted:
                    # nothing to preempt and nothing in flight: the
                    # waiting request's context alone exceeds the pool
                    # minus other waiters' leavings — surface it, this
                    # is a sizing bug, not a transient
                    raise
                break
            pages = list(shared) + fresh
            if self.wpool is not None:
                # the window pool covers the first launch only: the
                # tail of a whole-row prefill, or the first chunk
                held = WindowPages()
                try:
                    self.wpool.grow(
                        held, m if chunked else ctx,
                        min(ctx, m + need) if chunked else ctx, req.rid)
                except PagePoolExhausted:
                    self.cache.free(pages)
                    if not self.running and not admitted:
                        raise
                    break
                req.window = held
            if m % self.cache.page_size:
                # the hit ends MID-page: the suffix's first chunk will
                # write position m into the last shared page, so it is
                # copy-on-write'd HERE, at admission, where exhaustion
                # is still an ordinary stop-admitting event — a COW
                # failing mid-launch would have no clean rollback
                try:
                    self._privatize(pages, m // self.cache.page_size,
                                    req.rid)
                except PagePoolExhausted:
                    self.cache.free(pages)
                    if not self.running and not admitted:
                        raise
                    break
            self.waiting.popleft()
            req.pages = pages
            req.slot = self.cache.allocate_slot(req.rid)
            req.state = RUNNING
            req.prefix_hit = bool(m)
            req.prefix_tokens = m
            budget -= need
            if chunked:
                req.prefill_pos = m
                chunks.append((req, m, min(self.chunk_size, ctx - m)))
            admitted.append(req)
        self.running.extend(admitted)
        return chunks, admitted

    def _allocate_evicting(self, n: int, rid: int) -> List[int]:
        """:meth:`PagedKVCache.allocate`, but allocation pressure
        first evicts prefix-index entries (oldest-first) — an index
        entry is a reuse OPPORTUNITY, never a reason to fail an
        admission or preempt live work.  Only entries whose pages drop
        to refcount zero actually return capacity; entries still read
        by live requests release nothing (their pages stay live), so
        the loop is bounded by the index size."""
        while True:
            try:
                return self.cache.allocate(n, rid)
            except PagePoolExhausted:
                if self.prefix_index is None or \
                        len(self.prefix_index) == 0:
                    raise
                self.prefix_index.evict_one()

    def _privatize(self, pages: List[int], idx: int, rid: int) -> None:
        """Copy-on-write ``pages[idx]`` in place for ``rid``, evicting
        prefix-index entries under allocation pressure (the same relief
        order as :meth:`_allocate_evicting`).  If an eviction drops the
        page's OTHER reader, the caller's pin is the only reference
        left and no copy is needed — the loop re-checks sharedness
        before each attempt."""
        while self.cache.is_shared(pages[idx]):
            try:
                pages[idx] = self.cache.cow(pages[idx], rid)
                return
            except PagePoolExhausted:
                if self.prefix_index is None or \
                        len(self.prefix_index) == 0:
                    raise
                self.prefix_index.evict_one()

    # -- growth / preemption ---------------------------------------------

    def preempt_one(self) -> Optional[Request]:
        """Evict the most-recently-admitted running request: free its
        pages, keep its tokens, requeue it at the FRONT of the waiting
        queue.  Returns the victim (or None if nothing runs, or if
        landing the launch in flight retired a request instead).

        Anti-livelock aging (ISSUE 10): a request already preempted
        ``preempt_cap`` times is skipped — the victim is the newest
        request still UNDER the cap, so sustained pressure cannot hit
        the same request forever.  If every running request is capped
        the plain newest is evicted anyway: the aging rule bounds
        repeat victimization, it must never deadlock progress."""
        if not self.running:
            return None
        victim = None
        if self.preempt_cap is not None:
            for req in reversed(self.running):
                if req.preemptions < self.preempt_cap:
                    victim = req
                    break
        if victim is None:
            victim = self.running[-1]
        if victim.in_flight and self.land is not None:
            # its newest token is still on the device: land the launch
            # first, so the token is kept.  Where that finished a
            # request, its pages are back and nobody is evicted yet:
            # the caller tries again
            n = len(self.running)
            self.land()
            if len(self.running) < n:
                return None
        self.running.remove(victim)
        self._release(victim)
        victim.kv_len = 0
        # a mid-chunk victim restarts its chunked prefill on
        # re-admission — chunk progress is rebuildable, like KV
        victim.prefill_pos = None
        # re-admission does its own prefix lookup
        victim.prefix_hit = False
        victim.prefix_tokens = 0
        victim.state = WAITING
        victim.preemptions += 1
        self.waiting.appendleft(victim)
        return victim

    def _release(self, req: Request) -> None:
        """Give back every page ``req`` holds, of both lifetimes, and
        its slot of recurrent state."""
        self.cache.free(req.pages)
        req.pages = []
        self.cache.free_slot(req.slot)
        req.slot = None
        if req.window is not None:
            self.wpool.release(req.window)
            req.window = None

    def _grow_window(self, req: Request, first_query: int, end: int
                     ) -> List[Request]:
        """Take the window-pool pages ``req``'s next launch writes
        (positions up to ``end``, first query at ``first_query``),
        preempting from the back while the pool is dry.  Returns the
        requests preempted for them; ``req`` itself may be the last
        (it is then no longer RUNNING).  Nothing to do where there is
        no window pool."""
        evicted: List[Request] = []
        while self.wpool is not None and req.state == RUNNING:
            try:
                self.wpool.grow(req.window, first_query, end, req.rid)
                break
            except PagePoolExhausted:
                victim = self.preempt_one()
                if victim is not None:
                    evicted.append(victim)
        return evicted

    def slide_windows(self, reqs) -> int:
        """After a launch: give back the window-pool pages that no
        query still to come of ``reqs`` can see; returns how many."""
        if self.wpool is None:
            return 0
        return sum(self.wpool.slide(r.window, r.kv_len) for r in reqs
                   if r.window is not None)

    def ensure_decode_capacity(self, extra: Optional[Dict[int, int]]
                               = None) -> List[Request]:
        """Give every running request the page its next token needs,
        preempting from the back of the batch when the pool runs dry.
        Returns the requests preempted (possibly including ones that
        had already grown — eviction strictly follows admission
        order).

        ``extra`` (ISSUE 12): per-rid additional token headroom this
        boundary — a speculative verify launch writes its draft's K/V
        at positions ``seq_len .. seq_len + draft - 1``, so drafted
        requests grow to ``pages_needed(seq_len + draft)`` here and
        the engine rolls the rejected tail back afterwards
        (:meth:`PagedKVCache.free_tail`).

        A row's next token goes to position ``seq_len + in_flight - 1``
        (ISSUE 34: the token in flight is counted, its value is not
        needed); a row that will not be launched (mid-prefill, or its
        budget spent by the token in flight) takes nothing."""
        evicted: List[Request] = []
        for req in list(self.running):
            if req not in self.running:
                continue  # evicted while growing an earlier request
            while req in self.running and req.launchable:
                query = req.seq_len + req.in_flight - 1
                want = query + 1 + (extra.get(req.rid, 0) if extra else 0)
                need_pages = self.cache.pages_needed(want)
                if len(req.pages) >= need_pages:
                    evicted.extend(self._grow_window(req, query, want))
                    break
                try:
                    req.pages.extend(
                        self.cache.allocate(
                            need_pages - len(req.pages), req.rid))
                except PagePoolExhausted:
                    # pressure relief order: drop a prefix-index entry
                    # first (reuse opportunism is cheaper than killing
                    # live work), preempt only once the index is dry
                    if self.prefix_index is not None and \
                            len(self.prefix_index):
                        self.prefix_index.evict_one()
                        continue
                    # the victim can be ``req`` itself (it is the
                    # newest admission left): then the loop's membership
                    # check ends its growth and it waits its turn
                    victim = self.preempt_one()
                    if victim is not None:
                        evicted.append(victim)
        return evicted

    # -- deadlines -------------------------------------------------------

    def expire_deadlines(self, now: float, *, min_service_s: float = 0.0
                         ) -> tuple:
        """Enforce per-request deadlines; returns ``(shed, timed_out)``.

        *Shed* — queued requests that can no longer meet their deadline
        (``now + min_service_s`` at or past it; ``min_service_s`` is
        the caller's floor estimate of remaining service time, 0.0 =
        shed only once expired).  They finish with reason ``"shed"``
        without ever taking pool pages.

        *Timed out* — RUNNING requests whose deadline has passed:
        removed from the batch with reason ``"timeout"`` and their
        pages freed immediately (reusable by the very next admission —
        the timeout-storm no-leak test pins this).
        """
        shed: List[Request] = []
        timed_out: List[Request] = []
        for req in list(self.waiting):
            dt = req.deadline_t
            if dt is not None and now + min_service_s >= dt:
                self.waiting.remove(req)
                req.state = FINISHED
                req.finish_t = now
                req.finish_reason = "shed"
                self.finished.append(req)
                shed.append(req)
        for req in list(self.running):
            dt = req.deadline_t
            if req.done:
                # its last token was generated before the deadline
                # died — the request is COMPLETE, just not yet swept
                # by retire_finished (the engine retires right after
                # expiring); timing it out here would misreport a full
                # token stream as a timeout
                continue
            if dt is not None and now >= dt:
                self.running.remove(req)
                self._release(req)
                req.kv_len = 0
                req.prefill_pos = None
                req.state = FINISHED
                req.finish_t = now
                req.finish_reason = "timeout"
                self.finished.append(req)
                timed_out.append(req)
        return shed, timed_out

    # -- retirement ------------------------------------------------------

    def retire_finished(self, now: float) -> List[Request]:
        """Move done requests out of the batch and free their pages —
        the pages are reusable by the very next admission."""
        done = [r for r in self.running if r.done]
        for req in done:
            self.running.remove(req)
            self._release(req)
            req.state = FINISHED
            req.finish_t = now
            req.finish_reason = (
                "eos" if req.eos_id is not None and req.generated
                and req.generated[-1] == req.eos_id else "length")
            self.finished.append(req)
        return done

    @property
    def slots_used(self) -> int:
        """Batch slots taken: the running requests, less those that
        only wait for their last token to land (:attr:`Request.spent`).
        Such a row rides no further launch, so an admission takes its
        slot a step before it retires; its pages it keeps until then."""
        return sum(1 for r in self.running if not r.spent)

    @property
    def idle(self) -> bool:
        """Nothing queued, nothing running and no launch in flight: a
        driver that sleeps while the engine is idle leaves no token on
        the device."""
        return not (self.waiting or self.running or self.in_flight)
