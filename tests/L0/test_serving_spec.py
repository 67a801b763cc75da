"""Speculative decoding + chunked prefill tier (ISSUE 12).

THE acceptance pin lives here: greedy speculative decoding (n-gram
proposer, verify-accept at ``q_len = k + 1``, chunked prefill)
produces token streams BITWISE identical to non-speculative greedy
decoding over the seeded Poisson trace — including preemption
mid-draft and chunked-prefill requests — because exact greedy
acceptance commits only tokens the model's own argmax endorses
(docs/serving.md "Speculative decoding").  Speculation may only
change how many tokens commit per boundary, never which tokens.
"""

import json

import numpy as np
import pytest

import jax.numpy as jnp

from apex_tpu.ops import routing_override
from apex_tpu.serving import (ServingEngine, ServingModelConfig, SimClock,
                              SpecConfig, init_params, poisson_trace)
from apex_tpu.serving.spec import NgramProposer, Proposer, commit_tokens

pytestmark = pytest.mark.serving

CFG = ServingModelConfig(vocab_size=64, hidden_size=32, num_heads=4,
                         num_layers=2, max_position=96)


@pytest.fixture(scope="module")
def serving_params():
    return init_params(CFG, seed=0)


def _engine(params, spec=None, **kw):
    kw.setdefault("num_pages", 64)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_batch", 4)
    kw.setdefault("prefill_budget", CFG.max_position)
    kw.setdefault("clock", SimClock())
    return ServingEngine(CFG, params, spec=spec, **kw)


def _trace(seed=3, n=6, **kw):
    kw.setdefault("rate", 2.0)
    kw.setdefault("prompt_len", (4, 10))
    kw.setdefault("max_new", (3, 12))
    kw.setdefault("vocab_size", CFG.vocab_size)
    return poisson_trace(seed, n, **kw)


def _long_trace(seed=7, n=6, **kw):
    """Prompts long enough that chunk_size=16 splits them."""
    kw.setdefault("prompt_len", (20, 60))
    kw.setdefault("max_new", (3, 10))
    return _trace(seed, n, **kw)


def _streams(trace):
    return {r.rid: list(r.generated) for r in trace}


@pytest.fixture(scope="module")
def control_tokens(serving_params):
    """Non-speculative greedy streams for the shared trace shapes."""
    out = {}
    for name, mk in (("short", _trace), ("long", _long_trace)):
        tr = mk()
        _engine(serving_params).serve(tr)
        out[name] = _streams(tr)
    return out


# ---------------------------------------------------------------------------
# NgramProposer: suffix-cache lookup mechanics (pure host-side)
# ---------------------------------------------------------------------------


class TestNgramProposer:
    def test_proposes_continuation_of_repeated_ngram(self):
        p = NgramProposer(ngram_n=2)
        # history ...[5, 6] 7 8 ... [5, 6] -> draft continues 7, 8
        assert p.propose(0, [1, 5, 6, 7, 8, 2, 5, 6], 2) == [7, 8]

    def test_periodic_history_unrolls_past_its_end(self):
        p = NgramProposer(ngram_n=2)
        # period-2 cycle: the continuation reads from the draft itself
        # once it runs off committed history
        assert p.propose(0, [9, 3, 4, 3, 4], 5) == [3, 4, 3, 4, 3]

    def test_no_match_means_empty_draft(self):
        p = NgramProposer(ngram_n=3)
        assert p.propose(0, [1, 2, 3, 4, 5], 4) == []
        assert p.propose(0, [1, 1], 0) == []          # k = 0
        assert p.propose(0, [1], 4) == []             # too short

    def test_longest_ngram_wins_over_shorter(self):
        p = NgramProposer(ngram_n=2)
        # 1-gram [6] occurred at position 1 (-> 9), but the 2-gram
        # [5, 6] match (-> 7) is the more specific prediction
        assert p.propose(0, [5, 6, 9, 0, 5, 6, 7, 1, 5, 6], 1) == [7]

    def test_incremental_index_matches_fresh_proposer(self):
        # the suffix cache is incremental per rid; feeding the history
        # token-by-token must propose exactly what a fresh proposer
        # sees on the full history (determinism witness)
        rng = np.random.RandomState(0)
        hist = [int(t) for t in rng.randint(0, 8, 40)]
        inc = NgramProposer(ngram_n=3)
        for i in range(4, len(hist) + 1):
            got = inc.propose(0, hist[:i], 4)
            fresh = NgramProposer(ngram_n=3).propose(1, hist[:i], 4)
            assert got == fresh, i

    def test_release_and_shrunk_history_reset_state(self):
        p = NgramProposer(ngram_n=2)
        p.propose(0, [1, 2, 3, 1, 2], 2)
        p.release(0)
        assert p._index.get(0) is None
        # a rid reused with a SHORTER history (fresh engine, shared
        # proposer) must not propose phantom tokens from stale grams
        p.propose(1, [4, 5, 6, 7, 8, 9], 2)
        assert p.propose(1, [4, 5], 2) == []

    def test_rid_reuse_one_token_shorter_resets_not_crashes(self):
        # review regression: history shrunk by EXACTLY one token left
        # the old `done > len` guard asleep, and a stale gram whose
        # continuation start == the new length crashed the unroll with
        # IndexError on an empty draft list
        p = NgramProposer(ngram_n=2)
        p.propose(1, [1, 2, 3, 1, 2], 2)     # indexes up to done=4
        assert p.propose(1, [9, 9, 3, 1], 2) in ([], [2])  # no crash
        # same-length different-content reuse resets via the tail probe
        p2 = NgramProposer(ngram_n=2)
        p2.propose(2, [1, 2, 3, 1, 2], 2)
        got = p2.propose(2, [7, 8, 9, 7, 8], 2)
        assert got == [9, 7]   # fresh index of the NEW history only

    def test_protocol_conformance(self):
        assert isinstance(NgramProposer(), Proposer)


class TestEmptyWindowContract:
    def test_kv_len_shorter_than_window_is_exact_zeros(self):
        """The relaxed flash_decode contract the verify/chunk paths
        rely on: a row whose whole sequence is shorter than the fixed
        q window (kv_len < q_len) must return exact zeros for the
        empty-window rows and correct values for the real tail rows —
        on BOTH routes."""
        from apex_tpu.ops import flash_decode

        rng = np.random.RandomState(0)
        ps, h, d, q_len = 8, 2, 8, 5
        k_pages = jnp.asarray(rng.randn(4, ps, h, d).astype(np.float32))
        v_pages = jnp.asarray(rng.randn(4, ps, h, d).astype(np.float32))
        q = jnp.asarray(rng.randn(1, h, q_len, d).astype(np.float32))
        pt = jnp.asarray(np.array([[1, 2]], np.int32))
        kv = jnp.asarray(np.array([3], np.int32))   # < q_len
        outs = {}
        for route in ("xla", "decode"):
            with routing_override(decode=route):
                outs[route] = np.asarray(
                    flash_decode(q, k_pages, v_pages, pt, kv))
        for route, out in outs.items():
            assert np.all(np.isfinite(out)), route
            # rows 0..1 have empty causal windows (3 - 5 + i < 0)
            assert np.all(out[0, :, :2, :] == 0.0), route
            # rows 2..4 attend over 1..3 real columns — nonzero
            assert np.all(np.any(out[0, :, 2:, :] != 0.0, axis=-1)), route
        np.testing.assert_allclose(outs["decode"], outs["xla"],
                                   rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# commit_tokens: the exact-acceptance rule (pure policy)
# ---------------------------------------------------------------------------


class TestCommitTokens:
    def test_full_accept_commits_draft_plus_bonus(self):
        out, n_kv, a = commit_tokens([7, 8, 9], [7, 8, 9, 4],
                                     eos_id=None, remaining=10)
        assert out == [7, 8, 9, 4] and n_kv == 3 and a == 3

    def test_partial_accept_takes_bonus_from_divergence_row(self):
        # model agreed on d1, diverged at d2: commit d1 + the model's
        # own token at that position
        out, n_kv, a = commit_tokens([7, 8, 9], [7, 5, 9, 4],
                                     eos_id=None, remaining=10)
        assert out == [7, 5] and n_kv == 1 and a == 1

    def test_zero_accept_is_a_plain_decode_step(self):
        out, n_kv, a = commit_tokens([7, 8], [3, 8, 9],
                                     eos_id=None, remaining=10)
        assert out == [3] and n_kv == 0 and a == 0
        # and an empty draft commits exactly the argmax
        out, n_kv, a = commit_tokens([], [6], eos_id=None, remaining=10)
        assert out == [6] and n_kv == 0 and a == 0

    def test_eos_truncates_mid_commit(self):
        # d1 = eos: the stream ends there, accepted tail discarded
        out, n_kv, a = commit_tokens([5, 8, 9], [5, 8, 9, 4],
                                     eos_id=5, remaining=10)
        assert out == [5] and n_kv == 1 and a == 3

    def test_remaining_budget_truncates_mid_commit(self):
        out, n_kv, a = commit_tokens([7, 8, 9], [7, 8, 9, 4],
                                     eos_id=None, remaining=2)
        assert out == [7, 8] and n_kv == 2 and a == 3

    def test_row_count_mismatch_raises(self):
        with pytest.raises(ValueError, match="argmax rows"):
            commit_tokens([7, 8], [7], eos_id=None, remaining=5)
        with pytest.raises(ValueError, match="budget"):
            commit_tokens([7], [7, 8], eos_id=None, remaining=0)


# ---------------------------------------------------------------------------
# THE acceptance pin: bitwise streams, spec vs non-spec
# ---------------------------------------------------------------------------


class TestBitwiseContract:
    def test_speculative_streams_bitwise_match_plain_greedy(
            self, serving_params, control_tokens):
        tr = _trace()
        eng = _engine(serving_params, spec=SpecConfig(k=4))
        eng.serve(tr)
        assert _streams(tr) == control_tokens["short"]
        # the trace must actually have speculated (not vacuous)
        assert eng.proposer.drafted > 0
        assert eng.proposer.accepted > 0

    def test_chunked_prefill_streams_bitwise_match(
            self, serving_params, control_tokens):
        tr = _long_trace()
        eng = _engine(serving_params, spec=SpecConfig(k=0, chunk_size=16))
        eng.serve(tr)
        assert _streams(tr) == control_tokens["long"]

    def test_spec_plus_chunked_streams_bitwise_match(
            self, serving_params, control_tokens):
        tr = _long_trace()
        eng = _engine(serving_params,
                      spec=SpecConfig(k=3, chunk_size=16))
        eng.serve(tr)
        assert _streams(tr) == control_tokens["long"]
        assert eng.proposer.drafted > 0

    def test_preemption_mid_draft_is_output_invisible(
            self, serving_params, control_tokens):
        # a pool tight enough to preempt while speculation is live:
        # evicted drafts are simply dropped (proposer state is derived
        # from committed tokens), streams stay bitwise
        tr = _trace()
        eng = _engine(serving_params, spec=SpecConfig(k=4),
                      num_pages=7, max_pages_per_request=3)
        eng.serve(tr)
        assert sum(r.preemptions for r in eng.sched.finished) >= 1, (
            "tight pool was meant to force preemption")
        assert _streams(tr) == control_tokens["short"]
        assert eng.cache.pages_used == 0

    def test_preemption_of_mid_chunk_request_restarts_cleanly(
            self, serving_params):
        # a BURST of long arrivals over a pool too small to hold them:
        # chunked prefills get evicted mid-chunk, restart from zero on
        # re-admission, and the streams still match the roomy
        # non-speculative control
        tr = _long_trace(rate=50.0)
        ctrl = _engine(serving_params)
        ctrl.serve(tr)
        control = _streams(tr)
        tr2 = _long_trace(rate=50.0)
        eng = _engine(serving_params,
                      spec=SpecConfig(k=3, chunk_size=16),
                      num_pages=13, max_pages_per_request=9)
        eng.serve(tr2)
        assert sum(r.preemptions for r in eng.sched.finished) >= 1, (
            "burst was meant to force preemption")
        assert _streams(tr2) == control
        assert eng.cache.pages_used == 0

    def test_eos_mid_commit_matches_plain_greedy(self, serving_params):
        # pick a token the model emits mid-stream and rerun with it as
        # EOS on BOTH engines: the speculative commit must truncate at
        # exactly the same position plain decoding stops at
        prompts = [[int(x) for x in
                    np.random.RandomState(100 + i).randint(
                        0, CFG.vocab_size, 5 + 3 * i)] for i in range(2)]

        def run(spec, eos):
            eng = _engine(serving_params, spec=spec, max_batch=2)
            reqs = [eng.submit(p, 12, eos_id=eos) for p in prompts]
            eng.run()
            return [list(r.generated) for r in reqs]

        free = run(None, None)
        eos = free[0][4]
        assert run(SpecConfig(k=4), eos) == run(None, eos)

    def test_decode_route_ab_identical_tokens_with_spec(
            self, serving_params):
        # the verify launch at q_len = k+1 through the Pallas decode
        # kernel (interpret mode) vs the XLA baseline: same tokens
        prompts = [[1, 5, 1, 5, 1], [7, 3, 7, 3, 7, 3]]

        def run():
            eng = _engine(serving_params, spec=SpecConfig(k=3),
                          max_batch=2, max_pages_per_request=2)
            reqs = [eng.submit(p, 6) for p in prompts]
            eng.run()
            return [list(r.generated) for r in reqs], eng

        xla_out, _ = run()
        with routing_override(decode="decode"):
            kern_out, eng = run()
        assert kern_out == xla_out
        assert eng.proposer.drafted > 0


# ---------------------------------------------------------------------------
# Rollback, fallback, and page accounting
# ---------------------------------------------------------------------------


class _FixedProposer:
    """Test double: propose a fixed draft for every request."""

    def __init__(self, draft):
        self.draft = list(draft)
        self.observed = []

    def propose(self, rid, context, k):
        return self.draft[:k]

    def observe(self, drafted, accepted):
        self.observed.append((drafted, accepted))

    def release(self, rid):
        pass


class _EmptyProposer(_FixedProposer):
    def __init__(self):
        super().__init__([])


class TestRollbackAndFallback:
    def test_rejected_draft_rolls_back_kv_len(self, serving_params):
        # a garbage draft is fully rejected: the boundary commits ONE
        # token (the bonus), kv_len advances only over the committed
        # prefix, and the pages grown for the draft return to the pool.
        # (One engine step = admit + prefill + a first decode boundary,
        # so the verify fires inside step #1.)
        bad = _FixedProposer([63, 62, 61, 60])
        eng = _engine(serving_params,
                      spec=SpecConfig(k=4, proposer=bad), page_size=4)
        req = eng.submit([1, 2, 3, 4, 5], 8)
        eng.step()
        # prefill sampled token 1, the verify boundary committed ONLY
        # the bonus (drafted, accepted) == (4, 0)
        assert bad.observed == [(4, 0)]
        assert len(req.generated) == 2
        # THE rollback pin: the verify wrote K/V for positions
        # [5, 9] (last token + 4 draft rows) but only the last
        # committed token's row stays — kv_len is back to the
        # pre-draft seq_len (the bonus's K/V appends next boundary,
        # the plain-decode contract)
        assert req.kv_len == 6
        # ...and the pages grown for the rejected rows went back
        assert len(req.pages) == eng.cache.pages_needed(req.seq_len)
        # the engine still finishes the request identically to a
        # proposer-free control
        eng.run()
        ctrl = _engine(serving_params, page_size=4)
        ctrl_req = ctrl.submit([1, 2, 3, 4, 5], 8)
        ctrl.run()
        assert list(req.generated) == list(ctrl_req.generated)
        assert eng.cache.pages_used == 0

    def test_empty_drafts_fall_back_to_plain_decode(self, serving_params):
        from apex_tpu import telemetry as tel

        mem = tel.MemorySink()
        bus = tel.TelemetryBus(run_id="fallback", sinks=[mem])
        eng = _engine(serving_params,
                      spec=SpecConfig(k=4, proposer=_EmptyProposer()),
                      telemetry=bus)
        tr = _trace()
        eng.serve(tr)
        steps = [e for e in mem.events if e["type"] == "decode_step"]
        assert steps and all("spec_verify" not in e for e in steps), (
            "empty drafts must take the plain q_len=1 decode executable")
        # a speculative boundary lands the launch before it proposes
        # (ISSUE 34): no launch runs ahead, and every one lands
        assert all(e["in_flight"] == 0 for e in steps)
        assert sum(e["new_tokens"] for e in steps) == \
            sum(e["batch"] for e in steps)

    def test_draft_clamped_by_remaining_budget(self, serving_params):
        # a request one token from its budget must not overshoot
        # max_new_tokens however eagerly the proposer drafts
        greedy = _FixedProposer([1, 1, 1, 1])
        eng = _engine(serving_params,
                      spec=SpecConfig(k=4, proposer=greedy))
        req = eng.submit([2, 2, 2, 2], 2)
        eng.run()
        assert len(req.generated) == 2

    def test_spec_config_validates(self):
        with pytest.raises(ValueError, match="enables nothing"):
            SpecConfig(k=0)
        with pytest.raises(ValueError, match="k must be"):
            SpecConfig(k=-1)
        with pytest.raises(ValueError, match="chunk_size"):
            SpecConfig(k=2, chunk_size=0)

    def test_chunk_wider_than_prefill_budget_rejected(self, serving_params):
        with pytest.raises(ValueError, match="prefill "):
            _engine(serving_params,
                    spec=SpecConfig(k=0, chunk_size=CFG.max_position + 1))


# ---------------------------------------------------------------------------
# Chunked prefill: interleaving + scheduler policy
# ---------------------------------------------------------------------------


class TestChunkedPrefill:
    def test_long_prefill_interleaves_with_decode(self, serving_params):
        from apex_tpu import telemetry as tel

        mem = tel.MemorySink()
        bus = tel.TelemetryBus(run_id="interleave", sinks=[mem])
        eng = _engine(serving_params,
                      spec=SpecConfig(k=0, chunk_size=16),
                      telemetry=bus)
        short = eng.submit([1, 2, 3], 12)
        eng.step()                     # short admitted, decoding
        long_req = eng.submit(list(range(1, 61)), 4)
        eng.run()
        admits = {e["rid"]: e for e in mem.events
                  if e["type"] == "request_admit"}
        assert admits[long_req.rid].get("chunked") is True
        assert "chunked" not in admits[short.rid]
        # decode boundaries ran BETWEEN the long request's admission
        # and its first token — the 60-token prefill (4 chunks of 16)
        # never monopolized a boundary
        admit_step = admits[long_req.rid]["step"]
        first_tok_step = next(
            e["step"] for e in mem.events if e["type"] == "decode_step"
            and e["step"] >= admit_step)
        decode_between = [
            e for e in mem.events if e["type"] == "decode_step"
            and admit_step <= e["step"] < admit_step + 4]
        assert len(decode_between) >= 3, (
            "the short request must keep decoding under the long "
            "request's chunked prefill")
        assert first_tok_step is not None
        assert list(long_req.generated)  # and the long request finished

    def test_whole_row_path_used_at_or_under_chunk_size(
            self, serving_params):
        from apex_tpu import telemetry as tel

        mem = tel.MemorySink()
        bus = tel.TelemetryBus(run_id="wholerow", sinks=[mem])
        eng = _engine(serving_params, spec=SpecConfig(k=0, chunk_size=16),
                      telemetry=bus)
        req = eng.submit([1] * 16, 2)
        eng.step()
        # ctx == chunk_size: whole-row prefill (kv for the FULL context
        # lands in one launch and the admit event carries no chunked
        # flag), never chunked mode
        adm = next(e for e in mem.events if e["type"] == "request_admit")
        assert "chunked" not in adm
        assert req.prefill_pos is None and req.generated

    def test_admit_on_chunked_scheduler_refuses(self, serving_params):
        eng = _engine(serving_params, spec=SpecConfig(k=0, chunk_size=16))
        with pytest.raises(RuntimeError, match="schedule_prefill"):
            eng.sched.admit()

    def test_chunk_budget_caps_per_boundary_work(self, serving_params):
        # prefill_budget 20 / chunk 16: two long arrivals cannot both
        # launch a chunk in one boundary — a's first chunk consumes the
        # budget, so b's ADMISSION (which would launch its first chunk)
        # waits for the next boundary
        eng = _engine(serving_params, spec=SpecConfig(k=0, chunk_size=16),
                      prefill_budget=20, max_pages_per_request=6)
        a = eng.submit(list(range(1, 41)), 2)
        b = eng.submit(list(range(2, 42)), 2)
        eng.step()
        assert a.prefill_pos == 16               # one chunk advanced
        assert b.state == "waiting" and not b.pages
        eng.step()
        # in-flight chunks outrank admissions: a advances again, b
        # keeps waiting until a boundary has chunk_size budget free
        assert a.prefill_pos == 32
        assert b.state == "waiting"
        eng.run()
        assert len(a.generated) == 2 and len(b.generated) == 2

    def test_chunked_default_page_table_width_covers_max_position(
            self, serving_params):
        # review regression: with chunking on, the DEFAULT
        # max_pages_per_request must derive from max_position, not the
        # prefill row — the old default rejected the exact requests
        # chunking exists for, with a misleading pages error
        eng = _engine(serving_params, spec=SpecConfig(k=0, chunk_size=16),
                      prefill_budget=32)   # no explicit mppr
        req = eng.submit(list(range(1, 61)), 4)   # 64 > the 32-row
        eng.run()
        assert len(req.generated) == 4

    def test_restore_into_chunkless_engine_refuses_beyond_row_request(
            self, serving_params):
        # review regression: the restore() twin of recover()'s
        # chunk_size-preserving rebuild — a chunked snapshot holding a
        # beyond-the-row request must fail LOUDLY in a chunk-less
        # engine, not queue a request admission can never take
        src = _engine(serving_params, spec=SpecConfig(k=0, chunk_size=16),
                      prefill_budget=32)
        src.submit([7, 8, 9], 2)                  # servable anywhere
        src.submit(list(range(1, 61)), 4)         # beyond the row
        src.step()
        snap = json.loads(json.dumps(src.snapshot()))
        dst = _engine(serving_params, prefill_budget=32,
                      max_pages_per_request=10)
        with pytest.raises(ValueError, match="prefill budget"):
            dst.restore(snap)
        # ...and the refusal is ATOMIC: nothing was queued or retired,
        # so the engine is still fresh for a correctly-configured retry
        assert not dst.sched.waiting and not dst.sched.finished
        dst2 = _engine(serving_params, spec=SpecConfig(k=0, chunk_size=16),
                       prefill_budget=32)
        dst2.restore(snap)
        dst2.run()

    def test_chunked_request_may_exceed_the_prefill_row(
            self, serving_params):
        # THE point of chunking: with chunk_size set, prompt+max_new
        # may exceed the whole-row prefill budget (the request never
        # touches the row executable) — the same submit is rejected on
        # a row-only engine
        long_prompt = list(range(1, 61))
        row_only = _engine(serving_params, prefill_budget=32,
                           max_pages_per_request=9)
        with pytest.raises(ValueError, match="prefill budget"):
            row_only.submit(long_prompt, 4)
        eng = _engine(serving_params, spec=SpecConfig(k=0, chunk_size=16),
                      prefill_budget=32, max_pages_per_request=9)
        req = eng.submit(long_prompt, 4)
        eng.run()
        # and the stream matches a roomy whole-row control
        ctrl = _engine(serving_params, max_pages_per_request=9)
        ctrl_req = ctrl.submit(long_prompt, 4)
        ctrl.run()
        assert list(req.generated) == list(ctrl_req.generated)


# ---------------------------------------------------------------------------
# Snapshot/restore: in-flight chunk + draft state round trip
# ---------------------------------------------------------------------------


class TestSnapshotRestore:
    @pytest.mark.parametrize("cut", [1, 2, 3, 5, 8])
    def test_round_trip_mid_chunk_and_mid_draft(
            self, serving_params, control_tokens, cut):
        """Snapshot a spec+chunked engine at boundary ``cut`` — with
        requests mid-chunk and drafts in flight — restore into a
        fresh spec engine with a sentinel-poisoned pool, continue:
        streams bitwise the non-speculative control.  Chunk cursors
        and drafts are deliberately NOT in the snapshot: both rebuild
        deterministically from committed tokens, exactly like KV."""
        spec = SpecConfig(k=3, chunk_size=16)
        src = _engine(serving_params, spec=spec)
        tr = _long_trace()
        for r in tr:
            src.submit_request(r)
        for _ in range(cut):
            if src.sched.idle:
                break
            src.step()
        snap = json.loads(json.dumps(src.snapshot()))  # serializability
        dst = _engine(serving_params, spec=SpecConfig(k=3, chunk_size=16))
        dst.cache.k = jnp.full_like(dst.cache.k, 1e3)
        dst.cache.v = jnp.full_like(dst.cache.v, 1e3)
        restored = dst.restore(snap)
        dst.run()
        assert restored
        for r in restored:
            assert list(r.generated) == control_tokens["long"][r.rid], (
                cut, r.rid)

    def test_recover_keeps_chunking_for_beyond_row_requests(
            self, serving_params):
        """Review regression: recover() must rebuild the scheduler
        WITH chunk_size — a chunk-less rebuild could never re-admit a
        request whose context exceeds the prefill row (legal on a
        chunked engine), and FIFO first-failure-stops admission would
        then starve everything behind it forever."""
        from apex_tpu.resilience import chaos

        eng = _engine(serving_params, spec=SpecConfig(k=2, chunk_size=16),
                      prefill_budget=32, max_pages_per_request=10)
        ctrl = _engine(serving_params, spec=SpecConfig(k=2, chunk_size=16),
                       prefill_budget=32, max_pages_per_request=10)
        long_prompt = list(range(1, 61))       # 60 + 4 > the 32-row
        c = ctrl.submit(long_prompt, 4)
        ctrl.run()
        with chaos.ServingDeviceLoss(at_step=1, device_ids=[0]) as dl:
            req = eng.submit(long_prompt, 4)
            behind = eng.submit([1, 2, 3], 2)
            eng.run()
        assert dl.fired and eng.recoveries == 1
        assert eng.sched.chunk_size == 16      # chunking survived
        assert list(req.generated) == list(c.generated)
        assert len(behind.generated) == 2      # nothing starved

    def test_timeout_retirement_releases_proposer_state(
            self, serving_params):
        # review regression: a deadline death is a retirement too —
        # the expire path must drop the rid's suffix cache like
        # retire_finished does
        eng = _engine(serving_params, spec=SpecConfig(k=2),
                      clock=SimClock(1.0))
        req = eng.submit([5, 6, 5, 6, 5], 30, deadline_s=3.0)
        for _ in range(6):
            eng.step()
        assert req.finish_reason == "timeout"
        assert req.rid not in eng.proposer._index

    def test_context_is_memoized_until_tokens_commit(self):
        from apex_tpu.serving import Request

        r = Request(rid=0, prompt=[1, 2, 3], max_new_tokens=4)
        a = r.context
        assert r.context is a          # frozen history: same list,
        r.generated.append(7)          # no per-access copy
        b = r.context
        assert b is not a and b == [1, 2, 3, 7]

    def test_recovery_path_retirement_releases_proposer_state(
            self, serving_params):
        # review regression: a request finished through the recovery
        # path (_finish_restored) must drop its suffix-cache entry
        # like any other retirement
        eng = _engine(serving_params, spec=SpecConfig(k=2))
        req = eng.submit([5, 6, 5, 6, 5], 3)
        eng.run()
        assert req.rid not in eng.proposer._index
        eng2 = _engine(serving_params, spec=SpecConfig(k=2))
        r2 = eng2.submit([5, 6, 5, 6, 5], 3)
        # run to completion but capture BEFORE retirement, then finish
        # through the restore path
        while r2.launchable:
            eng2.step()
        # its last token is in flight: the snapshot lands it
        assert r2.in_flight == 1 and not r2.done
        snap = eng2.snapshot()
        assert r2.in_flight == 0 and r2.done
        dst = _engine(serving_params, spec=SpecConfig(k=2))
        dst.proposer.propose(r2.rid, [1, 2, 1, 2], 2)  # seed rid state
        dst.restore(snap)                     # done request: finished
        assert r2.rid not in dst.proposer._index

    def test_corrupt_page_between_chunks_caught_and_recovered_bitwise(
            self, serving_params):
        """Review regression: the chunk step must run the CRC
        read-back like every other pool-reading step — a page
        corrupted between chunks must raise BEFORE the final chunk
        samples the first token from damaged K/V (which recovery's
        re-prefill-from-kept-tokens would then have preserved
        forever)."""
        from apex_tpu.resilience.chaos import corrupt_page

        ctrl = _engine(serving_params, spec=SpecConfig(k=0, chunk_size=16))
        c = ctrl.submit(list(range(1, 61)), 4)
        ctrl.run()
        eng = _engine(serving_params, spec=SpecConfig(k=0, chunk_size=16),
                      validate_pages=True)
        req = eng.submit(list(range(1, 61)), 4)
        eng.step()                       # chunk 1 filled its pages
        assert req.prefill_pos == 16 and not req.generated
        corrupt_page(eng.cache, req.pages[0])
        eng.run()                        # chunk 2's read-back catches it
        assert eng.recoveries == 1
        assert list(req.generated) == list(c.generated)

    def test_recover_mid_trace_stays_bitwise(self, serving_params,
                                             control_tokens):
        # the in-process twin: a device loss mid-speculative-decode
        # rebuilds the pool and the streams still match the control
        from apex_tpu.resilience import chaos

        tr = _long_trace()
        with chaos.ServingDeviceLoss(at_step=3, device_ids=[0]) as dl:
            eng = _engine(serving_params,
                          spec=SpecConfig(k=3, chunk_size=16))
            eng.serve(tr)
        assert dl.fired and eng.recoveries == 1
        assert _streams(tr) == control_tokens["long"]


# ---------------------------------------------------------------------------
# Telemetry: spec_verify fields, accepted-tokens-per-step, schema
# ---------------------------------------------------------------------------


class TestSpecTelemetry:
    def test_stream_validates_and_carries_spec_fields(
            self, serving_params, tmp_path):
        from apex_tpu import telemetry as tel
        from apex_tpu.telemetry.__main__ import main as tel_cli

        path = str(tmp_path / "spec.jsonl")
        mem = tel.MemorySink()
        bus = tel.TelemetryBus(run_id="spec-l0",
                               sinks=[tel.JsonlSink(path), mem])
        eng = _engine(serving_params, spec=SpecConfig(k=4, chunk_size=16),
                      telemetry=bus)
        eng.serve(_long_trace())
        bus.close()
        for ev in mem.events:
            tel.validate_event(ev)
        assert tel_cli(["validate", path]) == 0
        verify_steps = [e for e in mem.events
                        if e["type"] == "decode_step"
                        and e.get("spec_verify")]
        assert verify_steps, "the trace was meant to speculate"
        for e in verify_steps:
            assert e["spec_verify"] is True
            assert e["spec_drafted"] >= 1
            assert 0 <= e["spec_accepted"] <= e["spec_drafted"]
            assert e["new_tokens"] >= e["batch"]  # bonus per row, minimum

    def test_summarize_reports_accepted_tokens_per_step(
            self, serving_params, tmp_path):
        from apex_tpu import telemetry as tel

        path = str(tmp_path / "spec_sum.jsonl")
        bus = tel.TelemetryBus(run_id="spec-sum",
                               sinks=[tel.JsonlSink(path)])
        eng = _engine(serving_params, spec=SpecConfig(k=4), telemetry=bus)
        eng.serve(_trace())
        bus.close()
        s = tel.summarize_file(path)
        acc = s["serving_accepted_tokens_per_step"]
        assert acc is not None and acc > 1.0, acc
        assert 0.0 < s["serving_spec_accept_rate"] <= 1.0
        out = tel.format_summary(s)
        assert "tok/step" in out and "spec accept" in out
        # ...and the diff table grows the acc-tok/step row
        assert "acc tok/step" in tel.format_diff(s, s)

    def test_plain_stream_reports_exactly_one(self, serving_params,
                                              tmp_path):
        from apex_tpu import telemetry as tel

        path = str(tmp_path / "plain.jsonl")
        bus = tel.TelemetryBus(run_id="plain", sinks=[tel.JsonlSink(path)])
        _engine(serving_params, telemetry=bus).serve(_trace())
        bus.close()
        s = tel.summarize_file(path)
        assert s["serving_accepted_tokens_per_step"] == 1.0
        assert "serving_spec_accept_rate" not in s

    def test_spec_fields_schema_discipline(self):
        from apex_tpu.telemetry import validate_event
        from apex_tpu.telemetry.schema import SchemaError

        def stamp(**payload):
            ev = {"type": "decode_step", "run_id": "r", "step": 0,
                  "t": 0.0, "ts": 0.0, "mesh": {},
                  "batch": 2, "new_tokens": 5, "pool_used": 1,
                  "pool_pages": 8}
            ev.update(payload)
            return ev

        validate_event(stamp(spec_verify=True, spec_drafted=4,
                             spec_accepted=3))
        validate_event(stamp())     # optional means absent is fine
        with pytest.raises(SchemaError, match="spec_verify"):
            validate_event(stamp(spec_verify=1))    # bool-not-int
        with pytest.raises(SchemaError, match="spec_drafted"):
            validate_event(stamp(spec_drafted=True))  # int-not-bool
        # request_admit's chunked flag is a real bool too
        adm = {"type": "request_admit", "run_id": "r", "step": 0,
               "t": 0.0, "ts": 0.0, "mesh": {}, "rid": 1,
               "context_tokens": 4, "pages": 1, "preemptions": 0}
        validate_event(dict(adm, chunked=True))
        with pytest.raises(SchemaError, match="chunked"):
            validate_event(dict(adm, chunked=1))
