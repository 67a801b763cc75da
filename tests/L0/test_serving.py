"""Serving-engine tier (ISSUE 8): flash-decode parity over paged KV,
page-pool accounting, continuous-batching scheduler policy, and the
engine's bitwise batched-vs-sequential contract.

The decode kernel runs in interpret mode on CPU (forced via
``routing_override(decode="decode")``), so the parity sweep A/Bs the
Pallas kernel against the gather-based XLA baseline on IDENTICAL page
state — the acceptance bar is ≤ 1 bf16 ulp of the output scale
(measured ~1e-7 fp32; the two sides reduce in different orders, so
fp32-bitwise is not expected — docs/serving.md "Parity bar").
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_tpu.ops import flash_decode, flash_decode_route, routing_override
from apex_tpu.serving import (FINISHED, WAITING, ContinuousBatchingScheduler,
                              PagedKVCache, PagePoolExhausted, Request,
                              ServingEngine, ServingModelConfig, SimClock,
                              init_params, poisson_trace)

pytestmark = pytest.mark.serving


# ---------------------------------------------------------------------------
# Decode routing (ISSUE 8 satellite: the route must be forceable both
# ways so identical pages can A/B kernel vs generic)
# ---------------------------------------------------------------------------


class TestDecodeRouting:
    def _shapes(self, page_size=64, q_len=1):
        q = jax.ShapeDtypeStruct((2, 4, q_len, 16), jnp.float32)
        kp = jax.ShapeDtypeStruct((8, page_size, 4, 16), jnp.float32)
        return q, kp

    def test_auto_route_needs_tpu(self):
        q, kp = self._shapes()
        assert jax.default_backend() != "tpu"
        assert flash_decode_route(q, kp) == "xla"

    def test_forced_decode_skips_backend_gate(self):
        q, kp = self._shapes()
        with routing_override(decode="decode"):
            assert flash_decode_route(q, kp) == "decode"
        assert flash_decode_route(q, kp) == "xla"  # restored

    def test_forced_decode_still_respects_shape_gate(self):
        # a 6-row page is not a whole number of 8-row sublane tiles:
        # even a forced "decode" falls back
        q, kp = self._shapes(page_size=6)
        with routing_override(decode="decode"):
            assert flash_decode_route(q, kp) == "xla"

    def test_forced_xla(self):
        q, kp = self._shapes()
        with routing_override(decode="xla"):
            assert flash_decode_route(q, kp) == "xla"

    def test_head_mismatch_routes_generic(self):
        # 6 query heads over 4 pool heads is no grouping (8 over 4 is:
        # tests/L0/test_attention_gqa_window.py)
        q = jax.ShapeDtypeStruct((2, 6, 1, 16), jnp.float32)
        kp = jax.ShapeDtypeStruct((8, 64, 4, 16), jnp.float32)
        with routing_override(decode="decode"):
            assert flash_decode_route(q, kp) == "xla"

    def test_auto_route_requires_lane_aligned_head_dim(self, monkeypatch):
        # auto routing on TPU additionally requires d % 128 == 0 (the
        # K/V block's lane extent); a forced "decode" skips the lane
        # check (interpret mode has no lane constraint)
        from apex_tpu.ops import attention as att

        monkeypatch.setattr(att.jax, "default_backend", lambda: "tpu")
        q128 = jax.ShapeDtypeStruct((2, 4, 1, 128), jnp.float32)
        kp128 = jax.ShapeDtypeStruct((8, 64, 4, 128), jnp.float32)
        q16, kp16 = self._shapes()
        assert flash_decode_route(q128, kp128) == "decode"
        assert flash_decode_route(q16, kp16) == "xla"
        with routing_override(decode="decode"):
            assert flash_decode_route(q16, kp16) == "decode"

    def test_grain_is_dtype_dependent(self):
        # the sublane grain follows the POOL dtype (8 rows at fp32, 16
        # at bf16 — the `_pallas_ok` Mosaic rule): an 8-row bf16 page
        # must fall back even when the route is forced
        q16 = jax.ShapeDtypeStruct((2, 4, 1, 16), jnp.bfloat16)
        kp8 = jax.ShapeDtypeStruct((8, 8, 4, 16), jnp.bfloat16)
        kp16 = jax.ShapeDtypeStruct((8, 16, 4, 16), jnp.bfloat16)
        with routing_override(decode="decode"):
            assert flash_decode_route(q16, kp8) == "xla"
            assert flash_decode_route(q16, kp16) == "decode"


# ---------------------------------------------------------------------------
# Flash-decode parity sweep (acceptance): kernel vs XLA baseline on
# identical paged KV state
# ---------------------------------------------------------------------------


def _paged_state(rng, lengths, page_size, p_max, h, d, q_len,
                 dtype=np.float32):
    """Build a pool + page tables for ragged ``lengths``.

    Every pool slot is pre-filled with a large sentinel, then only the
    VALID (page, offset) slots of each request are overwritten with
    real values — if the kernel (or the baseline) ever reads a dead
    page or a past-``kv_len`` tail slot, the sentinel blows the diff up
    instead of hiding in the noise."""
    b = len(lengths)
    n_pages = 1 + b * p_max
    k_pages = np.full((n_pages, page_size, h, d), 1e3, dtype)
    v_pages = np.full((n_pages, page_size, h, d), 1e3, dtype)
    table = np.zeros((b, p_max), np.int32)
    # non-contiguous, shuffled page ids: the page-list indirection is
    # the thing under test
    free = list(rng.permutation(np.arange(1, n_pages)))
    for i, n in enumerate(lengths):
        used = -(-n // page_size)
        pages = [free.pop() for _ in range(used)]
        table[i, :used] = pages
        for t in range(n):
            pg, off = pages[t // page_size], t % page_size
            k_pages[pg, off, :, :] = rng.randn(h, d).astype(dtype)
            v_pages[pg, off, :, :] = rng.randn(h, d).astype(dtype)
    q = rng.randn(b, h, q_len, d).astype(dtype)
    return (jnp.asarray(q), jnp.asarray(k_pages), jnp.asarray(v_pages),
            jnp.asarray(table), jnp.asarray(np.asarray(lengths, np.int32)))


def _bf16_ulp_bound(ref):
    """One bf16 ulp at the output's magnitude scale — the documented
    parity bar (docs/serving.md)."""
    return max(float(np.max(np.abs(ref))), 1.0) * 2.0 ** -8


class TestFlashDecodeParity:
    @pytest.mark.parametrize("q_len", [1, 4])
    @pytest.mark.parametrize("page_size", [64, 128])
    def test_kernel_matches_xla_on_ragged_pages(self, q_len, page_size):
        rng = np.random.RandomState(q_len * 1000 + page_size)
        p_max, h, d = 3, 2, 16
        # ragged per-request lengths: minimal (= q_len), one-short-of,
        # exactly-at, and JUST-PAST a page boundary, plus a multi-page
        # crossing — the off-by-one surface of the page math
        lengths = [q_len, page_size - 1, page_size, page_size + 1,
                   2 * page_size + 1, 3 * page_size]
        args = _paged_state(rng, lengths, page_size, p_max, h, d, q_len)
        with routing_override(decode="xla"):
            ref = flash_decode(*args)
        with routing_override(decode="decode"):
            out = flash_decode(*args)
        ref, out = np.asarray(ref), np.asarray(out)
        assert np.all(np.abs(ref) < 100), "baseline read a sentinel slot"
        diff = np.max(np.abs(out - ref))
        assert diff <= _bf16_ulp_bound(ref), (
            f"decode kernel diverges from XLA baseline by {diff}")
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)

    def test_kernel_matches_xla_small(self):
        # the fast-tier sentinel of the slow sweep: one page size, both
        # q_lens, same adversarial sentinel construction
        for q_len in (1, 2):
            rng = np.random.RandomState(q_len)
            args = _paged_state(rng, [q_len, 9, 17], 8, 3, 2, 8, q_len)
            with routing_override(decode="xla"):
                ref = flash_decode(*args)
            with routing_override(decode="decode"):
                out = flash_decode(*args)
            ref, out = np.asarray(ref), np.asarray(out)
            assert np.max(np.abs(out - ref)) <= _bf16_ulp_bound(ref)

    def test_bf16_pool_parity(self):
        # page_size 16: the bf16 sublane grain (8 would fail the gate)
        rng = np.random.RandomState(7)
        args = _paged_state(rng, [5, 17], 16, 2, 2, 8, 1,
                            dtype=np.float32)
        args = tuple(a.astype(jnp.bfloat16) if a.dtype == jnp.float32
                     else a for a in args)
        with routing_override(decode="xla"):
            ref = np.asarray(flash_decode(*args), np.float32)
        with routing_override(decode="decode"):
            out = np.asarray(flash_decode(*args), np.float32)
        # bf16 storage: both sides accumulate fp32 but round the output
        # to bf16 — agreement bar is one bf16 ulp of the scale
        assert np.max(np.abs(out - ref)) <= _bf16_ulp_bound(ref)

    def test_causal_tail_within_q_len(self):
        # q_len > 1: row i of the query tail must NOT see columns past
        # kv_len - q_len + i.  Perturb the last cached token and check
        # only the last query row moves.
        rng = np.random.RandomState(3)
        q_len, ps = 3, 8
        args = _paged_state(rng, [10], ps, 2, 1, 8, q_len)
        q, kp, vp, pt, kl = args
        with routing_override(decode="xla"):
            base = np.asarray(flash_decode(q, kp, vp, pt, kl))
        # token index 9 (the last, seen only by query row 2) lives at
        # page pt[0,1], offset 1
        pg = int(pt[0, 1])
        vp2 = vp.at[pg, 1].add(1.0)
        for route in ("xla", "decode"):
            with routing_override(decode=route):
                pert = np.asarray(flash_decode(q, kp, vp2, pt, kl))
            assert np.allclose(pert[0, :, :2], base[0, :, :2],
                               atol=1e-6), route
            assert not np.allclose(pert[0, :, 2], base[0, :, 2]), route


# ---------------------------------------------------------------------------
# The whole pool as the operand (ISSUE 28): layer ``li`` of
# ``[L, n_pages, page_size, h, d]`` through the index map, against the
# same call on the slice ``pool[li]`` — and the model's jaxpr, which
# must hand the kernel the pool itself and never build the slice
# ---------------------------------------------------------------------------


def _whole_pool_state(rng, q_len, quantized, h=2):
    """Three rows over a random three-layer pool (every layer other
    bytes, so a read of the wrong layer cannot agree): one whose whole
    sequence is shorter than the query window, one idle row (an
    all-scratch page row, as the engine pads them) and one crossing
    two page boundaries.  ``quantized``: False, True (int8 codes) or
    "fp8"."""
    page_size, p_max, d = 32, 3, 8
    shape = (3, 7, page_size, h, d)     # [L, n_pages, page_size, h, d]
    if quantized:
        code = ((lambda: jnp.asarray(rng.randn(*shape), jnp.float8_e4m3fn))
                if quantized == "fp8" else
                (lambda: jnp.asarray(rng.randint(-127, 128, shape),
                                     jnp.int8)))
        k, v = code(), code()
        scales = {name: jnp.asarray(rng.uniform(0.01, 0.1, shape[:-1]),
                                    jnp.float32)
                  for name in ("k_scale", "v_scale")}
    else:
        k, v = (jnp.asarray(rng.randn(*shape), jnp.bfloat16)
                for _ in range(2))
        scales = {}
    table = np.zeros((3, p_max), np.int32)
    table[0, :1] = [4]
    table[2] = [5, 2, 6]
    kv_len = np.asarray([q_len - 1, q_len, 2 * page_size + 3], np.int32)
    q = jnp.asarray(rng.randn(3, h, q_len, d), jnp.bfloat16)
    return q, k, v, jnp.asarray(table), jnp.asarray(kv_len), scales


class TestFlashDecodeWholePool:
    @pytest.mark.parametrize("layer", [0, 1, 2],
                             ids=["first", "middle", "last"])
    @pytest.mark.parametrize("q_len", [1, 4])
    @pytest.mark.parametrize("quantized", [False, True, "fp8"],
                             ids=["bf16", "int8", "fp8"])
    @pytest.mark.parametrize("route", ["decode", "xla"])
    def test_layer_of_whole_pool_equals_its_slice(self, route, quantized,
                                                  q_len, layer):
        self._whole_equals_slice(route, quantized, q_len, layer, heads=2)

    @pytest.mark.parametrize("layer", [0, 2], ids=["first", "last"])
    @pytest.mark.parametrize("q_len", [1, 4])
    @pytest.mark.parametrize("quantized", [False, True, "fp8"],
                             ids=["bf16", "int8", "fp8"])
    @pytest.mark.parametrize("heads", [8, 16])
    def test_layer_of_whole_pool_all_heads_at_once(self, heads, quantized,
                                                   q_len, layer):
        # eight and sixteen heads fill sublane tiles: the kernel scores
        # them in one contraction (ISSUE 30), two go head by head; the
        # layer rides in the DMAs' source either way
        self._whole_equals_slice("decode", quantized, q_len, layer, heads)

    def _whole_equals_slice(self, route, quantized, q_len, layer, heads):
        rng = np.random.RandomState(17 * q_len + bool(quantized))
        q, k, v, table, kv_len, scales = _whole_pool_state(
            rng, q_len, quantized, heads)
        with routing_override(decode=route):
            assert flash_decode_route(q, k) == route
            whole = flash_decode(q, k, v, table, kv_len, layer=layer,
                                 **scales)
            sliced = flash_decode(
                q, k[layer], v[layer], table, kv_len,
                **{name: s[layer] for name, s in scales.items()})
        whole = np.asarray(whole, np.float32)
        assert np.array_equal(whole, np.asarray(sliced, np.float32))
        assert np.any(whole[2] != 0) and np.all(np.isfinite(whole))
        # row 0's window reaches one column short of its last query row
        assert np.all(whole[0, :, :1] == 0)
        if route == "decode":
            with routing_override(decode="xla"):
                ref = np.asarray(flash_decode(q, k, v, table, kv_len,
                                              layer=layer, **scales),
                                 np.float32)
            # the kernel rounds the probabilities to the pool's dtype
            # before it sums the values: one ulp of the output's binade
            assert np.max(np.abs(whole - ref)) <= 2 * _bf16_ulp_bound(ref)

    def test_layer_outside_the_pool_raises(self):
        q, k, v, table, kv_len, _ = _whole_pool_state(
            np.random.RandomState(0), 1, False)
        for layer in (-1, 3):
            with pytest.raises(ValueError, match="layer"):
                flash_decode(q, k, v, table, kv_len, layer=layer)
        with pytest.raises(ValueError, match="layer"):
            flash_decode(q, k[0], v[0], table, kv_len, layer=1)


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it, but
    not a kernel's own body (its blocks are pages, not pools)."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


@pytest.mark.parametrize("quantized", [False, True], ids=["fp", "int8"])
@pytest.mark.parametrize("method", ["decode", "extend"])
def test_model_hands_the_kernel_the_pool_itself(method, quantized):
    # what would have caught the copy of ISSUE 28: a slice of the pool
    # as a kernel operand is an array XLA has to materialise
    from apex_tpu.serving.model import PagedDecoder

    cfg = ServingModelConfig(vocab_size=64, hidden_size=32, num_heads=4,
                             num_layers=3, max_position=96)
    n_pages, page_size, b, p_max, window = 5, 32, 2, 2, 4
    sds = jax.ShapeDtypeStruct
    pool = sds((cfg.num_layers, n_pages, page_size, cfg.num_heads,
                cfg.head_dim), jnp.int8 if quantized else cfg.dtype)
    rows = (b,) if method == "decode" else (b, window)
    args = [sds(rows, jnp.int32)] * (2 if method == "decode" else 4)
    args += [sds((b, p_max), jnp.int32), sds((b,), jnp.int32)]
    kw = {}
    if quantized:
        kw = dict.fromkeys(("k_scale", "v_scale"),
                           sds(pool.shape[:-1], jnp.float32))
    params = jax.eval_shape(lambda: init_params(cfg, 0))
    step = getattr(PagedDecoder(cfg), method)
    with routing_override(decode="decode"):
        closed = jax.make_jaxpr(
            lambda params, k_pool, v_pool, args, kw: step(
                params, k_pool, v_pool, *args, **kw))(
            params, pool, pool, args, kw)
    n_leaves = len(jax.tree_util.tree_leaves(params))
    pools = set(closed.jaxpr.invars[n_leaves:n_leaves + 2])
    calls = 0
    for eqn in _eqns(closed.jaxpr):
        for out in eqn.outvars:
            assert out.aval.shape != pool.shape[1:], (
                f"{eqn.primitive.name} builds one layer of the pool")
        if eqn.primitive.name == "scatter" and eqn.invars[0] in pools:
            pools.add(eqn.outvars[0])       # the in-place append
        if eqn.primitive.name == "pallas_call":
            assert eqn.params["name"] == "flash_decode"
            calls += 1
            # page_table, kv_len, layer, q, then K and V
            k_op, v_op = eqn.invars[4:6]
            assert k_op in pools and v_op in pools and k_op is not v_op
            assert k_op.aval.shape == v_op.aval.shape == pool.shape
    assert calls == cfg.num_layers


# ---------------------------------------------------------------------------
# Page pool accounting
# ---------------------------------------------------------------------------


def _cache(num_pages=9, page_size=8, **kw):
    kw.setdefault("num_layers", 1)
    kw.setdefault("num_heads", 2)
    kw.setdefault("head_dim", 4)
    kw.setdefault("max_pages_per_request", 4)
    return PagedKVCache(num_pages=num_pages, page_size=page_size, **kw)


class TestPagedKVCache:
    def test_lowest_first_deterministic(self):
        c = _cache()
        assert c.allocate(3, owner=1) == [1, 2, 3]
        assert c.allocate(2, owner=2) == [4, 5]
        c.free([2, 4])
        # freed pages rejoin sorted: the next taker gets the LOWEST ids
        assert c.allocate(2, owner=3) == [2, 4]

    def test_exhaustion_raises_pool_untouched(self):
        c = _cache(num_pages=5)  # 4 allocatable
        c.allocate(3, owner=1)
        with pytest.raises(PagePoolExhausted):
            c.allocate(2, owner=2)
        assert c.pages_free == 1  # the failed allocate took nothing
        assert c.allocate(1, owner=2) == [4]

    def test_double_free_and_scratch_free_raise(self):
        c = _cache()
        pages = c.allocate(2, owner=1)
        c.free(pages)
        with pytest.raises(ValueError):
            c.free([pages[0]])
        with pytest.raises(ValueError):
            c.free([0])

    def test_page_table_pads_with_scratch_and_bounds_width(self):
        c = _cache()
        t = np.asarray(c.page_table([[3, 1], [2]], rows=4))
        assert t.shape == (4, 4)
        assert t[0].tolist() == [3, 1, 0, 0]
        assert t[1].tolist() == [2, 0, 0, 0]
        assert t[2].tolist() == [0, 0, 0, 0]
        with pytest.raises(ValueError):
            c.page_table([[1, 2, 3, 4, 5]])

    def test_write_tokens_lands_in_pages(self):
        c = _cache(num_pages=4, page_size=4, max_pages_per_request=3)
        pages = c.allocate(2, owner=1)  # 6 tokens -> 2 pages of 4
        T = 6
        k_new = np.arange(1 * T * 2 * 4, dtype=np.float32).reshape(
            1, T, 2, 4)
        idx = np.arange(T)
        pg = np.asarray(pages, np.int32)[idx // 4]
        off = idx % 4
        c.write_tokens(jnp.asarray(k_new), jnp.asarray(k_new), pg, off)
        got = np.asarray(c.k)[0, pg, off]
        np.testing.assert_array_equal(got, k_new[0])

    def test_defrag_compacts_and_rewrites_lists(self):
        c = _cache(num_pages=9, page_size=4)
        a = c.allocate(2, owner=1)
        b = c.allocate(2, owner=2)
        cc = c.allocate(2, owner=3)
        # stamp each page with its owner id so content is trackable
        k = np.array(c.k)  # writable copy
        for p in a + b + cc:
            k[:, p] = p
        c.k = jnp.asarray(k)
        c.v = jnp.asarray(k)
        c.free(b)
        lists = [a, cc]
        old_live = set(a) | set(cc)
        before = [[int(np.asarray(c.k)[0, p, 0, 0, 0]) for p in lst]
                  for lst in lists]
        mapping = c.defrag(lists)
        # live pages now occupy the dense prefix 1..4, lists rewritten
        assert sorted(p for lst in lists for p in lst) == [1, 2, 3, 4]
        after = [[int(np.asarray(c.k)[0, p, 0, 0, 0]) for p in lst]
                 for lst in lists]
        assert before == after  # content moved with the ids
        assert set(mapping) == old_live  # only live pages map
        assert c.pages_free == 4
        assert c.allocate(1, owner=9) == [5]

    def test_defrag_rejects_overlapping_lists(self):
        c = _cache()
        a = c.allocate(2, owner=1)
        with pytest.raises(ValueError):
            c.defrag([a, [a[0]]])


# ---------------------------------------------------------------------------
# Continuous-batching scheduler (host-side policy, no model)
# ---------------------------------------------------------------------------


def _sched(num_pages=9, page_size=8, max_batch=4, prefill_budget=64,
           max_position=64, max_pages_per_request=8):
    cache = PagedKVCache(num_layers=1, num_pages=num_pages,
                         page_size=page_size, num_heads=1, head_dim=4,
                         max_pages_per_request=max_pages_per_request)
    return ContinuousBatchingScheduler(
        cache, max_batch=max_batch, prefill_budget=prefill_budget,
        max_position=max_position), cache


def _simulate(sched, trace, max_steps=500):
    """Drive the scheduler with a fake model (decode = append token 0):
    returns the (admit/evict/retire) event log — the determinism
    witness."""
    log = []
    pending = sorted(trace, key=lambda r: (r.arrival_t, r.rid))
    i, t = 0, 0
    for t in range(max_steps):
        while i < len(pending) and pending[i].arrival_t <= t:
            sched.submit(pending[i])
            i += 1
        for req in sched.admit():
            req.kv_len = len(req.context)
            req.generated.append(0)  # prefill samples one token
            log.append(("admit", req.rid, len(req.pages)))
        for req in sched.retire_finished(float(t)):
            log.append(("retire", req.rid, len(req.generated)))
        if sched.running:
            for req in sched.ensure_decode_capacity():
                log.append(("evict", req.rid))
            for req in sched.running:
                req.kv_len = req.seq_len
                req.generated.append(0)
        for req in sched.retire_finished(float(t)):
            log.append(("retire", req.rid, len(req.generated)))
        if sched.idle and i == len(pending):
            break
    assert sched.idle, "scheduler did not drain"
    return log


class TestScheduler:
    def test_submit_rejects_never_servable(self):
        sched, _ = _sched(max_position=32)
        with pytest.raises(ValueError, match="max_position"):
            sched.submit(Request(rid=0, prompt=[1] * 30,
                                 max_new_tokens=10))
        sched2, _ = _sched(prefill_budget=16, max_position=64)
        with pytest.raises(ValueError, match="prefill budget"):
            sched2.submit(Request(rid=0, prompt=[1] * 10,
                                  max_new_tokens=10))
        sched3, _ = _sched(max_pages_per_request=2)
        with pytest.raises(ValueError, match="max_pages_per_request"):
            sched3.submit(Request(rid=0, prompt=[1] * 20,
                                  max_new_tokens=10))

    def test_seeded_trace_replays_identically(self):
        def run():
            sched, _ = _sched(num_pages=7, max_pages_per_request=4)
            trace = poisson_trace(42, 12, rate=2.0, prompt_len=(3, 12),
                                  max_new=(2, 8), vocab_size=16)
            return _simulate(sched, trace)

        a, b = run(), run()
        assert a == b
        assert any(e[0] == "evict" for e in a), (
            "trace was meant to exercise preemption")

    def test_exhaustion_evicts_not_oom(self):
        # pool of 4 pages, page_size 8: two requests of 20+12 tokens
        # cannot both finish resident — growth must preempt the newest
        sched, cache = _sched(num_pages=5, max_pages_per_request=4)
        r0 = Request(rid=0, prompt=[1] * 14, max_new_tokens=18)
        r1 = Request(rid=1, prompt=[1] * 14, max_new_tokens=4)
        sched.submit(r0)
        sched.submit(r1)
        for req in sched.admit():
            req.kv_len = len(req.context)
            req.generated.append(0)
        assert {r.rid for r in sched.running} == {0, 1}
        evicted = []
        for _ in range(60):
            if not sched.running and not sched.waiting:
                break
            evicted += sched.ensure_decode_capacity()
            for req in sched.running:
                req.kv_len = req.seq_len
                req.generated.append(0)
            sched.retire_finished(0.0)
            for req in sched.admit():
                req.kv_len = len(req.context)
                req.generated.append(0)
        assert evicted, "pool pressure should have preempted"
        assert all(r.state == FINISHED
                   for r in (r0, r1)), (r0.state, r1.state)
        assert cache.pages_used == 0

    def test_evicted_request_requeues_front_with_pages_freed(self):
        sched, cache = _sched(num_pages=5, max_pages_per_request=4)
        r0 = Request(rid=0, prompt=[1] * 8, max_new_tokens=2)
        sched.submit(r0)
        sched.admit()
        used = cache.pages_used
        assert used > 0
        victim = sched.preempt_one()
        assert victim is r0
        assert r0.state == WAITING and r0.pages == [] and r0.kv_len == 0
        assert r0.preemptions == 1
        assert cache.pages_used == 0
        assert sched.waiting[0] is r0

    def test_sizing_bug_caught_at_construction(self):
        # a request that could never fit the pool is impossible by
        # construction: submit() bounds every request by
        # max_pages_per_request, and the cache refuses an
        # max_pages_per_request wider than its allocatable pool — so
        # admit()'s PagePoolExhausted raise is pure defence in depth
        with pytest.raises(ValueError, match="allocatable"):
            PagedKVCache(num_layers=1, num_pages=3, page_size=8,
                         num_heads=1, head_dim=4,
                         max_pages_per_request=4)

    def test_retired_pages_immediately_reusable(self):
        sched, cache = _sched(num_pages=5, max_pages_per_request=4)
        r0 = Request(rid=0, prompt=[1] * 16, max_new_tokens=1)
        sched.submit(r0)
        sched.admit()
        first_pages = list(r0.pages)
        r0.generated.append(0)
        sched.retire_finished(0.0)
        assert cache.pages_used == 0
        r1 = Request(rid=1, prompt=[1] * 16, max_new_tokens=1)
        sched.submit(r1)
        sched.admit()
        # lowest-first allocation hands the SAME page ids back
        assert r1.pages == first_pages


# ---------------------------------------------------------------------------
# The engine: bitwise batching contract, preemption, telemetry
# ---------------------------------------------------------------------------


CFG = ServingModelConfig(vocab_size=64, hidden_size=32, num_heads=4,
                         num_layers=2, max_position=96)


@pytest.fixture(scope="module")
def serving_params():
    return init_params(CFG, seed=0)


def _prompts(n=4):
    return [[int(x) for x in
             np.random.RandomState(100 + i).randint(0, CFG.vocab_size,
                                                    5 + 3 * i)]
            for i in range(n)]


# a prefill row wide enough for a ladder of widths (ISSUE 32): rungs of
# 128 and 256 tokens, where CFG's row of 96 is its own only rung
CFG_LADDER = ServingModelConfig(vocab_size=64, hidden_size=32, num_heads=4,
                                num_layers=2, max_position=256)


def _run_engine(params, prompts, *, max_batch=4, num_pages=64,
                max_new=12, mppr=None, telemetry=None, eos=None, cfg=CFG):
    eng = ServingEngine(cfg, params, num_pages=num_pages, page_size=8,
                        max_batch=max_batch, max_pages_per_request=mppr,
                        prefill_budget=cfg.max_position,
                        telemetry=telemetry, clock=SimClock())
    reqs = [eng.submit(p, max_new, eos_id=eos) for p in prompts]
    eng.run()
    return [list(r.generated) for r in reqs], eng


class TestServingEngine:
    @pytest.mark.parametrize("ladder", [False, True],
                             ids=["one_rung", "every_rung"])
    def test_batched_matches_sequential_bitwise(self, serving_params,
                                                ladder):
        # THE acceptance criterion: continuous batching must not
        # perturb any request's greedy stream — token-for-token.  With
        # a ladder of prefill rows the requests land on every rung, and
        # on the same rung alone as in the batch (ISSUE 32).
        kw = {}
        prompts = _prompts(4)
        if ladder:
            kw = dict(cfg=CFG_LADDER, num_pages=160)
            serving_params = init_params(CFG_LADDER, seed=0)
            rng = np.random.RandomState(7)
            prompts = [[int(x) for x in rng.randint(0, 64, n)]
                       for n in (9, 128, 129, 192, 240)]
        batched, engB = _run_engine(serving_params, prompts, max_batch=4,
                                    **kw)
        sequential = [
            _run_engine(serving_params, [p], max_batch=1, **kw)[0][0]
            for p in prompts]
        assert batched == sequential
        assert all(len(g) == 12 for g in batched)
        assert engB.cache.pages_used == 0  # retirement drained the pool
        if ladder:
            assert engB.prefill_widths == (128, 256)
            assert [engB.prefill_width(len(p)) for p in prompts] == [
                128, 128, 256, 256, 256]

    def test_isolation_one_vs_crowd(self, serving_params):
        # one request's pages must never leak into another's attention:
        # the same prompt decodes identically alone and in a crowd
        prompts = _prompts(4)
        alone = _run_engine(serving_params, [prompts[2]], max_batch=1)[0][0]
        crowd, _ = _run_engine(serving_params, prompts, max_batch=4)
        assert crowd[2] == alone

    def test_eos_retires_early(self, serving_params):
        prompts = _prompts(2)
        free, _ = _run_engine(serving_params, prompts, max_new=12)
        # pick the token the model actually emits mid-stream and rerun
        # with it as EOS: greedy determinism makes this a fixed point
        eos = free[0][4]
        stopped, eng = _run_engine(serving_params, prompts, max_new=12,
                                   eos=eos)
        req0 = next(r for r in eng.sched.finished if r.rid == 0)
        assert stopped[0] == free[0][:free[0].index(eos) + 1]
        assert req0.finish_reason == "eos"
        assert len(stopped[0]) < 12

    def test_preemption_is_output_invisible(self, serving_params):
        prompts = _prompts(4)
        roomy, _ = _run_engine(serving_params, prompts, num_pages=64)
        tight, eng = _run_engine(serving_params, prompts, num_pages=9,
                                 mppr=4)
        assert sum(r.preemptions for r in eng.sched.finished) >= 1, (
            "tight pool was meant to force preemption")
        assert tight == roomy
        assert eng.cache.pages_used == 0

    def test_telemetry_stream_validates_and_summarizes(
            self, serving_params, tmp_path):
        from apex_tpu import telemetry as tel
        from apex_tpu.telemetry.__main__ import main as tel_cli

        path = str(tmp_path / "serving.jsonl")
        mem = tel.MemorySink()
        bus = tel.TelemetryBus(run_id="serve-l0",
                               sinks=[tel.JsonlSink(path), mem])
        _run_engine(serving_params, _prompts(3), num_pages=7, mppr=4,
                    max_new=6, telemetry=bus)
        bus.close()
        for ev in mem.events:
            tel.validate_event(ev)
        types = {e["type"] for e in mem.events}
        assert {"request_admit", "request_retire",
                "decode_step"} <= types
        # a preempted request's re-admission is visible in the stream
        readmits = [e for e in mem.events if e["type"] == "request_admit"
                    and e["preemptions"] > 0]
        evictions = [r for e in mem.events if e["type"] == "decode_step"
                     for r in e.get("evicted", [])]
        assert bool(readmits) == bool(evictions)
        # the existing CLI validates the stream (acceptance criterion)
        assert tel_cli(["validate", path]) == 0
        s = tel.summarize_file(path)
        assert s["serving_requests"] == 3
        assert s["serving_tpot_p50"] is not None
        assert s["serving_ttft_p50"] is not None
        assert 0 < s["serving_pool_peak"] <= 1
        out = tel.format_summary(s)
        assert "serving" in out and "tpot" in out

    def test_decode_route_ab_identical_tokens(self, serving_params):
        # the satellite A/B: the SAME engine workload with the decode
        # kernel forced (interpret mode on CPU) vs the generic paged
        # XLA baseline must emit identical greedy tokens
        prompts = _prompts(2)
        # mppr=2 keeps the interpret-mode page grid narrow
        xla_out, _ = _run_engine(serving_params, prompts, max_batch=2,
                                 max_new=4, mppr=2)
        with routing_override(decode="decode"):
            kern_out, _ = _run_engine(serving_params, prompts,
                                      max_batch=2, max_new=4, mppr=2)
        assert kern_out == xla_out

    def test_poisson_trace_serve_deterministic(self, serving_params):
        def run():
            eng = ServingEngine(CFG, serving_params, num_pages=17,
                                page_size=8, max_batch=3,
                                max_pages_per_request=5,
                                prefill_budget=CFG.max_position,
                                clock=SimClock(0.5))
            trace = poisson_trace(9, 10, rate=1.0, prompt_len=(4, 12),
                                  max_new=(2, 8), vocab_size=CFG.vocab_size)
            fin = eng.serve(trace)
            assert len(fin) == 10
            return {r.rid: list(r.generated) for r in fin}

        a, b = run(), run()
        assert a == b

    def test_serve_rejects_reused_trace(self, serving_params):
        # serve() rebases arrival times in place: a re-served trace
        # would double-rebase (and replay half-mutated request state),
        # so non-fresh requests are rejected up front
        eng = ServingEngine(CFG, serving_params, num_pages=16,
                            page_size=8, max_batch=2,
                            clock=SimClock(0.1))
        trace = poisson_trace(4, 3, rate=5.0, prompt_len=(4, 8),
                              max_new=(2, 3), vocab_size=CFG.vocab_size)
        assert len(eng.serve(trace)) == 3
        eng2 = ServingEngine(CFG, serving_params, num_pages=16,
                             page_size=8, max_batch=2,
                             clock=SimClock(0.1))
        with pytest.raises(ValueError, match="single-use"):
            eng2.serve(trace)

    def test_warmup_compiles_without_perturbing_serving(
            self, serving_params):
        # warmup must leave the pool in a servable state (its zero K/V
        # lands only in scratch page 0) and not change any output
        prompts = _prompts(2)
        cold, _ = _run_engine(serving_params, prompts, max_batch=2,
                              max_new=5)
        eng = ServingEngine(CFG, serving_params, num_pages=64,
                            page_size=8, max_batch=2,
                            prefill_budget=CFG.max_position,
                            clock=SimClock())
        assert eng.warmup() > 0
        reqs = [eng.submit(p, 5) for p in prompts]
        eng.run()
        assert [list(r.generated) for r in reqs] == cold

    def test_rejects_unservable_up_front(self, serving_params):
        eng = ServingEngine(CFG, serving_params, num_pages=16,
                            page_size=8, clock=SimClock())
        with pytest.raises(ValueError):
            eng.submit([1] * 90, 20)  # 110 > max_position
        with pytest.raises(ValueError):
            eng.submit([1], 0)
        with pytest.raises(ValueError):
            eng.submit([], 4)


# ---------------------------------------------------------------------------
# One decode launch in flight (ISSUE 34): step n+1 is built and
# dispatched while step n runs, and step n's tokens are fetched after
# it.  Counts and order on the CPU, never times.
# ---------------------------------------------------------------------------


def _engine(params, **kw):
    kw.setdefault("num_pages", 64)
    kw.setdefault("max_batch", 4)
    kw.setdefault("clock", SimClock())
    return ServingEngine(CFG, params, page_size=8,
                         prefill_budget=CFG.max_position, **kw)


def _decode_records():
    from apex_tpu.telemetry import PHASE_RING
    return [r for r in PHASE_RING.snapshot() if r.name == "engine.decode"]


def _step_until_in_flight(eng, req, generated):
    """Step until ``req`` holds ``generated`` landed tokens and one more
    in flight."""
    while not (len(req.generated) == generated and req.in_flight):
        assert len(req.generated) <= generated
        eng.step()


class TestLaunchInFlight:
    @pytest.mark.parametrize("finish", ["length", "eos"])
    def test_streams_are_bitwise_sequential_with_rows_finishing_mid_batch(
            self, serving_params, finish):
        # budgets (or an EOS) that end rows in the middle of a batch
        # while the others run on
        prompts = _prompts(4)
        budgets = [3, 12, 7, 1]
        free, _ = _run_engine(serving_params, prompts, max_new=12)
        eos = free[1][5] if finish == "eos" else None

        def run(ps, bs, max_batch):
            eng = _engine(serving_params, max_batch=max_batch)
            reqs = [eng.submit(p, b, eos_id=eos) for p, b in zip(ps, bs)]
            eng.run()
            return reqs, eng

        reqs, eng = run(prompts, budgets, 4)
        alone = [run([p], [b], 1)[0][0] for p, b in zip(prompts, budgets)]
        assert [r.generated for r in reqs] == [r.generated for r in alone]
        assert [r.finish_reason for r in reqs] == \
            [r.finish_reason for r in alone]
        for r, b in zip(reqs, budgets):
            if r.finish_reason == "eos":
                assert r.generated.index(eos) == len(r.generated) - 1
            else:
                assert len(r.generated) == b
            assert r.in_flight == 0 and not r.pages
        if finish == "eos":
            assert reqs[1].finish_reason == "eos"
            assert len(reqs[1].generated) < budgets[1]
        assert eng.cache.pages_used == 0
        assert eng._flight is None and not eng.sched.in_flight

    def test_the_token_launched_after_an_unlanded_eos_is_dropped(
            self, serving_params):
        from apex_tpu.telemetry import PHASE_RING

        free, _ = _run_engine(serving_params, _prompts(1), max_new=12)
        eos = free[0][4]
        n = free[0].index(eos) + 1           # tokens up to the EOS
        PHASE_RING.clear()
        eng = _engine(serving_params)
        req = eng.submit(_prompts(1)[0], 12, eos_id=eos)
        eng.run()
        assert req.generated == free[0][:n] and req.finish_reason == "eos"
        decodes = _decode_records()
        # the prefill gave one token, n - 1 launches the rest, and one
        # more went out before the EOS was on the host: its token never
        # entered `generated`
        assert sum(r.attrs["rows"] for r in decodes) == n
        assert sum(len(r.attrs["rids"]) for r in decodes) == n - 1
        assert eng.cache.pages_used == 0 and not req.pages

    def test_in_flight_is_1_on_every_launch_but_the_first(
            self, serving_params):
        from apex_tpu import telemetry as tel
        from apex_tpu.telemetry import PHASE_RING

        PHASE_RING.clear()
        mem = tel.MemorySink()
        eng = _engine(serving_params,
                      telemetry=tel.TelemetryBus(run_id="fl", sinks=[mem]))
        for p in _prompts(4):
            eng.submit(p, 9)
        eng.run()
        launches = [r.attrs for r in _decode_records() if r.attrs["rows"]]
        assert len(launches) == eng.decode_steps == 8
        assert all(a["rows"] == 4 for a in launches)
        assert [a["in_flight"] for a in launches] == [0] + [1] * 7
        events = [e for e in mem.events if e["type"] == "decode_step"]
        for ev in events:
            tel.validate_event(ev)
        assert [e["in_flight"] for e in events if e["batch"]] == \
            [0] + [1] * 7
        # a forced landing empties the queue: the next launch is a
        # first again
        eng = _engine(serving_params)
        req = eng.submit(_prompts(1)[0], 9)
        PHASE_RING.clear()
        _step_until_in_flight(eng, req, 3)
        eng.snapshot()
        assert req.in_flight == 0 and len(req.generated) == 4
        eng.run()
        flags = [r.attrs["in_flight"] for r in _decode_records()
                 if r.attrs["rows"]]
        assert flags == [0, 1, 1, 0, 1, 1, 1, 1]

    def test_first_token_and_finish_are_stamped_at_landing(
            self, serving_params):
        eng = _engine(serving_params)
        req = eng.submit(_prompts(1)[0], 3)
        eng.step()                  # t=0: prefill, launch of token 2
        assert req.first_token_t == 0.0 and req.stream_t == 0.0
        assert len(req.generated) == 1 and req.in_flight == 1
        eng.step()                  # t=1: launch of token 3, token 2 lands
        assert len(req.generated) == 2 and req.in_flight == 1
        assert req.spent and not req.done and req.finish_t is None
        eng.step()                  # t=2: nothing to launch, token 3 lands
        assert len(req.generated) == 3 and req.in_flight == 0
        assert req.done and req.finish_t is None    # not yet retired
        assert eng.sched.running == [req] and not eng.sched.idle
        eng.step()                  # t=3: retired, on the host's clock
        assert req.finish_t == 3.0 and req.finish_reason == "length"
        assert eng.sched.idle

    def test_idle_is_false_while_a_launch_is_in_flight(self, serving_params):
        eng = _engine(serving_params)
        # a deadline that dies with the request's second token in
        # flight: nothing waits, nothing runs, and the launch is still
        # to be landed
        req = eng.submit(_prompts(1)[0], 8, deadline_s=0.5)
        eng.step()
        assert req.in_flight == 1
        eng.step()
        assert req.finish_reason == "timeout" and not req.pages
        assert not eng.sched.running and not eng.sched.waiting
        assert eng._flight is None and eng.sched.idle
        assert len(req.generated) == 1      # the token in flight: dropped
        eng = _engine(serving_params)
        req = eng.submit(_prompts(1)[0], 8)
        eng.step()
        eng.sched.running.remove(req)       # as a timeout leaves it
        assert eng.sched.in_flight and not eng.sched.idle

    @pytest.mark.parametrize("driver", ["run", "run_cut", "serve"])
    def test_the_drivers_return_with_nothing_in_flight(self, serving_params,
                                                       driver):
        eng = _engine(serving_params)
        if driver == "serve":
            trace = poisson_trace(3, 5, rate=2.0, prompt_len=(4, 9),
                                  max_new=(2, 6), vocab_size=CFG.vocab_size)
            fin = eng.serve(trace)
            assert len(fin) == 5
        else:
            reqs = [eng.submit(p, 6) for p in _prompts(3)]
            if driver == "run_cut":
                # cut short with a launch in flight: it lands, nothing
                # is left on the device
                eng.run(max_steps=3, raise_on_stall=False)
                assert all(len(r.generated) == 4 for r in reqs)
            else:
                eng.run()
        assert eng._flight is None and not eng.sched.in_flight
        assert all(r.in_flight == 0 for r in
                   list(eng.sched.running) + eng.sched.finished)

    @pytest.mark.parametrize("what", ["snapshot", "recover", "export",
                                      "preempt", "adopt"])
    def test_taken_with_a_launch_in_flight_the_stream_is_bitwise(
            self, serving_params, what):
        prompts = _prompts(3)
        control, _ = _run_engine(serving_params, prompts, max_new=10)
        kw = dict(kv_import=True) if what == "export" else {}
        eng = _engine(serving_params, **kw)
        reqs = [eng.submit(p, 10) for p in prompts]
        _step_until_in_flight(eng, reqs[0], 4)
        assert all(r.in_flight == 1 for r in reqs)
        if what == "snapshot":
            snap = eng.snapshot()           # lands the launch first
            assert all(len(r["generated"]) == 5 for r in snap["requests"])
            eng = _engine(serving_params)
            reqs = eng.restore(snap)
            eng.run()
        elif what == "recover":
            eng.recover(cause="device_loss")    # the launch is lost
            assert all(len(r.generated) == 4 and r.in_flight == 0
                       for r in reqs)
            assert eng._flight is None and not eng.sched.in_flight
            eng.run()
        elif what == "export":
            record, pages, kv_len = eng.export_request(reqs[1].rid)
            assert len(record["generated"]) == 5 and kv_len == \
                len(prompts[1]) + 4
            dst = _engine(serving_params, kv_import=True)
            dst.warmup()
            reqs[1] = dst.adopt_prefilled(record, pages, kv_len)
            dst.run()
            eng.run()
        elif what == "preempt":
            victim = eng.sched.preempt_one()
            assert victim is reqs[2] and victim.preemptions == 1
            # its token landed before its pages went
            assert len(victim.generated) == 5 and victim.in_flight == 0
            assert all(len(r.generated) == 5 for r in reqs)
            eng.run()
        else:
            other = _engine(serving_params)
            moved = other.submit(prompts[0], 10)
            other.step()
            eng.adopt([r for r in other.snapshot()["requests"]
                       if r["rid"] == moved.rid
                       and not r.update(rid=7)])
            assert all(len(r.generated) == 5 for r in reqs)
            eng.run()
            extra = next(r for r in eng.sched.finished if r.rid == 7)
            assert extra.generated == control[0]
        assert [r.generated for r in reqs] == control
        assert eng.cache.pages_used == 0

    def test_landing_for_a_preemption_retires_what_it_finishes(
            self, serving_params):
        # the victim's token in flight is its last: landing it retires
        # the request, its pages come back, and nobody is evicted
        eng = _engine(serving_params)
        long_, short = (eng.submit(p, n) for p, n in
                        zip(_prompts(2), (10, 3)))
        _step_until_in_flight(eng, short, 2)
        assert short.spent and eng.sched.running[-1] is short
        assert eng.sched.preempt_one() is None
        assert short.finish_reason == "length" and len(short.generated) == 3
        assert short.preemptions == 0 and not short.pages
        assert eng.sched.running == [long_] and long_.in_flight == 0
        eng.run()
        control, _ = _run_engine(serving_params, _prompts(2)[:1], max_new=10)
        assert long_.generated == control[0]

    def test_a_spent_row_gives_its_slot_to_the_next_admission(
            self, serving_params):
        eng = _engine(serving_params, max_batch=2)
        a, b, c = (eng.submit(p, n) for p, n in
                   zip(_prompts(3), (2, 8, 8)))
        eng.step()                  # a and b prefilled, launched
        assert a.spent and eng.sched.slots_used == 1
        eng.step()                  # c takes a's slot; a's token lands
        assert c.admit_t == 1.0 and a.done and a.finish_t is None
        assert len(eng.sched.running) == 3
        eng.step()
        assert a.finish_t == 2.0 and len(eng.sched.running) == 2
        eng.run()
        control = [_run_engine(serving_params, [p], max_new=n)[0][0]
                   for p, n in zip(_prompts(3), (2, 8, 8))]
        assert [r.generated for r in (a, b, c)] == control

    def test_no_recompile_from_empty_to_busy_to_empty_and_back(
            self, serving_params):
        from apex_tpu.analysis import hot_path_guard

        eng = _engine(serving_params)
        eng.warmup()
        with hot_path_guard("empty -> busy -> empty, twice",
                            transfers=None) as g:
            for _ in range(2):
                reqs = [eng.submit(p, 5) for p in _prompts(3)]
                eng.run()
                assert eng.sched.idle and eng._flight is None
                assert all(len(r.generated) == 5 for r in reqs)
        assert g.recompiles == 0 and g.syncs == []
