"""Grouped-query heads and a causal sliding window in the serving
attention (ISSUE 29): ``flash_attention``, ``flash_attention_varlen``
and ``flash_decode`` against a plain masked softmax, on the XLA routes
and every kernel route in interpret mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops import (flash_attention, flash_attention_varlen,
                          flash_decode)
from apex_tpu.ops import attention as att
from apex_tpu.ops.attention import flash_decode_route, routing_override

D = 16


def plain(q, k, v, see):
    """q [hq, sq, d], k/v [hk, sk, d], see [sq, sk] bool -> [hq, sq, d];
    a row that sees nothing gives zeros."""
    group = q.shape[0] // k.shape[0]
    k, v = np.repeat(k, group, 0), np.repeat(v, group, 0)
    s = np.einsum("hqd,hkd->hqk", q, k) / np.sqrt(q.shape[-1])
    s = np.where(see[None], s, -np.inf)
    m = np.where(np.isfinite(s.max(-1, keepdims=True)),
                 s.max(-1, keepdims=True), 0.0)
    p = np.where(see[None], np.exp(s - m), 0.0)
    l = p.sum(-1, keepdims=True)
    return np.einsum("hqk,hkd->hqd", p / np.where(l == 0, 1.0, l), v)


def rand(*shape, seed=0):
    return np.random.RandomState(seed).randn(*shape).astype(np.float32)


# -- prefill: flash_attention and flash_attention_varlen -----------------------

@pytest.mark.parametrize("route", ["xla", "varlen", "stream_skip", "stream"])
@pytest.mark.parametrize("window", [None, 1, 10, 64, 200])
def test_flash_attention_gqa_and_window(route, window, monkeypatch):
    hq, hk, s = 6, 2, 64
    q, k, v = rand(1, hq, s, D), rand(1, hk, s, D, seed=1), \
        rand(1, hk, s, D, seed=2)
    real = 50                      # the row's padding is its own segment
    seg = (np.arange(s) < real).astype(np.int32)
    if route == "stream":
        seg = np.ones(s, np.int32)
    i, j = np.arange(s)[:, None], np.arange(s)[None]
    see = (j <= i) & (seg[:, None] == seg[None])
    if window is not None:
        see &= j > i - window
    monkeypatch.setattr(att, "_pallas_ok", lambda *a: True)
    with routing_override(fwd=route):
        got = flash_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=True,
            segment_ids=None if route == "stream" else jnp.asarray(seg)[None],
            window=window, block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(got[0]), plain(q[0], k[0], v[0],
                                                         see), atol=2e-5)


def test_flash_attention_mha_without_window_is_the_call_it_was():
    q, k, v = (jnp.asarray(rand(2, 4, 32, D, seed=i)) for i in range(3))
    a = jax.make_jaxpr(lambda *x: flash_attention(*x, causal=True))(q, k, v)
    b = jax.make_jaxpr(lambda *x: flash_attention(
        *x, causal=True, window=None))(q, k, v)
    assert str(a) == str(b) and "custom_vjp" in str(a)


@pytest.mark.parametrize("bad", [dict(causal=False), dict(dropout_rate=0.1,
                                                          dropout_seed=1)])
def test_gqa_and_window_are_for_causal_inference_calls(bad):
    q, k = jnp.zeros((1, 4, 16, D)), jnp.zeros((1, 2, 16, D))
    kw = {"causal": True, **bad}
    with pytest.raises(ValueError, match="grouped-query"):
        flash_attention(q, k, k, **kw)


@pytest.mark.parametrize("window", [None, 5])
def test_flash_attention_varlen_gqa_and_window(window):
    hq, hk = 4, 2
    lens = [7, 12, 3]
    cu = np.concatenate([[0], np.cumsum(lens)])
    total = 24                      # two tokens of padding
    q, k, v = rand(total, hq, D), rand(total, hk, D, seed=1), \
        rand(total, hk, D, seed=2)
    got = np.asarray(flash_attention_varlen(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(cu),
        causal=True, window=window))
    for a, b in zip(cu[:-1], cu[1:]):
        n = b - a
        i, j = np.arange(n)[:, None], np.arange(n)[None]
        see = j <= i
        if window is not None:
            see &= j > i - window
        want = plain(q[a:b].transpose(1, 0, 2), k[a:b].transpose(1, 0, 2),
                     v[a:b].transpose(1, 0, 2), see)
        np.testing.assert_allclose(got[a:b].transpose(1, 0, 2), want,
                                   atol=2e-5)


# -- paged decode --------------------------------------------------------------

PS = 8


def paged_case(hq, h, q_len, window, kv_lens, compact, seed=0,
               lane_dense=False):
    """Pools, tables and the plain answer for rows of ``kv_lens``;
    ``lane_dense``: the pools handed over as ``[L, n, PS, h * D]``."""
    rng = np.random.RandomState(seed)
    b = len(kv_lens)
    n_pages = 1 + sum(-(-kv // PS) for kv in kv_lens)
    kp = rng.randn(2, n_pages, PS, h, D).astype(np.float32)
    vp = rng.randn(2, n_pages, PS, h, D).astype(np.float32)
    q = rng.randn(b, hq, q_len, D).astype(np.float32)
    width = max(-(-kv // PS) for kv in kv_lens)
    table = np.zeros((b, width), np.int32)
    start = np.zeros((b,), np.int32)
    want = np.zeros((b, hq, q_len, D), np.float32)
    nxt = 1
    for i, kv in enumerate(kv_lens):
        pages = list(range(nxt, nxt + -(-kv // PS)))
        nxt += len(pages)
        base = 0
        if compact and window is not None:
            # what a window pool still holds: from the first query's
            # oldest visible key on
            base = max(0, kv - q_len - window + 1) // PS
        table[i, :len(pages) - base] = pages[base:]
        start[i] = base * PS
        K = kp[1, pages].reshape(-1, h, D)[:kv].transpose(1, 0, 2)
        V = vp[1, pages].reshape(-1, h, D)[:kv].transpose(1, 0, 2)
        pos = kv - q_len + np.arange(q_len)[:, None]
        j = np.arange(kv)[None]
        see = (j <= pos) & (pos >= 0)
        if window is not None:
            see &= j > pos - window
        want[i] = plain(q[i], K, V, see)
    kw = dict(window=window)
    if compact:
        kw["kv_start"] = jnp.asarray(start)
    if lane_dense:
        kp, vp = (a.reshape(a.shape[:3] + (-1,)) for a in (kp, vp))
    got = flash_decode(jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
                       jnp.asarray(table), jnp.asarray(kv_lens, jnp.int32),
                       layer=1, **kw)
    return np.asarray(got), want


# contexts below, at and beyond the window (11: an edge inside a page)
KV_LENS = [5, 10, 11, 12, 19, 40, 83]


@pytest.mark.parametrize("route", ["xla", "decode"])
@pytest.mark.parametrize("hq,q_len,window,compact", [
    (2, 1, None, False),          # multi-head, as ever
    (6, 1, None, False),          # grouped
    (6, 1, 11, False),            # a window over the whole table
    (6, 1, 11, True),             # ... over the pages still held
    (6, 4, 11, True),             # a short chunk
    (6, 16, 11, True),            # a chunk wider than the window
    (2, 16, 24, False),           # multi-head chunk under a window
])
def test_flash_decode_gqa_and_window(route, hq, q_len, window, compact):
    with routing_override(decode=route):
        got, want = paged_case(hq, 2, q_len, window, KV_LENS, compact)
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("h", [1, 2, 4])
@pytest.mark.parametrize("q_len,window,compact", [
    (1, None, False),             # decode, a group of 16 a K/V head
    (1, 11, True),                # ... over a window pool's table
    (32, None, False),            # a chunk's tile: 512 rows a K/V head
    (32, 11, True),
])
def test_flash_decode_lane_dense_pool(h, q_len, window, compact):
    """A pool whose heads fill no sublane tile is kept ``[L, n, PS, h *
    D]`` (``lane_dense_pages``; one head stays head-split): the kernel
    over it agrees with the plain answer, the XLA route over it too,
    and it is the kernel over the same numbers stored head-split, bit
    for bit: the same contractions in the same order."""
    lane_dense = att.lane_dense_pages(h, jnp.float32)
    assert lane_dense == (h > 1)
    got = {}
    for route, layout in (("decode", lane_dense), ("decode", False),
                          ("xla", lane_dense)):
        with routing_override(decode=route):
            got[route, layout], want = paged_case(
                16 * h, h, q_len, window, KV_LENS, compact,
                lane_dense=layout)
    np.testing.assert_allclose(got["decode", lane_dense], want, atol=2e-5)
    np.testing.assert_allclose(got["xla", lane_dense], want, atol=2e-5)
    np.testing.assert_array_equal(got["decode", lane_dense],
                                  got["decode", False])


def test_lane_dense_pages_follow_the_heads_and_the_dtype():
    """Lane-dense where a device's heads fill no sublane tile and the
    pool is not one-byte codes; never one head."""
    for h, dtype, dense in ((2, jnp.bfloat16, True), (4, jnp.float32, True),
                            (6, jnp.bfloat16, True), (1, jnp.bfloat16, False),
                            (8, jnp.bfloat16, False),
                            (16, jnp.float32, False), (2, jnp.int8, False),
                            (4, jnp.float8_e4m3fn, False)):
        assert att.lane_dense_pages(h, dtype) == dense, (h, dtype)
    # a four-dimensional pool is lane-dense unless its last axis is the
    # head's: then it is one layer of a head-split pool
    assert att._pool_layout(jnp.zeros((1, 5, 64, 256)), 128) == (True, 64, 2)
    assert att._pool_layout(jnp.zeros((5, 64, 2, 128)), 128) == (False, 64, 2)
    assert att._pool_layout(jnp.zeros((1, 5, 64, 2, 128)), 128) == (
        False, 64, 2)


def test_a_quantized_pool_is_never_lane_dense():
    q = jnp.zeros((1, 4, 1, D))
    pool = jnp.zeros((1, 3, PS, 2 * D), jnp.int8)
    scale = jnp.ones((1, 3, PS, 2))
    with pytest.raises(ValueError, match="lane-dense"):
        flash_decode(q, pool, pool, jnp.zeros((1, 1), jnp.int32),
                     jnp.ones((1,), jnp.int32), k_scale=scale, v_scale=scale)


@pytest.mark.parametrize("hq,window,compact", [(6, 11, True), (6, None, False),
                                              (2, 20, False)])
def test_flash_decode_tiles_a_wide_grouped_chunk(hq, window, compact,
                                                 monkeypatch):
    """A K/V head's rows beyond 512 go in tiles of query positions; at
    test size the tile is forced down to 8."""
    monkeypatch.setattr(att, "_decode_q_tile",
                        lambda q_len, group: 8 if q_len > 8 else q_len)
    with routing_override(decode="decode"):
        got, want = paged_case(hq, 2, 32, window, [32, 33, 70, 90], compact)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_decode_q_tile():
    assert att._decode_q_tile(1, 6) == 1          # plain decode
    assert att._decode_q_tile(5, 1) == 5          # a verify window
    assert att._decode_q_tile(512, 1) == 512      # a multi-head chunk
    assert att._decode_q_tile(2048, 6) == 64      # afmoe's chunk
    assert att._decode_q_tile(2048, 1) == 512


def test_decode_body_and_pages_follow_the_shapes():
    """The body and the block of pages at the geometries the cells run:
    nothing but shapes and the pool's dtype chooses."""
    assert att._decode_body(1, 16, False) == "all_heads"    # GPT decode
    assert att._decode_body(6, 8, False) == "all_heads"     # Trinity decode
    assert att._decode_body(5, 16, False) == "all_heads"    # a verify window
    assert att._decode_body(1, 4, False) == "per_page"      # a tp=4 shard
    assert att._decode_body(1, 16, True) == "per_page"      # int8 / fp8 codes
    assert att._decode_body(6 * 2048, 8, False) == "per_head"   # a chunk
    assert att._decode_body(128, 16, True) == "per_page"
    # a lane-dense pool (Nemotron-H: 32 query heads on 2): its decode
    # rows go head by head over blocks; a few rows page by page
    assert att._decode_body(16, 2, False, True) == "per_head"
    assert att._decode_body(16 * 1024, 2, False, True) == "per_head"
    assert att._decode_body(1, 4, False, True) == "per_page"
    assert att._decode_body(1, 8, False, True) == "per_page"
    pages = att._decode_pages_per_step
    assert pages(64, 16, 128, 2, 28, "all_heads") == 4      # GPT: 2 MB a turn
    assert pages(64, 8, 128, 2, 264, "all_heads") == 8      # Trinity, full
    assert pages(64, 8, 128, 2, 97, "per_head") == 16       # its chunk
    assert pages(64, 16, 128, 2, 28, "per_head") == 8       # VMEM bounds it
    assert pages(64, 16, 128, 2, 3, "all_heads") == 3       # a short table
    assert pages(64, 16, 128, 1, 28, "per_page") == 16
    assert pages(64, 2, 128, 2, 32, "per_head") == 16       # Nemotron-H


@pytest.fixture
def pages_per_step(monkeypatch):
    """Force ``P`` (at test size the shapes would put a whole table in
    one block)."""
    def force(pages):
        monkeypatch.setattr(
            att, "_decode_pages_per_step",
            lambda page_size, h, d, itemsize, p_max, body: min(pages, p_max))
    return force


def both_routes(q, kp, vp, table, kv_lens, **kw):
    """The kernel in interpret mode and ``_paged_attention_xla`` on the
    same pages."""
    out = {}
    for route in ("decode", "xla"):
        with routing_override(decode=route):
            out[route] = np.asarray(flash_decode(
                q, kp, vp, jnp.asarray(table),
                jnp.asarray(kv_lens, jnp.int32), layer=1, **kw), np.float32)
    return out["decode"], out["xla"]


def pool_of(rng, shape, dtype):
    """A random two-layer pool in ``dtype`` and its scales (quantized)."""
    if dtype in ("int8", "fp8"):
        codes = (jnp.asarray(rng.randint(-127, 128, shape), jnp.int8)
                 if dtype == "int8" else
                 jnp.asarray(rng.randn(*shape), jnp.float8_e4m3fn))
        return codes, jnp.asarray(rng.uniform(0.01, 0.1, shape[:-1]),
                                  jnp.float32)
    return jnp.asarray(rng.randn(*shape), dtype), None


# P = 2 pages of 16 (32 with one-byte codes): kv_len one short of a
# block's edge, at it, one past it; an idle row (one token, a table of
# page 0); a row shorter than its query window; the table's last page
# (5 pages: p_max is no multiple of P)
@pytest.mark.parametrize("hq,h,q_len,dtype", [
    (16, 16, 1, "bfloat16"),      # all heads at once, a whole bf16 tile
    (8, 8, 1, "float32"),
    (48, 8, 1, "float32"),        # ... grouped, six rows a K/V head
    (8, 8, 5, "float32"),         # ... a verify window
    (4, 4, 1, "float32"),         # four heads: head by head, page by page
    (4, 4, 5, "bfloat16"),
    (16, 16, 1, "int8"),          # codes and scales follow the pages
    (8, 8, 5, "int8"),
    (16, 16, 1, "fp8"),
    (4, 4, 5, "fp8"),
    (12, 2, 16, "float32"),       # many rows: head by head, block by block
    (2, 2, 16, "bfloat16"),
])
def test_flash_decode_walks_blocks_of_pages(hq, h, q_len, dtype,
                                            pages_per_step):
    pages_per_step(2)
    ps = 32 if dtype in ("int8", "fp8") else 16
    edge = 2 * ps
    kv_lens = [edge - 1, edge, edge + 1, 1, max(q_len - 2, 1), 5 * ps,
               3 * ps + 3]
    rng = np.random.RandomState(q_len + h)
    b, p_max = len(kv_lens), 5
    n_pages = 1 + b * p_max
    kp, ks = pool_of(rng, (2, n_pages, ps, h, D), dtype)
    vp, vs = pool_of(rng, (2, n_pages, ps, h, D), dtype)
    table = np.zeros((b, p_max), np.int32)
    free = list(rng.permutation(np.arange(1, n_pages)))
    for i, kv in enumerate(kv_lens):
        if kv > 1:                  # the idle row keeps its table of page 0
            table[i, :-(-kv // ps)] = [free.pop() for _ in range(-(-kv // ps))]
    q = jnp.asarray(rng.randn(b, hq, q_len, D),
                    jnp.float32 if dtype == "float32" else jnp.bfloat16)
    kw = {} if ks is None else dict(k_scale=ks, v_scale=vs)
    got, want = both_routes(q, kp, vp, table, kv_lens, **kw)
    tol = 2e-5 if dtype == "float32" else 2 ** -7 * max(
        1.0, float(np.abs(want).max()))
    np.testing.assert_allclose(got, want, atol=tol)
    assert np.all(np.isfinite(got))
    # the short row: query rows before its first token see nothing
    short = kv_lens.index(max(q_len - 2, 1))
    empty = q_len - kv_lens[short]
    assert np.all(got[short, :, :empty] == 0)
    if empty < q_len:
        assert np.any(got[short, :, empty:] != 0)


@pytest.mark.parametrize("hq,h,q_len", [(8, 8, 1), (48, 8, 1), (48, 8, 4),
                                        (4, 4, 1), (12, 2, 16)])
@pytest.mark.parametrize("slack", [0, 1, 3], ids=["compact", "one_page_more",
                                                  "three_pages_more"])
def test_flash_decode_window_starts_inside_a_block(hq, h, q_len, slack,
                                                   pages_per_step):
    """The first visible page is the table's column ``slack``: with P =
    2 at a block's start, in its middle, and in the next block's
    middle; ``kv_start`` says where column 0 stands."""
    pages_per_step(2)
    ps, window = 8, 20
    kv_lens = [61, 64, 65, 99, 23, 9]
    rng = np.random.RandomState(slack)
    b, p_max = len(kv_lens), 9
    n_pages = 1 + b * p_max
    kp, vp = (jnp.asarray(rng.randn(2, n_pages, ps, h, D), jnp.float32)
              for _ in range(2))
    table = np.zeros((b, p_max), np.int32)
    start = np.zeros((b,), np.int32)
    free = list(rng.permutation(np.arange(1, n_pages)))
    for i, kv in enumerate(kv_lens):
        first = max(0, max(0, kv - q_len - window + 1) // ps - slack)
        held = -(-kv // ps) - first
        table[i, :held] = [free.pop() for _ in range(held)]
        start[i] = first * ps
    q = jnp.asarray(rng.randn(b, hq, q_len, D), jnp.float32)
    got, want = both_routes(q, kp, vp, table, kv_lens, window=window,
                            kv_start=jnp.asarray(start))
    np.testing.assert_allclose(got, want, atol=2e-5)
    # what lies before the window is not read: a pool whose pages
    # outside every row's window are poisoned gives the same answer
    seen = np.zeros((n_pages,), bool)
    for i, kv in enumerate(kv_lens):
        lo = max(0, kv - q_len - window + 1) - start[i]
        seen[table[i, lo // ps:-(-(kv - start[i]) // ps)]] = True
    poison = jnp.where(jnp.asarray(seen)[None, :, None, None, None], kp,
                       jnp.nan)
    with routing_override(decode="decode"):
        again = flash_decode(q, poison, jnp.where(
            jnp.asarray(seen)[None, :, None, None, None], vp, jnp.nan),
            jnp.asarray(table), jnp.asarray(kv_lens, jnp.int32), layer=1,
            window=window, kv_start=jnp.asarray(start))
    np.testing.assert_array_equal(np.asarray(again), got)


def test_flash_decode_tiled_chunk_walks_blocks(pages_per_step, monkeypatch):
    """A tile's rows end before the row's last page: its walk stops at
    the tile's own last block, and starts at its own window's first."""
    pages_per_step(2)
    monkeypatch.setattr(att, "_decode_q_tile",
                        lambda q_len, group: 8 if q_len > 8 else q_len)
    for window, compact in ((None, False), (20, True)):
        with routing_override(decode="decode"):
            got, want = paged_case(12, 2, 32, window, [32, 47, 48, 49, 90],
                                   compact)
        np.testing.assert_allclose(got, want, atol=2e-5)


def test_mha_decode_without_window_reaches_the_kernel_it_always_did():
    """Group 1, no window, no start: the same kernel name and operands
    as before grouped heads and windows were known; the grid is rows of
    the batch by tiles of query positions (the pages are walked inside
    the kernel), and the pools stay where they are."""
    q = jnp.zeros((2, 4, 1, D))
    pool = jnp.zeros((3, 6, PS, 4, D))
    table = jnp.zeros((2, 3), jnp.int32)
    kv = jnp.ones((2,), jnp.int32)

    def call(q=q, **kw):
        with routing_override(decode="decode"):
            jaxpr = jax.make_jaxpr(lambda *a: flash_decode(
                *a, layer=1, **kw))(q, pool, pool, table, kv)
        [eqn] = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
        return eqn

    plain_call = call()
    assert plain_call.params["name"] == "flash_decode"
    assert len(plain_call.invars) == 6      # table, kv_len, layer, q, k, v
    assert plain_call.params["grid_mapping"].grid == (2, 1)
    assert [v.aval.shape for v in plain_call.invars[4:]] == [pool.shape] * 2
    windowed = call(window=5, kv_start=jnp.zeros((2,), jnp.int32))
    assert windowed.params["name"] == "flash_decode_window"
    assert len(windowed.invars) == 7
    assert windowed.params["grid_mapping"].grid == (2, 1)
    # 1,024 rows a head: two tiles of query positions
    chunk = call(q=jnp.zeros((2, 4, 1024, D)))
    assert chunk.params["grid_mapping"].grid == (2, 2)


def test_decode_route_takes_grouped_heads_and_refuses_a_mismatch():
    pool = jax.ShapeDtypeStruct((8, 64, 4, 16), jnp.float32)
    with routing_override(decode="decode"):
        assert flash_decode_route(
            jax.ShapeDtypeStruct((2, 8, 1, 16), jnp.float32), pool) == "decode"
        assert flash_decode_route(
            jax.ShapeDtypeStruct((2, 6, 1, 16), jnp.float32), pool) == "xla"
