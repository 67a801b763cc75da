"""``flash_decode_latent`` (ISSUE 33): paged decode attention over a
latent page, one vector a token that every query head scores and whose
first ``v_dim`` numbers are the value.  Both routes (the kernel in
interpret mode, the XLA baseline) against a plain softmax over EXPANDED
heads: the absorbed query and output are what a latent layer hands the
kernel, per-head keys and values are what they stand for."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops import (flash_decode_latent, flash_decode_latent_route,
                          latent_walk_tiles, routing_override)

HEADS, RANK, ROPE, NOPE, VDIM, PS = 8, 64, 8, 16, 16, 8
WIDTH = 128        # RANK + ROPE, padded to a lane tile
SCALE = 0.17


def case(seed, b, q_len, p_max, kv_lens, share=0, dtype=jnp.float32):
    """A pool, page tables of which the rows' first ``share`` pages are
    the same pages, and per-head queries with the up-projections that
    turn the latent into keys and values."""
    rng = np.random.RandomState(seed)
    n_pages = b * p_max + 1
    pool = np.zeros((2, n_pages, PS, WIDTH), np.float32)
    pool[..., :RANK + ROPE] = rng.randn(2, n_pages, PS, RANK + ROPE)
    table = np.arange(1, 1 + b * p_max).reshape(b, p_max).astype(np.int32)
    table[1:, :share] = table[0, :share]
    q_nope = rng.randn(b, q_len, HEADS, NOPE).astype(np.float32)
    q_pe = rng.randn(b, q_len, HEADS, ROPE).astype(np.float32)
    wuk = rng.randn(RANK, HEADS, NOPE).astype(np.float32) / 8
    wuv = rng.randn(RANK, HEADS, VDIM).astype(np.float32) / 8
    return (jnp.asarray(pool, dtype), table, np.asarray(kv_lens, np.int32),
            q_nope, q_pe, wuk, wuv)


def expanded(pool, table, kv_lens, q_nope, q_pe, wuk, wuv, layer):
    """Plain softmax attention over per-head K and V, in float64."""
    pool = np.asarray(pool, np.float64)
    b, q_len = q_nope.shape[:2]
    out = np.zeros((b, q_len, HEADS, VDIM))
    for i in range(b):
        lat = pool[layer][table[i]].reshape(-1, WIDTH)[:kv_lens[i]]
        c, k_pe = lat[:, :RANK], lat[:, RANK:RANK + ROPE]
        k_nope = np.einsum("kc,chd->khd", c, wuk)
        v = np.einsum("kc,chd->khd", c, wuv)
        for r in range(q_len):
            limit = kv_lens[i] - q_len + r
            if limit < 0:
                continue
            s = (np.einsum("hd,khd->hk", q_nope[i, r], k_nope[:limit + 1])
                 + q_pe[i, r] @ k_pe[:limit + 1].T) * SCALE
            p = np.exp(s - s.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            out[i, r] = np.einsum("hk,khd->hd", p, v[:limit + 1])
    return out


def absorbed(route, pool, table, kv_lens, q_nope, q_pe, wuk, wuv, layer):
    q = np.concatenate([np.einsum("bqhd,chd->bqhc", q_nope, wuk), q_pe,
                        np.zeros(q_pe.shape[:-1] + (WIDTH - RANK - ROPE,),
                                 np.float32)], -1)
    with routing_override(decode=route):
        o = flash_decode_latent(jnp.asarray(q, pool.dtype), pool, table,
                                kv_lens, v_dim=RANK, scale=SCALE,
                                layer=layer)
    assert o.shape == q.shape[:-1] + (RANK,)
    return np.einsum("bqhc,chd->bqhd", np.asarray(o, np.float32), wuv)


CASES = {
    "one query a row, contexts that end inside a page":
        dict(b=3, q_len=1, p_max=5, kv_lens=[1, 17, 40]),
    "a chunk, tiled over its query positions":
        dict(b=2, q_len=128, p_max=40, kv_lens=[130, 300]),
    "rows sharing pages":
        dict(b=3, q_len=4, p_max=6, kv_lens=[20, 48, 33], share=2),
    "a context shorter than the window":
        dict(b=2, q_len=8, p_max=6, kv_lens=[3, 48]),
    "more pages than a block holds":
        dict(b=1, q_len=2, p_max=150, kv_lens=[1190]),
}


@pytest.mark.parametrize("route", ["xla", "decode"])
@pytest.mark.parametrize("name", list(CASES))
def test_absorbed_over_latent_pages_is_softmax_over_expanded_heads(
        name, route):
    args = case(sum(map(ord, name)), **CASES[name])
    got = absorbed(route, *args, layer=1)
    np.testing.assert_allclose(got, expanded(*args, layer=1), atol=2e-5)


def test_the_kernel_and_the_baseline_agree_in_bfloat16():
    args = case(7, b=2, q_len=16, p_max=8, kv_lens=[30, 64],
                dtype=jnp.bfloat16)
    np.testing.assert_allclose(absorbed("decode", *args, layer=0),
                               absorbed("xla", *args, layer=0), atol=2e-2)


def test_rows_with_nothing_to_see_return_zeros():
    pool, table, _, q_nope, q_pe, wuk, wuv = case(
        9, b=2, q_len=4, p_max=3, kv_lens=[0, 0])
    for route in ("xla", "decode"):
        got = absorbed(route, pool, table, np.asarray([2, 0], np.int32),
                       q_nope, q_pe, wuk, wuv, layer=0)
        assert not got[0, :2].any() and not got[1].any()
        assert np.isfinite(got).all() and got[0, 2:].any()


def test_a_chunks_front_padding_is_skipped_and_changes_no_real_row():
    """``q_start``: the kernel walks nothing for the steps that hold
    only padding (zeros come back), and every real row is what it was."""
    args = case(12, b=2, q_len=128, p_max=40, kv_lens=[200, 310])
    q_start = np.asarray([70, 0], np.int32)
    pool, table, kv_lens, q_nope, q_pe, wuk, wuv = args
    q = np.concatenate([np.einsum("bqhd,chd->bqhc", q_nope, wuk), q_pe,
                        np.zeros(q_pe.shape[:-1] + (WIDTH - RANK - ROPE,),
                                 np.float32)], -1)
    with routing_override(decode="decode"):
        plain = np.asarray(flash_decode_latent(
            jnp.asarray(q), pool, table, kv_lens, v_dim=RANK, scale=SCALE))
        skipped = np.asarray(flash_decode_latent(
            jnp.asarray(q), pool, table, kv_lens, v_dim=RANK, scale=SCALE,
            q_start=q_start))
    np.testing.assert_array_equal(skipped[0, 70:], plain[0, 70:])
    np.testing.assert_array_equal(skipped[1], plain[1])
    # the tiles of 64 positions wholly before position 70: one
    assert not skipped[0, :64].any() and plain[0, :64].any()


def test_routes_and_refusals():
    q = jax.ShapeDtypeStruct((1, 1, HEADS, WIDTH), jnp.float32)
    pool = jax.ShapeDtypeStruct((1, 4, PS, WIDTH), jnp.float32)
    assert flash_decode_latent_route(q, pool) == "xla"        # on the CPU
    with routing_override(decode="decode"):
        assert flash_decode_latent_route(q, pool) == "decode"
        odd = jax.ShapeDtypeStruct((1, 4, 4, WIDTH), jnp.float32)
        assert flash_decode_latent_route(q, odd) == "xla"
    zeros = jnp.zeros
    with pytest.raises(ValueError, match="layer"):
        flash_decode_latent(zeros(q.shape), zeros(pool.shape),
                            zeros((1, 2), jnp.int32), zeros((1,), jnp.int32),
                            v_dim=RANK, scale=1.0, layer=1)
    with pytest.raises(ValueError, match="width"):
        flash_decode_latent(zeros((1, 1, HEADS, 64)), zeros(pool.shape),
                            zeros((1, 2), jnp.int32), zeros((1,), jnp.int32),
                            v_dim=RANK, scale=1.0)


# ---------------------------------------------------------------------------
# Rows that read the same pages share the fetch and the product (ISSUE
# 36).  Pages of 64 as in the cell: a block is 16 pages, 1,024 columns.
# ---------------------------------------------------------------------------

BIG, BLOCK = 64, 16


def shared_batch(seed, rows, p_max, dtype=jnp.float32):
    """``rows``: (document or None, pages of it the row holds, kv_len,
    {slot: a page of its own in the document's place}).  A document's
    pages are the same page ids in every row that holds it; what a row
    needs beyond them is its own."""
    rng = np.random.RandomState(seed)
    table = np.zeros((len(rows), p_max), np.int32)
    docs, free = {}, 1
    for i, (doc, lead, kv_len, own) in enumerate(rows):
        if doc is not None and doc not in docs:
            docs[doc] = free + np.arange(p_max)
            free += p_max
        need = -(-kv_len // BIG)
        for slot in range(need):
            if doc is not None and slot < lead and slot not in own:
                table[i, slot] = docs[doc][slot]
            else:
                table[i, slot] = free
                free += 1
    pool = np.zeros((1, free, BIG, WIDTH), np.float32)
    pool[..., :RANK + ROPE] = rng.randn(1, free, BIG, RANK + ROPE)
    q = np.zeros((len(rows), 1, HEADS, WIDTH), np.float32)
    q[..., :RANK + ROPE] = rng.randn(len(rows), 1, HEADS, RANK + ROPE)
    return (jnp.asarray(q, dtype), jnp.asarray(pool, dtype), table,
            np.asarray([r[2] for r in rows], np.int32))


@functools.lru_cache(maxsize=None)
def _jitted(route):
    # a trace keeps the route it was made under
    return jax.jit(lambda *args: flash_decode_latent(
        *args, v_dim=RANK, scale=SCALE))


def latent(route, q, pool, table, kv_lens):
    with routing_override(decode=route):
        return np.asarray(_jitted(route)(q, pool, table, kv_lens),
                          np.float32)


def tiles_of(table, kv_lens):
    t = latent_walk_tiles(table, kv_lens, q_len=1, heads=HEADS,
                          page_size=BIG)
    n = int(np.sum(np.asarray(t.count) > 0))
    return (np.asarray(t.count)[:n].tolist(),
            np.asarray(t.shared)[:n].tolist(),
            int(t.walked), int(t.fetched))


DOC = 2 * BLOCK     # a document of two whole blocks
SHARED = {
    "eight rows on one document, tails of one to three blocks": dict(
        rows=[(0, DOC, 2048 + own, {}) for own in
              (5, 700, 1024, 1030, 2000, 2048, 2500, 3072)],
        p_max=80, count=[4, 4], shared=[2, 2],
        walked=8 * 2 + 15, fetched=2 * 2 + 15),
    "documents of one, two, three and five rows": dict(
        rows=[(2, DOC, 2300, {}), (0, DOC, 2100, {}), (3, DOC, 2050, {}),
              (2, DOC, 2049, {}), (3, DOC, 3000, {}), (3, DOC, 2500, {}),
              (1, DOC, 2200, {}), (2, DOC, 2700, {}), (3, DOC, 2048, {}),
              (1, DOC, 2600, {}), (3, DOC, 2101, {})],
        # in the order of the documents' first pages: 2, 0, 3, 3, 1
        p_max=48, count=[3, 1, 4, 1, 2], shared=[2, 0, 2, 0, 2],
        walked=11 * 3 - 1, fetched=(2 + 3) + 3 + (2 + 3) + 3 + (2 + 2)),
    "two tables that part inside a block": dict(
        rows=[(0, DOC, 2200, {}), (0, DOC, 2300, {20: "own"})],
        p_max=48, count=[2], shared=[1], walked=6, fetched=1 + 4),
    "a row that ends inside what the others share": dict(
        rows=[(0, DOC, 2200, {}), (0, DOC, 1500, {}), (0, DOC, 2300, {}),
              (0, DOC, 2100, {})],
        p_max=48, count=[4], shared=[1], walked=3 + 2 + 3 + 3,
        fetched=1 + 2 + 1 + 2 + 2),
    "rows with nothing to see between rows that share": dict(
        rows=[(0, DOC, 2100, {}), (None, 0, 0, {}), (0, DOC, 2200, {}),
              (None, 0, 0, {}), (0, DOC, 2300, {})],
        p_max=48, count=[3, 2], shared=[2, 0], walked=9, fetched=2 + 3),
    "no sharing at all": dict(
        rows=[(None, 0, 70, {}), (None, 0, 1024, {}), (None, 0, 2300, {}),
              (None, 0, 1025, {}), (None, 0, 3000, {})],
        p_max=48, count=[1] * 5, shared=[0] * 5, walked=10, fetched=10),
}


@pytest.mark.parametrize("name", list(SHARED))
def test_the_tiles_follow_from_the_page_tables(name):
    spec = SHARED[name]
    _, _, table, kv_lens = shared_batch(1, spec["rows"], spec["p_max"])
    assert tiles_of(table, kv_lens) == (
        spec["count"], spec["shared"], spec["walked"], spec["fetched"])


@pytest.mark.parametrize("name", list(SHARED))
def test_a_row_returns_bitwise_what_it_returns_alone(name):
    spec = SHARED[name]
    q, pool, table, kv_lens = shared_batch(
        sum(map(ord, name)), spec["rows"], spec["p_max"])
    got = latent("decode", q, pool, table, kv_lens)
    for i in range(len(kv_lens)):
        alone = latent("decode", q[i:i + 1], pool, table[i:i + 1],
                       kv_lens[i:i + 1])
        np.testing.assert_array_equal(got[i], alone[0], err_msg=f"row {i}")
    np.testing.assert_allclose(
        got, latent("xla", q, pool, table, kv_lens), atol=2e-5)


@pytest.mark.parametrize("name", list(SHARED))
def test_a_row_does_not_depend_on_the_batch_around_it(name):
    """Another order, and half of the rows taken away: other tiles,
    the same numbers."""
    spec = SHARED[name]
    q, pool, table, kv_lens = shared_batch(
        sum(map(ord, name)), spec["rows"], spec["p_max"])
    got = latent("decode", q, pool, table, kv_lens)
    order = np.random.RandomState(3).permutation(len(kv_lens))
    for keep in (order, order[::2]):
        part = latent("decode", q[keep], pool, table[keep], kv_lens[keep])
        np.testing.assert_array_equal(part, got[keep])


def test_the_grouped_walk_and_the_baseline_agree_in_bfloat16():
    spec = SHARED["documents of one, two, three and five rows"]
    args = shared_batch(5, spec["rows"], spec["p_max"], dtype=jnp.bfloat16)
    np.testing.assert_allclose(latent("decode", *args),
                               latent("xla", *args), atol=2e-2)
