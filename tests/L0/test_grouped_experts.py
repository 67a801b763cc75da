"""The expert layer's grouped product (``ops/grouped.py``) on the CPU, its
two kernels in Pallas' interpret mode.

Exactness first: the operands are small integers in bf16
(``chip_smoke.grouped_operands``), so every product and sum of the layer
is exact in float32 and a row lost, doubled or sent to the wrong expert
is an exact mismatch, not a question of tolerance.  Over group layouts
that a dropless layer meets (empty groups, one group holding every
pair, groups of ``tm - 1``, ``tm``, ``tm + 1`` and ``3 tm + 5`` rows,
7/8 of the pairs on absent experts, no expert held, every pair absent,
a pair count that is no multiple of ``tm``, padding tokens) the layer
equals, bit for bit, a plain loop over the experts and the three
``ragged_dot`` calls it replaced, with SwiGLU experts and with
squared-ReLU ones; and the layout keeps every tile to one expert.
Then one float case at each expert cell's decode and chunk shape,
widths reduced."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from apex_tpu.ops.grouped import group_rows, row_tile, rows_tiled  # noqa: E402
from apex_tpu.serving.experts import held_experts  # noqa: E402

D, F = 256, 128


def loop_reference(u, experts, weights, p, held, valid=None, act="swiglu"):
    """Expert by expert over the rows whose pairs chose it (numpy picks
    them): both products accumulated in float32, ``h`` (SwiGLU, or the
    squared ReLU of ``act="relu2"``) and the output rounded to the
    activations' type, the weighted sum in numpy."""
    lo, hi = held
    ex = np.asarray(experts)
    here = (ex >= lo) & (ex < hi)
    if valid is not None:
        here &= np.asarray(valid)[:, None]
    y = np.zeros(ex.shape + (u.shape[1],), np.float32)
    f32 = jnp.float32
    for e in range(hi - lo):
        t, j = np.nonzero(here & (ex == lo + e))
        if not len(t):
            continue
        x = u[t]
        up = jnp.dot(x, p["wu"][e], preferred_element_type=f32)
        if act == "relu2":
            h = jnp.square(jax.nn.relu(up)).astype(u.dtype)
        else:
            gate = jnp.dot(x, p["wg"][e], preferred_element_type=f32)
            h = (jax.nn.silu(gate) * up).astype(u.dtype)
        y[t, j] = np.asarray(jnp.dot(h, p["wd"][e], preferred_element_type=f32)
                             .astype(u.dtype), np.float32)
    w = np.where(here, np.asarray(weights), 0.0).astype(np.float32)
    return np.sum(y * w[..., None], axis=1)


def _sizes(counts):
    """k = 1 and expert ``e`` chosen by ``counts[e]`` tokens, in a
    shuffled order."""
    ex = np.concatenate([np.full(c, e) for e, c in counts.items()])
    return np.random.default_rng(7).permutation(ex)[:, None]


def _random(tokens, k, width, seed=3):
    rng = np.random.default_rng(seed)
    return np.argsort(-rng.random((tokens, width)), axis=1)[:, :k]


TM = 16        # row_tile of every case below but the last
LAYOUTS = {
    # name: (experts [T, k], router width, held, padding tokens first)
    "empty_groups": (np.where(_random(24, 2, 16) % 3 == 0, 0, 5), 16,
                     (0, 8), 0),
    "one_group_holds_every_pair": (_sizes({3: 40}), 16, (0, 8), 0),
    "a_group_of_tm_minus_1": (_sizes({1: TM - 1, 2: 3, 12: 4}), 16,
                              (0, 8), 0),
    "a_group_of_tm": (_sizes({1: TM, 2: 3, 12: 4}), 16, (0, 8), 0),
    "a_group_of_tm_plus_1": (_sizes({1: TM + 1, 2: 3, 12: 4}), 16,
                             (0, 8), 0),
    "a_group_of_3tm_plus_5": (_sizes({0: 3, 6: 3 * TM + 5, 7: TM,
                                      9: 2}), 16, (0, 8), 0),
    "seven_eighths_absent": (_random(40, 6, 16), 16, (6, 8), 0),
    "no_expert_held": (_random(16, 2, 16), 16, (0, 0), 0),
    "every_pair_absent": (_random(16, 2, 16) % 8, 16, (8, 16), 0),
    "pairs_no_multiple_of_tm": (_random(13, 3, 16), 16, (2, 10), 0),
    "padding_tokens_take_no_expert": (_random(20, 3, 16), 16, (0, 8), 7),
    "rows_of_one_document_route_alike": (
        np.argsort(-(np.random.default_rng(5).random((256, 16))
                     + 4.0 * (np.arange(16) % 5 == 0)), axis=1)[:, :4],
        16, (0, 16), 0),
}


@pytest.mark.parametrize("act", ["swiglu", "relu2"])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_integer_valued_layer_is_bitwise_the_loop_and_ragged_dot(layout, act):
    """SwiGLU experts (``grouped_swiglu_gate_up``) and squared-ReLU
    experts (``grouped_relu2_up``: the up weights -2..2 on four ones
    give ``relu(up)^2`` of at most 64, exact in bf16), then the same
    down kernel."""
    experts, width, held, padding = LAYOUTS[layout]
    tokens, k = experts.shape
    u, _, weights, p, valid = chip_smoke.grouped_operands(
        seed=11, tokens=tokens, top_k=k, width=width,
        held=(0, max(held[1] - held[0], 1)), d=D, f=F, padding=padding)
    if held[0] == held[1]:
        p = jax.tree_util.tree_map(lambda w: w[:0], p)
    experts = jnp.asarray(experts, jnp.int32)
    tm = row_tile(tokens * k, width)
    assert tm == (64 if layout.startswith("rows_of") else TM)
    y, load = jax.jit(lambda *a: held_experts(*a, held, valid, tm=tm,
                                              act=act))(
        u, experts, weights, p)
    want = loop_reference(u, experts, weights, p, held, valid, act)
    np.testing.assert_array_equal(np.asarray(y, np.float32),
                                  want.astype(u.dtype).astype(np.float32))
    here = ((np.asarray(experts) >= held[0]) & (np.asarray(experts) < held[1])
            & np.asarray(valid)[:, None])
    np.testing.assert_array_equal(
        np.asarray(load), np.bincount(np.asarray(experts)[here] - held[0],
                                      minlength=held[1] - held[0]))
    if held[0] == held[1]:
        assert not np.any(np.asarray(y))
        return
    y_r, load_r = jax.jit(lambda *a: chip_smoke.ragged_held_experts(
        *a, held, valid, act))(u, experts, weights, p)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_r))
    np.testing.assert_array_equal(np.asarray(load), np.asarray(load_r))


@pytest.mark.parametrize("layout", sorted(
    name for name, case in LAYOUTS.items() if case[2][0] != case[2][1]))
def test_no_tile_holds_two_experts_and_each_pair_has_one_row(layout):
    experts, width, (lo, hi), padding = LAYOUTS[layout]
    tokens, k = experts.shape
    n = hi - lo
    local = experts.reshape(-1) - lo
    here = (local >= 0) & (local < n) & np.repeat(
        np.arange(tokens) >= padding, k)
    key = np.where(here, local, n)
    tm = row_tile(tokens * k, width)
    g = jax.tree_util.tree_map(np.asarray, group_rows(
        jnp.asarray(key, jnp.int32), n, tm))
    size = len(g.src)
    assert size % tm == 0 and size >= here.sum() + n * (tm - 1)
    # each held pair has a row of its own that names it; no other does
    rows = g.dest[here]
    assert len(set(rows.tolist())) == here.sum() and rows.max(
        initial=-1) < size
    assert (g.dest[~here] == size).all()
    np.testing.assert_array_equal(g.src[rows], np.nonzero(here)[0])
    assert (g.src == len(key)).sum() == size - here.sum()
    # a tile's rows all belong to its expert, and the tiles that hold
    # rows come first
    active = int(g.active[0])
    assert active * tm == int(rows_tiled(g.load, tm))
    for t in range(size // tm):
        pairs = g.src[t * tm:(t + 1) * tm]
        pairs = pairs[pairs < len(key)]
        if t >= active:
            assert not len(pairs)
            # a tile past the last active one names the last active
            # tile's expert: no new weight block is fetched
            assert g.tile_expert[t] == g.tile_expert[max(active - 1, 0)]
        else:
            assert len(pairs) and (key[pairs] == g.tile_expert[t]).all()
    np.testing.assert_array_equal(g.load, np.bincount(key[here],
                                                      minlength=n))


@pytest.mark.parametrize("pairs, width, tm", [
    (64 * 4, 64, 16), (64 * 4, 256, 16), (64 * 6, 160, 16),   # decode
    (1024 * 4, 64, 64), (512 * 4, 64, 32),                    # LFM2 rows
    (2048 * 4, 256, 32), (1024 * 4, 256, 16),                 # Trinity
    (512 * 6, 160, 32),                                       # dsv2 chunk
    (64 * 22, 512, 16), (1024 * 22, 512, 48),                 # Nemotron
    (100_000, 8, 256), (3, 8, 16)])
def test_the_row_tile_is_the_mean_pairs_an_expert_takes(pairs, width, tm):
    assert row_tile(pairs, width) == tm


# The four expert cells' decode and chunk shapes (tokens, top_k, router
# width, experts held, the experts' activation), d and f cut to 256 and
# 128.
CELL_SHAPES = {
    "lfm2_decode": (64, 4, 64, (0, 64), "swiglu"),
    "lfm2_row": (1024, 4, 64, (0, 64), "swiglu"),
    "trinity_decode": (64, 4, 256, (0, 32), "swiglu"),
    "trinity_chunk": (2048, 4, 256, (0, 32), "swiglu"),
    "dsv2_decode": (64, 6, 160, (0, 20), "swiglu"),
    "dsv2_chunk": (512, 6, 160, (0, 20), "swiglu"),
    "nemotron_decode": (64, 22, 512, (0, 128), "relu2"),
    "nemotron_row": (1024, 22, 512, (0, 128), "relu2"),
}
# bf16, relative L2 of the output.  Against ragged_dot's three products:
# ragged_dot rounds gate and up to bf16 before silu where the kernel
# rounds h once from float32, so many h differ by a bf16 step (2^-8
# relative), and both round the output: measured 5.4e-3 to 5.8e-3.
# Against the float32 loop (the same bf16 inputs, nothing rounded): the
# kernel 2.9e-3, ragged_dot 4.9e-3 to 5.2e-3, so the kernel is also held
# to be no further from it than ragged_dot.
FLOAT_TOL = 1e-2


@pytest.mark.parametrize("shape", sorted(CELL_SHAPES))
def test_float_layer_at_each_cells_shapes(shape):
    tokens, k, width, held, act = CELL_SHAPES[shape]
    n = held[1] - held[0]
    ks = jax.random.split(jax.random.PRNGKey(41), 5)
    bf = jnp.bfloat16
    u = jax.random.normal(ks[0], (tokens, D), bf)
    p = {"wg": (jax.random.normal(ks[1], (n, D, F)) / 16).astype(bf),
         "wu": (jax.random.normal(ks[2], (n, D, F)) / 16).astype(bf),
         "wd": (jax.random.normal(ks[3], (n, F, D)) / 11).astype(bf)}
    experts = jnp.asarray(_random(tokens, k, width, seed=4), jnp.int32)
    weights = jax.random.uniform(ks[4], (tokens, k))
    tm = row_tile(tokens * k, width)
    y, _ = jax.jit(lambda *a: held_experts(*a, held, tm=tm, act=act))(
        u, experts, weights, p)
    y_r, _ = jax.jit(lambda *a: chip_smoke.ragged_held_experts(
        *a, held, act=act))(u, experts, weights, p)
    exact = loop_reference(u.astype(jnp.float32), experts, weights,
                           jax.tree_util.tree_map(
                               lambda w: w.astype(jnp.float32), p), held,
                           act=act)
    assert chip_smoke.rel_l2(y, y_r) < FLOAT_TOL
    assert chip_smoke.rel_l2(y, exact) < FLOAT_TOL
    assert chip_smoke.rel_l2(y, exact) <= chip_smoke.rel_l2(y_r, exact)
