"""The grouped expert product of a dropless expert layer.

For every (token, expert) pair that landed on a held expert ``e``, the
row ``x`` of its token goes through that expert's SwiGLU,
``(silu(x Wg_e) * (x Wu_e)) Wd_e``, or, for experts that are not gated
(Nemotron-H), its squared ReLU, ``relu(x Wu_e)^2 Wd_e``.  Two
``pallas_call``s do it over a PADDED PER-EXPERT LAYOUT of the pairs:

* **Row tiles fitted to the groups.**  The pairs are laid out expert by
  expert, and every expert's group starts on a multiple of the row tile
  ``tm``, so NO TILE EVER HOLDS THE ROWS OF TWO EXPERTS: a tile is
  multiplied by exactly one expert's weights, and a row lost, doubled
  or sent to the wrong expert cannot come of a group that straddles a
  tile, a group larger than a tile, or an empty group.  Pairs of
  experts this chip does not hold, and padding tokens, never enter the
  layout.  The buffer has the worst case's static size, ``pairs +
  n_held * (tm - 1)`` rounded up to ``tm``, so the layer stays dropless
  whatever the routing.
* **``tm`` from the static shape** (:func:`row_tile`): the mean pairs an
  expert takes, ``pairs / router width``, rounded up to whole bf16
  sublane tiles (16) and at most 256.  16 at a 64-row decode step of
  every expert model served here, 32-64 for a prefill row or chunk: a
  tile holds about the rows that really landed on its expert, where a
  grouped product with a fixed tile of hundreds of rows multiplies the
  whole tile for every expert it visits.
* **Each touched expert's weights are read from HBM once a call.**  The
  grid runs over column blocks of the weights (outer) and row tiles
  (inner), and a scalar-prefetched tile -> expert map picks each tile's
  weight block, so consecutive tiles of one expert name the same block
  and Pallas fetches nothing between them; an expert no pair chose is
  never fetched.  Tiles past the last active one (the count is
  prefetched too) name the last active tile's blocks, so they cost no
  DMA, and ``pl.when`` skips their multiply.
* **Numerics**: both products accumulate in float32; ``h = silu(gate) *
  up`` (or ``relu(up)^2``) is computed in float32 from the float32
  accumulators and rounded once to the activations' type before the
  down product, whose result is rounded to it too.

:func:`group_rows` makes the layout (outside any kernel: a sort,
scatters of indices, cumulative sums); :func:`grouped_swiglu` runs the two kernels,
``grouped_swiglu_gate_up`` (gate and up read the rows once, in one
kernel) and ``grouped_swiglu_down``; :func:`grouped_relu2` runs
``grouped_relu2_up`` and the same down kernel.  The weights keep the
stacked layout of the model (``wg``, ``wu`` ``[n, d, f]``, ``wd`` ``[n,
f, d]``; ``d`` the width the rows come in at, a latent one where the
layer has one).  Off a TPU the kernels run in Pallas' interpret mode.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops._pallas import LANE, pad_rows_to, use_interpret

#: the row tile is a multiple of this (a bf16 vector register's sublanes)
_TILE_ROWS = 16
_MAX_TILE = 256
#: a weight block (a column block of one expert's matrix) is at most
#: this large: two of gate and up, double-buffered, 16 MiB of VMEM
_WEIGHT_BLOCK_BYTES = 4 * 2 ** 20
_VMEM_LIMIT = 48 * 2 ** 20


def row_tile(pairs: int, width: int) -> int:
    """The row tile for ``pairs`` (token, expert) pairs routed over
    ``width`` experts: their mean an expert, in whole tiles of 16 rows,
    at most 256."""
    mean = -(-pairs // width)
    return min(_MAX_TILE, pad_rows_to(max(mean, 1), _TILE_ROWS))


def rows_tiled(load, tm: int):
    """The rows the kernels multiply for a layer whose held experts took
    ``load`` ``[n]`` pairs: its active tiles times ``tm``."""
    return jnp.sum((load + tm - 1) // tm) * tm


class GroupedRows(NamedTuple):
    """The padded per-expert layout of one call (:func:`group_rows`)."""
    load: jax.Array          # [n] pairs each held expert took
    dest: jax.Array          # [P] each pair's row of the buffer; M: none
    src: jax.Array           # [M] each row's pair; P: a padding row
    tile_expert: jax.Array   # [M // tm] each tile's expert
    active: jax.Array        # [1] tiles that hold any row


# jitted, as the kernels are below: traced once for every layer of a step
@functools.partial(jax.jit, static_argnames=("n", "tm"))
def group_rows(key, n: int, tm: int) -> GroupedRows:
    """``key`` ``[P]`` int32: the held expert ``[0, n)`` each pair went
    to, ``n`` where it goes to none.  Every expert's group starts on a
    multiple of ``tm`` and lies in order of the pairs; a tile past the
    last active one names the last active tile's expert (where no tile
    is active, expert ``n - 1``)."""
    pairs = key.shape[0]
    size = pad_rows_to(pairs + n * (tm - 1), tm)
    load = jnp.bincount(key, length=n + 1)[:n].astype(jnp.int32)
    tiles = (load + tm - 1) // tm
    tile_end = jnp.cumsum(tiles)
    active = tile_end[-1]
    group, order = jax.lax.sort(
        (key, jnp.arange(pairs, dtype=jnp.int32)), num_keys=1, is_stable=True)
    # a pair's row is its place in the sort shifted by its group's shift
    # (padded start less unpadded start).  Each group is a run of the
    # sort, so the shifts' steps are added where runs start and summed
    # along it, not gathered from the n shifts: the TPU compiles such a
    # gather into n selects a pair, which doubled a prefill row's
    # executable
    first = jnp.cumsum(load) - load
    shift = (tile_end - tiles) * tm - first
    steps = jnp.zeros((pairs,), jnp.int32).at[first].add(
        jnp.diff(shift, prepend=0), mode="drop")
    row = jnp.where(group < n,
                    jnp.arange(pairs, dtype=jnp.int32) + jnp.cumsum(steps),
                    size)
    dest = jnp.zeros((pairs,), jnp.int32).at[order].set(row)
    src = jnp.full((size,), pairs, jnp.int32).at[row].set(order, mode="drop")
    tile = jnp.minimum(jnp.arange(size // tm), jnp.maximum(active - 1, 0))
    tile_expert = jnp.minimum(
        jnp.sum(tile_end[None, :] <= tile[:, None], axis=1), n - 1)
    return GroupedRows(load, dest, src, tile_expert.astype(jnp.int32),
                       active.reshape(1).astype(jnp.int32))


def _column_block(cols: int, rows: int, itemsize: int) -> int:
    """The widest block of a ``[rows, cols]`` matrix's columns that is
    whole lane tiles, divides ``cols`` and stays under the block budget
    (``cols`` itself where it is no whole number of lane tiles)."""
    if cols % LANE:
        return cols
    fits = [b for b in range(LANE, cols + 1, LANE)
            if cols % b == 0 and rows * b * itemsize <= _WEIGHT_BLOCK_BYTES]
    return max(fits, default=LANE)


def _gate_up_kernel(tile_expert_ref, active_ref, x_ref, wg_ref, wu_ref,
                    h_ref):
    del tile_expert_ref                    # the index maps read it

    @pl.when(pl.program_id(1) < active_ref[0])
    def _():
        x = x_ref[...]
        gate = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
        up = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
        h_ref[...] = (gate / (1.0 + jnp.exp(-gate)) * up).astype(h_ref.dtype)


def _relu2_up_kernel(tile_expert_ref, active_ref, x_ref, wu_ref, h_ref):
    del tile_expert_ref

    @pl.when(pl.program_id(1) < active_ref[0])
    def _():
        up = jnp.maximum(jnp.dot(x_ref[...], wu_ref[...],
                                 preferred_element_type=jnp.float32), 0.0)
        h_ref[...] = (up * up).astype(h_ref.dtype)


def _down_kernel(tile_expert_ref, active_ref, h_ref, wd_ref, y_ref):
    del tile_expert_ref

    @pl.when(pl.program_id(1) < active_ref[0])
    def _():
        y_ref[...] = jnp.dot(h_ref[...], wd_ref[...],
                             preferred_element_type=jnp.float32
                             ).astype(y_ref.dtype)


def _grouped_call(kernel, name, rows, w, tm, interpret):
    """``rows`` ``[M, k_dim]`` times each tile's expert's ``w`` ``[n,
    k_dim, cols]`` -> ``[M, cols]``, a (column block, row tile) grid
    whose index maps clamp a row tile to the last active one."""
    size, k_dim = rows.shape
    cols = w.shape[-1]
    bc = _column_block(cols, k_dim, w.dtype.itemsize)

    def tile(i, active):
        return jnp.minimum(i, jnp.maximum(active[0] - 1, 0))

    row_spec = pl.BlockSpec(
        (tm, k_dim), lambda j, i, te, active: (tile(i, active), 0))
    w_spec = pl.BlockSpec(
        (None, k_dim, bc), lambda j, i, te, active: (te[i], 0, j))
    out_spec = pl.BlockSpec(
        (tm, bc), lambda j, i, te, active: (tile(i, active), j))
    n_w = 2 if kernel is _gate_up_kernel else 1
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(cols // bc, size // tm),
            in_specs=[row_spec] + [w_spec] * n_w, out_specs=out_spec),
        out_shape=jax.ShapeDtypeStruct((size, cols), rows.dtype),
        name=name,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )


def grouped_swiglu(rows, tile_expert, active, wg, wu, wd, *, tm: int):
    """``rows`` ``[M, d]`` in the layout of :func:`group_rows` (padding
    rows may hold anything: no other row reads them) -> ``[M, d]``, each
    active tile through its expert's SwiGLU; rows of inactive tiles are
    unspecified."""
    return _grouped_swiglu(rows, tile_expert, active, wg, wu, wd, tm=tm,
                           interpret=use_interpret())


# one jit for every layer of a model: the layers of one step share one
# tracing and one lowering of the two kernels (XLA inlines the calls);
# ``interpret`` is part of its key, so a trace for another backend never
# reuses it
@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def _grouped_swiglu(rows, tile_expert, active, wg, wu, wd, *, tm,
                    interpret):
    h = _grouped_call(_gate_up_kernel, "grouped_swiglu_gate_up", rows, wg,
                      tm, interpret)(tile_expert, active, rows, wg, wu)
    return _grouped_call(_down_kernel, "grouped_swiglu_down", h, wd, tm,
                         interpret)(tile_expert, active, h, wd)


def grouped_relu2(rows, tile_expert, active, wu, wd, *, tm: int):
    """As :func:`grouped_swiglu`, each active tile through its expert's
    squared ReLU ``relu(x Wu)^2 Wd``: ``grouped_relu2_up``, then the
    down kernel the SwiGLU experts use."""
    return _grouped_relu2(rows, tile_expert, active, wu, wd, tm=tm,
                          interpret=use_interpret())


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def _grouped_relu2(rows, tile_expert, active, wu, wd, *, tm, interpret):
    h = _grouped_call(_relu2_up_kernel, "grouped_relu2_up", rows, wu, tm,
                      interpret)(tile_expert, active, rows, wu)
    return _grouped_call(_down_kernel, "grouped_swiglu_down", h, wd, tm,
                         interpret)(tile_expert, active, h, wd)
