"""The Mamba-2 (state-space duality) mixer's recurrence, in its two forms.

A state-space layer keeps, for every request, a STATE of fixed size
instead of a cache that grows: per head ``h`` of ``P`` channels a matrix
``S^h`` ``[P, N]`` (``N`` the state size) that every token updates,

    ``S_t^h = exp(delta_t^h A_h) S_{t-1}^h + delta_t^h x_t^h (outer) B_t``
    ``y_t^h = S_t^h C_t + D_h x_t^h``

with ``delta_t = softplus(dt_t + dt_bias)`` a scalar a head, ``A_h =
-exp(A_log_h)``, and ``B_t``, ``C_t`` (``[N]``) shared by all heads (one
group).  ``x``, ``B`` and ``C`` come out of a depthwise causal
convolution of ``d_conv`` taps over the projection's ``xBC`` channels,
so a request also keeps the last ``d_conv - 1`` rows of ``xBC``: its
TAIL.

**The state's layout.**  A request's state of one layer is one
``[N, H * P]`` float32 matrix, state index major: the lanes are (head,
channel), the order the projection leaves ``x`` in, and ``N`` lies on
the sublanes.  In that layout the update is elementwise against two
LANE vectors (the decay and ``delta x``, a row each) and two SUBLANE
vectors (``B``, ``C``, a column each), and the read-out ``S C`` is a sum
over sublanes, which the vector unit does with plain adds: nothing in
the kernel crosses lanes, and ``y`` comes out as the ``[H * P]`` row the
gate and the output projection take.  (Head major, ``[H, P, N]``, as the
equations are written, would put a lane reduction and a lane broadcast
on every one of a row's 512 vector registers.)

* :func:`ssm_decode_update` — the DECODE form: one token a row.  Each
  row's tail and state are read where they lie in the pools, advanced
  one token (the convolution, the update, the read-out) and written
  back IN PLACE by one ``pallas_call`` named ``ssm_decode_update`` whose
  two pool operands are aliased to its outputs and addressed through a
  scalar-prefetched slot table (as ``flash_decode`` addresses pages), so
  a row's 2 MiB of state cross HBM once in each direction and no batch
  of states or tails is ever gathered or scattered (XLA, asked to
  gather 64 tails from the pool, first re-lays the whole pool out: 62 MB
  copied a layer a step).  An idle row names the scratch slot 0.  The
  tail lies in its pool as ONE lane vector, its ``taps - 1`` rows end to
  end (``[L, n_slots, 1, (taps - 1) * ch]``): slices of whole lane
  tiles, where three rows of 4,352 would be a quarter-empty tile.
* :func:`ssd_chunk_scan` — the CHUNKED form for prefill: the same
  mathematics over blocks of ``chunk`` positions, as matrix products
  (inside a block the quadratic form, between blocks the state), taking
  an initial state and returning the final one.  A padded position has
  ``delta = 0``: state and output pass through it.  Plain XLA einsums
  under named scopes (``PERF.md``, PR 35, has the traced share that says
  they are enough).
* :func:`causal_conv` — the convolution of a (padded) row that starts
  from a tail and leaves the new one.

No VJP: the serving path never differentiates (training and the scan's
backward are ROADMAP.md, Reach 5).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops._pallas import LANE, use_interpret
from apex_tpu.ops.attention import _ROUTE_OVERRIDE

# a row's state is one block of the decode kernel (2 MiB at the
# published sizes: contiguous in HBM; in + out, double-buffered, 8 MiB
# of VMEM); past this many bytes a state the kernel is not taken
_UPDATE_STATE_BYTES = 4 * 2 ** 20
_UPDATE_VMEM_LIMIT = 48 * 2 ** 20


def ssm_decode_route(state_pool) -> str:
    """``"decode"`` (the Pallas kernel) or ``"xla"`` for a state pool
    ``[L, n_slots, N, H * P]``: as :func:`~apex_tpu.ops.
    flash_decode_route`, auto-routing picks the kernel only on a TPU and
    where ``N`` is a whole lane tile and the state's lanes are whole
    tiles, and ``routing_override(decode=...)`` forces either (a forced
    kernel runs in interpret mode off the TPU, at any ``N`` of whole
    sublane tiles)."""
    forced = _ROUTE_OVERRIDE["decode"]
    if forced == "xla":
        return "xla"
    n, lanes = state_pool.shape[-2:]
    if n % 8 or lanes % LANE or 4 * n * lanes > _UPDATE_STATE_BYTES:
        return "xla"
    if forced is None and (jax.default_backend() != "tpu" or n % LANE):
        return "xla"
    return "decode"


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _make_update_kernel(n: int, lanes: int, ch: int, taps: int, act):
    def kernel(slots_ref, layer_ref, a_ref, delta_ref, xbc_ref, w_ref,
               bias_ref, d_ref, s_ref, t_ref, y_ref, s_out_ref, t_out_ref):
        del slots_ref, layer_ref        # the index maps read them
        f32 = jnp.float32

        def conv(lo, hi):
            """The convolution's channels ``[lo, hi)`` (whole lane
            tiles, sliced off the refs): the tail's rows lie end to
            end."""
            acc = bias_ref[:, lo:hi] + w_ref[taps - 1:taps, lo:hi] \
                * xbc_ref[0, :, lo:hi].astype(f32)
            for j in range(taps - 1):
                acc = acc + w_ref[j:j + 1, lo:hi] * t_ref[
                    0, 0, :, j * ch + lo:j * ch + hi].astype(f32)
            return _silu(acc).astype(act).astype(f32)

        # B and C are lane vectors and the state wants them along the
        # sublanes: the row broadcast down the sublanes, transposed
        col = lambda row: jnp.broadcast_to(row, (LANE, n)).T    # [n, LANE]
        b = col(conv(lanes, lanes + n))
        c = col(conv(lanes + n, ch))
        for j in range(lanes // LANE):
            sl = slice(j * LANE, (j + 1) * LANE)
            x = conv(sl.start, sl.stop)                   # [1, LANE]
            s = a_ref[0, :, sl] * s_ref[0, 0, :, sl] \
                + b * (delta_ref[0, :, sl] * x)
            s_out_ref[0, 0, :, sl] = s
            y_ref[0, :, sl] = (jnp.sum(s * c, axis=0, keepdims=True)
                               + d_ref[:, sl] * x)
        # the tail moves up one row and takes the token's xBC
        kept = (taps - 2) * ch
        t_out_ref[0, 0, :, :kept] = t_ref[0, 0, :, ch:]
        t_out_ref[0, 0, :, kept:] = xbc_ref[0]

    return kernel


def _state_update_pallas(state_pool, conv_pool, slots, layer, decay, delta,
                         xbc, conv_w, conv_b, d_rep, act):
    """decay, delta ``[rows, H * P]`` f32 (a head's scalar on each of
    its lanes), xbc ``[rows, ch]`` (the tail's type), conv_w ``[taps,
    ch]``, conv_b ``[1, ch]``, d_rep ``[1, H * P]`` f32 -> (y ``[rows,
    H * P]`` f32, both pools with each row's slot advanced).  The pools
    are operands and results of one call."""
    rows, lanes = decay.shape
    n = state_pool.shape[2]
    taps, ch = conv_w.shape
    row = lambda width: pl.BlockSpec((1, 1, width), lambda i, *_: (i, 0, 0))
    whole = lambda a: pl.BlockSpec(a.shape, lambda i, *_: (0,) * a.ndim)
    at_slot = lambda a: pl.BlockSpec(
        (1, 1) + a.shape[2:],
        lambda i, slots, layer: (layer[0], slots[i], 0, 0))
    y, state_pool, conv_pool = pl.pallas_call(
        _make_update_kernel(n, lanes, ch, taps, act),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(rows,),
            in_specs=[row(lanes), row(lanes), row(ch), whole(conv_w),
                      whole(conv_b), whole(d_rep), at_slot(state_pool),
                      at_slot(conv_pool)],
            out_specs=[row(lanes), at_slot(state_pool),
                       at_slot(conv_pool)]),
        out_shape=[jax.ShapeDtypeStruct((rows, 1, lanes), jnp.float32),
                   jax.ShapeDtypeStruct(state_pool.shape, state_pool.dtype),
                   jax.ShapeDtypeStruct(conv_pool.shape, conv_pool.dtype)],
        # operands count the two prefetched tables: the pools are the
        # 9th and 10th
        input_output_aliases={8: 1, 9: 2},
        name="ssm_decode_update",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_UPDATE_VMEM_LIMIT),
        interpret=use_interpret(),
    )(slots, jnp.full((1,), layer, jnp.int32), decay[:, None, :],
      delta[:, None, :], xbc[:, None, :], conv_w, conv_b, d_rep,
      state_pool, conv_pool)
    return y[:, 0], state_pool, conv_pool


def _state_update_xla(state_pool, conv_pool, slots, layer, decay, delta,
                      xbc, conv_w, conv_b, d_rep, act):
    """The generic baseline, the same mathematics: the rows' states and
    tails gathered, advanced and scattered back (rows that name one
    slot, the idle ones, write it in an unspecified order: nobody reads
    the scratch slot)."""
    rows, lanes = decay.shape
    n = state_pool.shape[2]
    taps, ch = conv_w.shape
    tail = conv_pool[layer, slots]                       # [rows, 1, W]
    seq = jnp.concatenate([tail[:, 0], xbc], axis=-1).astype(jnp.float32)
    acc = conv_b + sum(conv_w[j] * seq[:, j * ch:(j + 1) * ch]
                       for j in range(taps))
    conv = _silu(acc).astype(act).astype(jnp.float32)
    x, b, c = (conv[:, :lanes], conv[:, lanes:lanes + n],
               conv[:, lanes + n:])
    s = state_pool[layer, slots].astype(jnp.float32)     # [rows, N, H * P]
    s = decay[:, None, :] * s + b[:, :, None] * (delta * x)[:, None, :]
    y = jnp.sum(s * c[:, :, None], axis=1) + d_rep * x
    tail = jnp.concatenate([tail[..., ch:], xbc[:, None, :]], -1)
    return (y, state_pool.at[layer, slots].set(s.astype(state_pool.dtype)),
            conv_pool.at[layer, slots].set(tail))


def _conv_taps(seq, w, bias, length: int):
    """``silu(sum_j w[j] * seq[i + j] + bias)`` for ``i < length``:
    ``seq`` ``[..., length + taps - 1, ch]``, ``w`` ``[taps, ch]``,
    float32 accumulation."""
    wf = w.astype(jnp.float32)
    acc = bias.astype(jnp.float32)
    for j in range(w.shape[0]):
        acc = acc + wf[j] * jax.lax.slice_in_dim(
            seq, j, j + length, axis=-2).astype(jnp.float32)
    return _silu(acc)


def ssm_decode_update(state_pool, conv_pool, slots, xbc, dt, *, layer: int,
                      conv_w, conv_b, dt_bias, a_log, d_skip, heads: int):
    """One token a row through a state-space layer's convolution and
    recurrence, on the pools, in place.

    ``state_pool`` ``[L, n_slots, N, H * P]`` (float32 as served),
    ``conv_pool`` ``[L, n_slots, 1, (taps - 1) * ch]`` with ``ch = H *
    P + 2 N`` (a tail's rows end to end); ``slots`` ``[rows]`` int32,
    each row's slot (0, the scratch slot, for an idle row; two real
    rows never name one slot); ``xbc`` ``[rows, ch]`` and ``dt``
    ``[rows, H]``, the projection's outputs for the rows' tokens;
    ``layer`` (static) the pools' layer.  ``conv_w`` ``[taps, ch]``,
    ``conv_b`` ``[ch]``, ``dt_bias``, ``a_log``, ``d_skip`` ``[H]``.
    The convolution's output is rounded to ``xbc``'s type, as an
    activation is, before it enters the recurrence.

    Returns (``y`` ``[rows, H * P]`` float32, ``S_t C_t + D x_t``;
    ``state_pool'``; ``conv_pool'``).  Pass the WHOLE pools (and donate
    them): a slot is addressed through the kernel's index map and
    updated where it lies."""
    if not 0 <= layer < state_pool.shape[0]:
        raise ValueError(f"layer {layer} is not one of the pool's "
                         f"{state_pool.shape[0]}")
    slots = jnp.asarray(slots, jnp.int32)
    lanes = state_pool.shape[-1]
    f32 = jnp.float32
    with jax.named_scope("ssm_update"):
        delta = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32))
        decay = jnp.exp(-jnp.exp(a_log.astype(f32)) * delta)  # [rows, H]
        per_head = lambda v: jnp.repeat(v, lanes // heads, axis=-1)
        update = (_state_update_pallas
                  if ssm_decode_route(state_pool) == "decode"
                  else _state_update_xla)
        return update(
            state_pool, conv_pool, slots, layer, per_head(decay),
            per_head(delta), xbc.astype(conv_pool.dtype),
            conv_w.astype(f32), conv_b.astype(f32)[None],
            per_head(d_skip.astype(f32))[None], xbc.dtype)


def causal_conv(xbc, tail, valid, conv_w, conv_b):
    """The depthwise causal convolution of one padded row that starts
    from a tail.

    ``xbc`` ``[S, ch]``; ``valid`` ``[S]`` bool, true on ONE run of
    positions (front-padded, as a chunk, or back-padded, as a whole
    row); ``tail`` ``[taps - 1, ch]``, the ``xbc`` rows before the
    run's first (zeros for a request's first token).  The tail is laid
    directly before the run, so no padding enters a real position's
    window.  Returns (``silu(conv)`` ``[S, ch]`` float32, unspecified
    at padding; the new tail: the last ``taps - 1`` rows of tail and
    run)."""
    taps = conv_w.shape[0]
    s = xbc.shape[0]
    first = jnp.argmax(valid).astype(jnp.int32)
    count = jnp.sum(valid, dtype=jnp.int32)
    seq = jnp.concatenate(
        [jnp.zeros((taps - 1, xbc.shape[1]), xbc.dtype),
         jnp.where(valid[:, None], xbc, jnp.zeros_like(xbc))])
    seq = jax.lax.dynamic_update_slice_in_dim(
        seq, tail.astype(xbc.dtype), first, axis=0)
    new_tail = jax.lax.dynamic_slice_in_dim(seq, first + count, taps - 1, 0)
    return _conv_taps(seq, conv_w, conv_b, s), new_tail


def ssd_chunk_scan(x, dt, a_log, b, c, d_skip, state_in, valid, *,
                   dt_bias, chunk: int = 256):
    """The chunked (SSD) scan of one row from an initial state.

    ``x`` ``[S, H, P]``, ``dt`` ``[S, H]`` (before bias and softplus),
    ``b``, ``c`` ``[S, N]``, ``a_log``, ``d_skip``, ``dt_bias`` ``[H]``,
    ``state_in`` ``[N, H * P]`` float32 (this module's layout),
    ``valid`` ``[S]`` bool: a padded position has ``delta = 0``.  ``S``
    must be whole blocks of ``chunk`` (the engine's rows are) or at most
    one block.

    Over a block with ``a_t = delta_t A`` and ``L[i, j] = exp(sum_{j < k
    <= i} a_k)`` (``j <= i``, else 0): ``Y = (L * (C B^T)) (delta X) +
    (exp(cumsum a) * C) S_in^T + D X`` and ``S_out = exp(sum a) S_in +
    sum_j exp(sum_{k > j} a_k) delta_j x_j (outer) B_j``.  The decays
    and the state are float32; the matrix products take the operands in
    ``x``'s type and accumulate in float32.

    Returns (``y`` ``[S, H * P]`` float32, ``state_out`` ``[N, H * P]``
    float32)."""
    s, h, p = x.shape
    n = b.shape[-1]
    q = min(chunk, s)
    if s % q:
        raise ValueError(f"a row of {s} positions is not whole blocks "
                         f"of {q}")
    nc = s // q
    f32 = jnp.float32
    op = x.dtype
    dot = functools.partial(jnp.einsum, preferred_element_type=f32)
    with jax.named_scope("ssm_scan"):
        delta = jnp.where(
            valid[:, None],
            jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32)), 0.0)
        a = (-jnp.exp(a_log.astype(f32)) * delta).reshape(nc, q, h)
        acum = jnp.cumsum(a, axis=1)                       # [nc, q, h]
        xf = x.astype(f32)
        dx = (delta[..., None] * xf).reshape(nc, q, h, p)
        bq, cq = b.reshape(nc, q, n), c.reshape(nc, q, n)
        # inside a block: the quadratic form
        tri = jnp.tril(jnp.ones((q, q), bool))
        seg = acum[:, :, None, :] - acum[:, None, :, :]    # [nc, i, j, h]
        decay = jnp.where(tri[None, :, :, None], jnp.exp(
            jnp.where(tri[None, :, :, None], seg, 0.0)), 0.0)
        g = dot("cin,cjn->cij", cq, bq)                    # C B^T
        m = (decay * g[..., None]).astype(op)              # [nc, i, j, h]
        y = dot("cijh,cjhp->cihp", m, dx.astype(op))
        # each block's own contribution to the state at its end
        to_end = jnp.exp(acum[:, -1:, :] - acum)           # [nc, q, h]
        own = dot("cjn,cjhp->cnhp", bq,
                  (to_end[..., None] * dx).astype(op))     # [nc, n, h, p]
        # between blocks: the state, block by block
        total = jnp.exp(acum[:, -1, :])                    # [nc, h]
        state = state_in.astype(f32).reshape(n, h, p)
        starts = []
        for ci in range(nc):
            starts.append(state)
            state = total[ci][None, :, None] * state + own[ci]
        start = jnp.stack(starts)                          # [nc, n, h, p]
        y = y + jnp.exp(acum)[..., None] * dot(
            "cin,cnhp->cihp", cq, start.astype(op))
        y = y + d_skip.astype(f32)[:, None] * xf.reshape(nc, q, h, p)
    return y.reshape(s, h * p), state.reshape(n, h * p)
