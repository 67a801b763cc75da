"""Fused LayerNorm / RMSNorm.

TPU-native re-design of the reference's fused layer-norm stack:

* ``FusedLayerNorm`` / ``MixedFusedLayerNorm``
  (reference apex/normalization/fused_layer_norm.py:15-218) backed by
  ``fused_layer_norm_cuda`` (csrc/layer_norm_cuda_kernel.cu:684 forward,
  :791 backward), and
* the hidden-size-templated contrib ``FastLayerNorm``
  (reference apex/contrib/layer_norm/layer_norm.py:8-77, csrc/layer_norm/).

Design: one ``jax.custom_vjp`` function computes statistics in fp32
(matching the reference's welford accumulation in float), saves
``(mean, invvar)`` for the backward — exactly the residuals the CUDA
kernel returns — and runs a fused backward producing
``(dx, dgamma, dbeta)`` in one pass.  On TPU the forward row-reduction
runs as a Pallas kernel over (rows, hidden) blocks; elsewhere a pure-XLA
path is used (XLA fuses the same ops; the Pallas kernel exists to pin the
layout and avoid HBM round-trips for the stats on large rows).

"Mixed" dtypes (Megatron ``MixedFusedLayerNorm``): the output dtype follows
the *input*, statistics and parameter math stay fp32 — mirroring the
"mixed dtypes" instantiation in csrc/layer_norm_cuda.cpp:260-265.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from apex_tpu.ops._pallas import LANE, use_interpret

try:  # pltpu only resolves on TPU builds; interpret mode needs no memory spaces
    from jax.experimental.pallas import tpu as pltpu  # noqa: F401
except Exception:  # pragma: no cover
    pltpu = None


# ---------------------------------------------------------------------------
# Pallas forward kernel: per-row mean/invvar + normalize, stats in fp32.
# ---------------------------------------------------------------------------


def _ln_fwd_kernel(x_ref, w_ref, b_ref, y_ref, mean_ref, invvar_ref, *, eps, n_cols):
    x = x_ref[...].astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mean
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    invvar = jax.lax.rsqrt(var + eps)
    y = xc * invvar
    if w_ref is not None:
        y = y * w_ref[0].astype(jnp.float32)[None, :]
    if b_ref is not None:
        y = y + b_ref[0].astype(jnp.float32)[None, :]
    y_ref[...] = y.astype(y_ref.dtype)
    # stats keep a trailing singleton lane dim: Mosaic rejects 1-D
    # operands whose tiling disagrees with the XLA layout
    mean_ref[...] = mean
    invvar_ref[...] = invvar


def _ln_block_rows(rows, cols, quota):
    """Row-block size with at most ``quota`` elements per block, rounded
    to the Mosaic 8-row sublane grain (or the full row extent — wide
    cols drove the raw quotient below 8 and failed lowering, r5 fix)."""
    bm = max(8, min(rows, quota // max(cols, LANE)))
    return min(rows, bm // 8 * 8) if rows >= 8 else rows


def _pallas_ln_fwd(x2d, weight, bias, eps):
    rows, cols = x2d.shape
    block_rows = _ln_block_rows(rows, cols, 2048 * LANE)
    grid = (rows + block_rows - 1) // block_rows
    has_w, has_b = weight is not None, bias is not None

    def kernel(*refs):
        i = 0
        x_ref = refs[i]; i += 1
        w_ref = refs[i] if has_w else None; i += has_w
        b_ref = refs[i] if has_b else None; i += has_b
        _ln_fwd_kernel(x_ref, w_ref, b_ref, *refs[i:], eps=eps, n_cols=cols)

    in_specs = [pl.BlockSpec((block_rows, cols), lambda i: (i, 0))]
    args = [x2d]
    if has_w:
        in_specs.append(pl.BlockSpec((1, cols), lambda i: (0, 0)))
        args.append(weight[None, :])
    if has_b:
        in_specs.append(pl.BlockSpec((1, cols), lambda i: (0, 0)))
        args.append(bias[None, :])
    y, mean, invvar = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((block_rows, cols), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, cols), x2d.dtype),
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
        ],
        name="layer_norm_fwd",
        interpret=use_interpret(),
    )(*args)
    return y, mean[:, 0], invvar[:, 0]


def _xla_ln_fwd(x2d, weight, bias, eps):
    x = x2d.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1)
    xc = x - mean[:, None]
    var = jnp.mean(xc * xc, axis=-1)
    invvar = jax.lax.rsqrt(var + eps)
    y = xc * invvar[:, None]
    if weight is not None:
        y = y * weight.astype(jnp.float32)[None, :]
    if bias is not None:
        y = y + bias.astype(jnp.float32)[None, :]
    return y.astype(x2d.dtype), mean, invvar


# ---------------------------------------------------------------------------
# Pallas backward kernel: one pass dx + two-stage dgamma/dbeta
# (the reference backward architecture, csrc/layer_norm_cuda_kernel.cu:791 —
# cuda_layer_norm_gradient's part1/part2 partial reductions).  Stage 1 is a
# Pallas grid over row blocks emitting dx and per-block [1, cols] dgamma/
# dbeta partials; stage 2 sums the [n_blocks, cols] partials (tiny, XLA).
# Added in r5: the XLA one-pass backward measured 0.66x of the HBM roof at
# the bench shape (VERDICT r4 Next #5) — it re-reads x for the reductions;
# this kernel touches x/dy once.
# ---------------------------------------------------------------------------


def _ln_bwd_block_rows(rows, cols):
    """Backward row-block size.  The quota (2^19 elements) is larger
    than the forward's 2048*LANE=2^18 — the backward streams three
    blocks (x/dy/dx) instead of two but measured fastest with the
    bigger rows-per-block at the bench shape, and the cols<=2^15 gate
    in ``_layer_norm_bwd`` bounds the worst case."""
    return _ln_block_rows(rows, cols, 1 << 19)


def _pallas_ln_bwd(x2d, dy, mean, invvar, weight, has_w, has_b):
    rows, cols = x2d.shape
    bm = _ln_bwd_block_rows(rows, cols)
    grid = (rows + bm - 1) // bm

    def kernel(*refs):
        it = iter(refs)
        x_ref, dy_ref, mean_ref, invvar_ref = (
            next(it), next(it), next(it), next(it))
        w_ref = next(it) if has_w else None
        dx_ref = next(it)
        dwp_ref = next(it) if has_w else None
        dbp_ref = next(it) if has_b else None

        i = pl.program_id(0)
        x = x_ref[...].astype(jnp.float32)
        g = dy_ref[...].astype(jnp.float32)
        # ragged last block: Pallas pads reads — rows beyond the array
        # must not contribute to the dgamma/dbeta partial sums
        valid = (i * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)
                 ) < rows
        g = jnp.where(valid, g, 0.0)
        # mask xhat as well: padded rows carry garbage stats, and
        # 0 * inf would poison the dgamma partial with NaN
        xhat = jnp.where(valid, (x - mean_ref[...]) * invvar_ref[...], 0.0)
        gw = (g * w_ref[0].astype(jnp.float32)[None, :]
              if has_w else g)
        c1 = jnp.mean(gw, axis=-1, keepdims=True)
        c2 = jnp.mean(gw * xhat, axis=-1, keepdims=True)
        dx_ref[...] = ((gw - c1 - xhat * c2)
                       * invvar_ref[...]).astype(dx_ref.dtype)
        # dgamma/dbeta accumulate into an [8, cols] VMEM-resident buffer
        # (constant index_map keeps it on-chip across the sequential
        # grid; slot i%8 spreads the serial add chains 8-ways).  This is
        # the reference's part1/part2 two-stage reduction collapsed into
        # one kernel by the TPU grid's sequential execution; the final
        # 8-row sum happens outside.
        @pl.when(i == 0)
        def _():
            if has_w:
                dwp_ref[...] = jnp.zeros_like(dwp_ref)
            if has_b:
                dbp_ref[...] = jnp.zeros_like(dbp_ref)
        slot = i % 8
        if has_w:
            dwp_ref[pl.ds(slot, 1), :] += jnp.sum(g * xhat, axis=0,
                                                  keepdims=True)
        if has_b:
            dbp_ref[pl.ds(slot, 1), :] += jnp.sum(g, axis=0,
                                                  keepdims=True)

    in_specs = [
        pl.BlockSpec((bm, cols), lambda i: (i, 0)),
        pl.BlockSpec((bm, cols), lambda i: (i, 0)),
        pl.BlockSpec((bm, 1), lambda i: (i, 0)),
        pl.BlockSpec((bm, 1), lambda i: (i, 0)),
    ]
    args = [x2d, dy, mean[:, None], invvar[:, None]]
    if has_w:
        in_specs.append(pl.BlockSpec((1, cols), lambda i: (0, 0)))
        args.append(weight[None, :])
    out_specs = [pl.BlockSpec((bm, cols), lambda i: (i, 0))]
    out_shape = [jax.ShapeDtypeStruct((rows, cols), x2d.dtype)]
    for flag in (has_w, has_b):
        if flag:
            out_specs.append(pl.BlockSpec((8, cols), lambda i: (0, 0)))
            out_shape.append(
                jax.ShapeDtypeStruct((8, cols), jnp.float32))
    outs = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        name="layer_norm_bwd",
        interpret=use_interpret(),
    )(*args)
    outs = list(outs) if isinstance(outs, (list, tuple)) else [outs]
    dx = outs.pop(0)
    dw = jnp.sum(outs.pop(0), axis=0) if has_w else None
    db = jnp.sum(outs.pop(0), axis=0) if has_b else None
    return dx, dw, db


# ---------------------------------------------------------------------------
# custom_vjp wrapper
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _layer_norm(x2d, weight, bias, eps, use_pallas):
    y, _, _ = (_pallas_ln_fwd if use_pallas else _xla_ln_fwd)(x2d, weight, bias, eps)
    return y


def _layer_norm_fwd(x2d, weight, bias, eps, use_pallas):
    y, mean, invvar = (_pallas_ln_fwd if use_pallas else _xla_ln_fwd)(
        x2d, weight, bias, eps
    )
    return y, (x2d, weight, bias, mean, invvar)


def _layer_norm_bwd(eps, use_pallas, res, dy):
    # Fused dgrad+dgamma+dbeta, the cuda_layer_norm_gradient contract
    # (csrc/layer_norm_cuda_kernel.cu:791): everything in fp32, one pass.
    x2d, weight, bias, mean, invvar = res
    # width gate: at the bm=8 floor, very wide rows blow the VMEM budget
    # (double-buffered 8xcols blocks + the resident [8, cols] fp32
    # partial buffers) — fall back to the XLA backward there
    if (use_pallas and x2d.shape[1] % LANE == 0
            and x2d.shape[1] <= (1 << 15)):
        dx, dw, db = _pallas_ln_bwd(x2d, dy, mean, invvar, weight,
                                    weight is not None, bias is not None)
        return (dx,
                dw.astype(weight.dtype) if weight is not None else None,
                db.astype(bias.dtype) if bias is not None else None)
    x = x2d.astype(jnp.float32)
    g = dy.astype(jnp.float32)
    xhat = (x - mean[:, None]) * invvar[:, None]
    if weight is not None:
        gw = g * weight.astype(jnp.float32)[None, :]
    else:
        gw = g
    n = x.shape[-1]
    c1 = jnp.mean(gw, axis=-1, keepdims=True)
    c2 = jnp.mean(gw * xhat, axis=-1, keepdims=True)
    dx = (gw - c1 - xhat * c2) * invvar[:, None]
    dx = dx.astype(x2d.dtype)
    dw = jnp.sum(g * xhat, axis=0).astype(weight.dtype) if weight is not None else None
    db = jnp.sum(g, axis=0).astype(bias.dtype) if bias is not None else None
    return dx, dw, db


_layer_norm.defvjp(_layer_norm_fwd, _layer_norm_bwd)


def _normalized_size(normalized_shape) -> Tuple[int, ...]:
    if isinstance(normalized_shape, int):
        return (normalized_shape,)
    return tuple(normalized_shape)


def layer_norm(
    x: jnp.ndarray,
    weight: Optional[jnp.ndarray] = None,
    bias: Optional[jnp.ndarray] = None,
    *,
    eps: float = 1e-5,
    use_pallas: Optional[bool] = None,
) -> jnp.ndarray:
    """Fused layer norm over the trailing dims covered by ``weight``.

    Functional equivalent of ``FusedLayerNormAffineFunction.apply``
    (reference apex/normalization/fused_layer_norm.py:15-40).  Statistics are
    fp32; output dtype follows the input (the MixedFused semantics — for
    strict ``FusedLayerNorm`` parity cast inputs to the param dtype first).
    """
    norm_ndim = weight.ndim if weight is not None else 1
    norm_shape = x.shape[-norm_ndim:]
    cols = int(np.prod(norm_shape))
    rows = int(np.prod(x.shape)) // cols
    x2d = x.reshape(rows, cols)
    w = weight.reshape(cols) if weight is not None else None
    b = bias.reshape(cols) if bias is not None else None
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    y = _layer_norm(x2d, w, b, float(eps), bool(use_pallas))
    return y.reshape(x.shape)


def rms_norm(
    x: jnp.ndarray,
    weight: Optional[jnp.ndarray] = None,
    *,
    eps: float = 1e-5,
) -> jnp.ndarray:
    """Fused RMSNorm companion (no reference analog in the 2021 tree; provided
    for the same call sites modern apex serves with ``FusedRMSNorm``)."""
    norm_ndim = weight.ndim if weight is not None else 1
    cols = int(np.prod(x.shape[-norm_ndim:]))
    x2d = x.reshape(-1, cols).astype(jnp.float32)
    invvar = jax.lax.rsqrt(jnp.mean(x2d * x2d, axis=-1, keepdims=True) + eps)
    y = x2d * invvar
    if weight is not None:
        y = y * weight.reshape(cols).astype(jnp.float32)[None, :]
    return y.astype(x.dtype).reshape(x.shape)


class FusedLayerNorm:
    """Module-style wrapper mirroring ``apex.normalization.FusedLayerNorm``
    (reference fused_layer_norm.py:102-186).

    Holds only static config; parameters live in the pytree returned by
    :meth:`init` and are passed to :meth:`apply` — the functional idiom that
    replaces the reference's stateful ``nn.Module``.
    """

    def __init__(self, normalized_shape, eps: float = 1e-5,
                 elementwise_affine: bool = True):
        self.normalized_shape = _normalized_size(normalized_shape)
        self.eps = eps
        self.elementwise_affine = elementwise_affine

    def init(self, dtype=jnp.float32):
        if not self.elementwise_affine:
            return {}
        return {
            "weight": jnp.ones(self.normalized_shape, dtype),
            "bias": jnp.zeros(self.normalized_shape, dtype),
        }

    def apply(self, params, x):
        return layer_norm(
            x, params.get("weight"), params.get("bias"), eps=self.eps
        )

    __call__ = apply


class MixedFusedLayerNorm(FusedLayerNorm):
    """Megatron variant: stats fp32, output follows input dtype (reference
    fused_layer_norm.py:189-218).  Identical here — mixed is the default."""


# contrib fast_layer_norm (apex/contrib/layer_norm/layer_norm.py:40) is the
# same computation restricted to supported hidden sizes; on TPU one kernel
# covers every size, so FastLayerNorm is an alias.
FastLayerNorm = FusedLayerNorm
fast_layer_norm = layer_norm
