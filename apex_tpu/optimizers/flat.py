"""Superblock (flat) optimizer path with a Pallas multi-tensor Adam kernel.

This is the literal TPU analog of the reference's multi-tensor launcher
(csrc/multi_tensor_apply.cuh:41-133 driving csrc/multi_tensor_adam.cu): the
whole parameter set lives in ONE 1-D fp32 HBM buffer (packed by
:mod:`apex_tpu.multi_tensor.flat`), and one Pallas kernel walks it in
(block_rows × 128) VMEM tiles, updating params and both moments in place
(``input_output_aliases`` = the donated-buffer equivalent of the reference's
in-place pointer writes).

Use :class:`FlatFusedAdam` when the model has many small parameters (the
case multi_tensor_apply exists for); for typical large-tensor models the
pytree path in :class:`apex_tpu.optimizers.FusedAdam` compiles to equally
fused XLA and avoids the pack/unpack.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops._pallas import LANE, use_interpret


class FlatAdamState(NamedTuple):
    step: jnp.ndarray
    exp_avg: jnp.ndarray
    exp_avg_sq: jnp.ndarray


def _adam_kernel(scal_ref, p_ref, g_ref, m_ref, v_ref, po_ref, mo_ref, vo_ref,
                 *, beta1, beta2, eps, weight_decay, adam_w_mode):
    """One VMEM tile of the fused Adam update (AdamFunctor parity,
    csrc/multi_tensor_adam.cu:23-97)."""
    lr = scal_ref[0]
    c1 = scal_ref[1]
    c2 = scal_ref[2]
    g = g_ref[:]
    p = p_ref[:]
    if weight_decay and not adam_w_mode:
        g = g + weight_decay * p
    m = beta1 * m_ref[:] + (1.0 - beta1) * g
    v = beta2 * v_ref[:] + (1.0 - beta2) * g * g
    denom = jnp.sqrt(v / c2) + eps
    upd = (m / c1) / denom
    if weight_decay and adam_w_mode:
        upd = upd + weight_decay * p
    po_ref[:] = p - lr * upd
    mo_ref[:] = m
    vo_ref[:] = v


class FlatFusedAdam:
    """FusedAdam over a packed superblock (see module docstring).

    The flat buffer length must be a multiple of 8*128 = 1024 (pack with
    ``flatten(tree, total_multiple_of=1024)``).
    """

    def __init__(self, lr=1e-3, bias_correction=True, betas=(0.9, 0.999),
                 eps=1e-8, adam_w_mode=True, weight_decay=0.0,
                 block_rows: int = 512):
        self.lr = lr
        self.bias_correction = bias_correction
        self.beta1, self.beta2 = betas
        self.eps = eps
        self.adam_w_mode = adam_w_mode
        self.weight_decay = weight_decay
        self.block_rows = block_rows

    def init(self, flat_params: jnp.ndarray) -> FlatAdamState:
        z = jnp.zeros_like(flat_params, jnp.float32)
        return FlatAdamState(step=jnp.zeros((), jnp.int32), exp_avg=z, exp_avg_sq=z)

    def jit_step(self, *, donate: bool = True, plan=None):
        """Jitted :meth:`step` with ``state`` and ``flat_params``
        donated — the entry-level twin of the kernel's
        ``input_output_aliases={1: 0, 3: 1, 4: 2}`` (at flagship scale
        the old params + both moments ARE the fit margin).  The
        ISSUE 13 contract checker registers this executable and
        verifies the aliasing actually survived compilation;
        ``donate=False`` is its negative control.  ``plan`` (a
        :class:`~apex_tpu.multi_tensor.buckets.BucketPlan`, world=1)
        selects the bucketed walk — one kernel launch per bucket, the
        single-device twin of the flagship's per-bucket pipeline,
        registered separately by the checker
        (``zero_flat_adam_update_bucketed``)."""
        step = self.step if plan is None else functools.partial(
            self.step, plan=plan)
        return jax.jit(step, donate_argnums=(1, 2) if donate else ())

    def step(self, flat_grads, state: FlatAdamState, flat_params, *,
             plan=None):
        """One fused Adam step over the superblock.

        ``plan=None`` walks the whole buffer in one ``pallas_call``
        (one grid).  A :class:`~apex_tpu.multi_tensor.buckets.
        BucketPlan` with ``world=1`` walks it bucket by bucket — one
        kernel launch per span, each updating its slice in place
        (``input_output_aliases``) — the launch structure the
        bucketed ZeRO step pipelines collectives between.  Results
        are bitwise identical for every plan: the update is
        elementwise and every span sees the same scalars."""
        assert flat_params.ndim == 1 and flat_params.size % (8 * LANE) == 0, (
            "superblock must be 1-D with length a multiple of 1024; pack with "
            "apex_tpu.multi_tensor.flatten(tree, total_multiple_of=1024)"
        )
        step = state.step + 1
        if self.bias_correction:
            c1 = 1.0 - self.beta1 ** step.astype(jnp.float32)
            c2 = 1.0 - self.beta2 ** step.astype(jnp.float32)
        else:
            c1 = c2 = jnp.asarray(1.0, jnp.float32)
        scal = jnp.stack([jnp.asarray(self.lr, jnp.float32), c1, c2])

        n = flat_params.size
        if plan is None:
            spans = ((0, n),)
        else:
            if plan.world != 1 or plan.shard != n:
                raise ValueError(
                    f"FlatFusedAdam wants a world=1 plan over the whole "
                    f"buffer (shard={n}); got world={plan.world}, "
                    f"shard={plan.shard}")
            # hand-built plans are the documented use case (the
            # registry's FLAT_ADAM_SPANS) — overlapping/gapped spans
            # would silently corrupt the concat reassembly
            plan.validate()
            if any(lo % (8 * LANE) for lo, _ in plan.spans):
                raise ValueError(
                    "FlatFusedAdam bucket spans must start on 8*128 "
                    "sublane-row boundaries; plan with "
                    "plan_buckets(..., span_align=8*128)")
            spans = plan.spans

        p_parts, m_parts, v_parts = [], [], []
        for lo, hi in spans:
            p, m, v = self._span_update(
                scal,
                jax.lax.dynamic_slice_in_dim(flat_params, lo, hi - lo),
                jax.lax.dynamic_slice_in_dim(flat_grads, lo, hi - lo),
                jax.lax.dynamic_slice_in_dim(state.exp_avg, lo, hi - lo),
                jax.lax.dynamic_slice_in_dim(state.exp_avg_sq, lo,
                                             hi - lo))
            p_parts.append(p)
            m_parts.append(m)
            v_parts.append(v)
        if len(spans) == 1:
            p, m, v = p_parts[0], m_parts[0], v_parts[0]
        else:
            p = jnp.concatenate(p_parts)
            m = jnp.concatenate(m_parts)
            v = jnp.concatenate(v_parts)
        return p, FlatAdamState(step=step, exp_avg=m, exp_avg_sq=v)

    def _span_update(self, scal, p_span, g_span, m_span, v_span):
        """One kernel launch over a contiguous lane-aligned span."""
        n = p_span.size
        rows = n // LANE
        block_rows = min(self.block_rows, rows)
        # shrink to a divisor of rows (rows is a multiple of 8)
        while rows % block_rows:
            block_rows //= 2
        grid = rows // block_rows

        kern = functools.partial(
            _adam_kernel,
            beta1=self.beta1, beta2=self.beta2, eps=self.eps,
            weight_decay=self.weight_decay, adam_w_mode=self.adam_w_mode,
        )
        shape2d = (rows, LANE)
        tile = (block_rows, LANE)
        vspec = pl.BlockSpec(tile, lambda i: (i, 0))
        out = pl.pallas_call(
            kern,
            grid=(grid,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                vspec, vspec, vspec, vspec,
            ],
            out_specs=[vspec, vspec, vspec],
            out_shape=[jax.ShapeDtypeStruct(shape2d, jnp.float32)] * 3,
            input_output_aliases={1: 0, 3: 1, 4: 2},
            name="flat_adam_update",
            interpret=use_interpret(),
        )(
            scal,
            p_span.reshape(shape2d).astype(jnp.float32),
            g_span.reshape(shape2d).astype(jnp.float32),
            m_span.reshape(shape2d),
            v_span.reshape(shape2d),
        )
        p, m, v = (x.reshape(-1) for x in out)
        return p, m, v
