"""Fused scaled (masked / upper-triangular) softmax.

TPU-native re-design of the Megatron attention-softmax kernels:

* ``scaled_masked_softmax_cuda`` (reference csrc/megatron/scaled_masked_softmax.{cpp,h,cu})
* ``scaled_upper_triang_masked_softmax_cuda`` (csrc/megatron/scaled_upper_triang_*)
* the dispatching wrapper ``FusedScaleMaskSoftmax``
  (reference apex/transformer/functional/fused_softmax.py:21-177).

The reference fuses scale→mask→softmax into one warp-parallel kernel and is
limited to fp16/bf16, 4-D inputs, 16 < key-seq ≤ 2048 (fused_softmax.py:151-171).
Here the fusion is a single ``jax.custom_vjp`` function whose backward is the
fused softmax-grad contract of the CUDA kernel
(``dgrad = (dy - sum(dy*y)) * y * scale``); XLA fuses the elementwise chain
into the surrounding matmuls, and there is no sequence-length restriction.
Softmax math runs in fp32 regardless of input dtype (the kernels' accumulator
behavior), output dtype follows input.

This is an XLA formulation: its value is the backward contract and the
reference-API surface, not a speedup (no cell of the benchmark times it).
"""

from __future__ import annotations

import functools
from enum import Enum
from typing import Callable, Optional

import jax
import jax.numpy as jnp

MASK_FILL = -10000.0  # reference masked_fill value (fused_softmax.py:?? uses -10000.0)


class AttnMaskType(Enum):
    """Mirror of apex.transformer.enums.AttnMaskType (reference enums.py)."""

    padding = 1
    causal = 2


def _apply_masks(x, mask, causal):
    if causal:
        sq, sk = x.shape[-2], x.shape[-1]
        tri = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        x = jnp.where(tri, x, MASK_FILL)
    if mask is not None:
        x = jnp.where(mask, MASK_FILL, x)
    return x


def _softmax_fwd_math(x, mask, scale, causal):
    x = _apply_masks(x.astype(jnp.float32) * scale, mask, causal)
    x = x - jax.lax.stop_gradient(jnp.max(x, axis=-1, keepdims=True))
    ex = jnp.exp(x)
    return ex / jnp.sum(ex, axis=-1, keepdims=True)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _fused_softmax(x, mask, scale, causal):
    return _softmax_fwd_math(x, mask, scale, causal).astype(x.dtype)


def _fused_softmax_fwd(x, mask, scale, causal):
    y = _softmax_fwd_math(x, mask, scale, causal).astype(x.dtype)
    # residual kept in *input* dtype — the reference backward consumes the
    # half-precision softmax_results tensor (scaled_masked_softmax.h bwd);
    # an fp32 copy would double activation memory for the largest tensor.
    return y, (y,)


def _fused_softmax_bwd(scale, causal, res, dy):
    (y,) = res
    y32 = y.astype(jnp.float32)
    g = dy.astype(jnp.float32)
    dx = (g - jnp.sum(g * y32, axis=-1, keepdims=True)) * y32 * scale
    return dx.astype(dy.dtype), None


_fused_softmax.defvjp(_fused_softmax_fwd, _fused_softmax_bwd)


def scaled_masked_softmax(x: jnp.ndarray, mask: Optional[jnp.ndarray],
                          scale: float = 1.0) -> jnp.ndarray:
    """``ScaledMaskedSoftmax`` (reference fused_softmax.py:51-73): 4-D input
    [b, np, sq, sk], boolean ``mask`` broadcastable to it, True = masked out."""
    return _fused_softmax(x, mask, float(scale), False)


def scaled_softmax(x: jnp.ndarray, scale: float = 1.0) -> jnp.ndarray:
    """``ScaledSoftmax`` (no mask) — reference fused_softmax.py: scaled path."""
    return _fused_softmax(x, None, float(scale), False)


def scaled_upper_triang_masked_softmax(x: jnp.ndarray,
                                       scale: float = 1.0) -> jnp.ndarray:
    """``ScaledUpperTriangMaskedSoftmax`` (reference fused_softmax.py:21-48):
    causal mask applied inside the kernel; input [..., sq, sk]."""
    return _fused_softmax(x, None, float(scale), True)


class FusedScaleMaskSoftmax:
    """Dispatching wrapper mirroring ``FusedScaleMaskSoftmax``
    (reference apex/transformer/functional/fused_softmax.py:95-177).

    The reference decides per-call between the fused CUDA kernel and an
    unfused torch path (availability gate :146-171).  On TPU the fused path is
    always available, so the gate reduces to the ``softmax_in_fp32`` /
    ``scale`` consistency checks; ``mask_func`` is kept for API parity with
    generic (non-boolean-where) masking.
    """

    def __init__(
        self,
        input_in_fp16: bool = False,
        input_in_bf16: bool = True,
        attn_mask_type: AttnMaskType = AttnMaskType.padding,
        scaled_masked_softmax_fusion: bool = True,
        mask_func: Optional[Callable] = None,
        softmax_in_fp32: bool = True,
        scale: Optional[float] = None,
    ):
        if input_in_fp16 and input_in_bf16:
            raise ValueError("both fp16 and bf16 flags cannot be active")
        if scale is not None and not softmax_in_fp32:
            # reference fused_softmax.py:128-129
            raise ValueError("softmax should be in fp32 when scaled")
        self.input_in_float16 = input_in_fp16 or input_in_bf16
        self.attn_mask_type = attn_mask_type
        self.fusion = scaled_masked_softmax_fusion
        self.mask_func = mask_func
        self.softmax_in_fp32 = softmax_in_fp32
        self.scale = scale

    def __call__(self, x: jnp.ndarray, mask: Optional[jnp.ndarray]) -> jnp.ndarray:
        scale = self.scale if self.scale is not None else 1.0
        if self.fusion:
            if self.attn_mask_type == AttnMaskType.causal:
                # the reference kernel asserts mask is None here; the fused
                # path supports both masks at once, matching the unfused path
                return _fused_softmax(x, mask, float(scale), True)
            return scaled_masked_softmax(x, mask, scale)
        # unfused parity path (reference forward_torch_softmax :173-186)
        xs = x.astype(jnp.float32) if self.softmax_in_fp32 else x
        xs = xs * scale
        causal = self.attn_mask_type == AttnMaskType.causal
        if self.mask_func is not None and mask is not None:
            xs = self.mask_func(_apply_masks(xs, None, causal), mask)
        else:
            xs = _apply_masks(xs, mask, causal)
        probs = jax.nn.softmax(xs, axis=-1)
        if self.softmax_in_fp32 and self.input_in_float16:
            probs = probs.astype(x.dtype)
        return probs

    @staticmethod
    def is_kernel_available(*_args, **_kw) -> bool:
        """Reference gate (fused_softmax.py:146-171) — always True on TPU."""
        return True
