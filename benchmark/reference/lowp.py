"""The operand hook of the references.

``exact`` leaves a matmul's operands alone (the reference proper).
``fp8`` is the control of a bfloat16 configuration: both operands of
every matmul go through float8_e4m3 with one scale per tensor (the way
an fp8 path is built), and come back to float32.  The step that would
tempt a later PR, and the one the limits have to catch.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

E4M3_MAX = 448.0


def exact(x):
    return x


@jax.custom_vjp
def fp8(x):
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    scale = E4M3_MAX / amax
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


def _fp8_fwd(x):
    return fp8(x), None


def _fp8_bwd(_, g):
    # straight-through, and the cotangent is an operand of the
    # backward's matmuls: it goes through fp8 as well
    return (fp8(g),)


fp8.defvjp(_fp8_fwd, _fp8_bwd)

CASTS = {"exact": exact, "fp8": fp8}
