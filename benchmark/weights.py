"""Weights from the seed, made on the device in one jitted call, in the
type they are served or trained in.  The layout (paths, shapes, kinds)
is the plain reference's; the same function feeds the program and,
after the window, the reference."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[1], str)


def make(layout: dict, seed: int, dtype, sharding=None):
    """A tree shaped like ``layout`` (``{path: (shape, kind, std)}``)."""
    specs, treedef = jax.tree_util.tree_flatten(layout, is_leaf=is_spec)

    def build(key):
        leaves = []
        for i, (shape, kind, std) in enumerate(specs):
            x = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32) * std
            if kind == "gain":
                x = x + 1.0
            leaves.append(x.astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    # a seed may exceed 31 bits: fold its halves in
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             seed >> 31)
    return jax.jit(build, out_shardings=sharding)(key)
