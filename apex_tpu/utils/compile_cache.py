"""Where the persistent XLA compilation cache lives.

The directory is part of the cache key, so it must not move between
runs: never a tempdir, a pid or a timestamp.  Whoever starts the
program places the cache with ``JAX_COMPILATION_CACHE_DIR`` (JAX reads
that variable itself); otherwise it is ``<checkout>/.jax_cache``, which
``.gitignore`` lists.  Entry points call
:func:`configure_compile_cache` once, before the first compilation.
"""

from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def configure_compile_cache() -> str:
    """Returns the cache directory in use.  Sets nothing in code when
    ``JAX_COMPILATION_CACHE_DIR`` is set."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
