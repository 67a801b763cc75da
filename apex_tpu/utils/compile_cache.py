"""Where the persistent XLA compilation cache lives.

The directory is part of the cache key, so it must not move between
runs: never a tempdir, a pid or a timestamp.  Whoever starts the
program places the cache with ``JAX_COMPILATION_CACHE_DIR`` (JAX reads
that variable itself); otherwise it is ``<checkout>/.jax_cache``, which
``.gitignore`` lists.  Entry points call
:func:`configure_compile_cache` once, before the first compilation.

A key must not move with the call site either.  A Pallas kernel's body
is serialized into its executable's module WITH its MLIR locations, and
a location holds the Python call stack of the trace, ten frames deep by
default: far enough to reach the caller of the jitted function.  The
key of an executable that holds a kernel then moves with any edit to a
caller, and a second lowering of the same function from another place
(``telemetry.scopes.scope_maps`` after a window, where ``warmup()``
lowered the first) misses and recompiles (PERF.md, PR 37).  So a
location keeps :data:`LOCATION_FRAMES` frames: the kernel's own line and
the ``pallas_call`` that holds it, which lie under every jitted
function.  (Turning whole tracebacks off instead renames the kernels'
instructions, which the benchmark finds by name.)
"""

from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: frames of the Python call stack an MLIR location keeps
LOCATION_FRAMES = 2

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def configure_compile_cache() -> str:
    """Returns the cache directory in use.  Sets no directory in code
    when ``JAX_COMPILATION_CACHE_DIR`` is set."""
    jax.config.update("jax_traceback_in_locations_limit", LOCATION_FRAMES)
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
