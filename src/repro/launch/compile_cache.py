"""JAX's persistent compilation cache, switched on by the entry points.

Only the ``main`` functions of ``repro.pipeline`` and ``repro.serving`` and
``chip_smoke.py`` call :func:`enable_compile_cache` — never an import, never
a test — so a library caller keeps whatever cache setting it chose.
"""
from __future__ import annotations

import os

# <checkout>/src/repro/launch/compile_cache.py -> <checkout>
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this sets no other directory. Otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache``: the directory is part of what a later
    process must find again, so it never depends on a temp dir, pid or
    time."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
