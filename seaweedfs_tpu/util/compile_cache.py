"""Where JAX keeps its persistent compilation cache.

The path is part of the cache key, so a cache that moves never hits.
`JAX_COMPILATION_CACHE_DIR` wins when the environment sets it; otherwise
the cache lives at a fixed `<checkout>/.jax_cache`.  Entry points call
place_compile_cache() once at start (CLI, chip_smoke.py);
nothing calls it on import.
"""

from __future__ import annotations

import os
import sys

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def place_compile_cache() -> str:
    """Point JAX's persistent cache at its one directory; returns it.

    Sets the environment variable (JAX reads it on import, and child
    processes inherit it) and, when JAX is already imported, its config
    too — importing JAX here would cost every CLI verb ~2 s."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = os.path.join(CHECKOUT, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax = sys.modules.get("jax")
    if jax is not None:
        jax.config.update("jax_compilation_cache_dir", path)
    return path
