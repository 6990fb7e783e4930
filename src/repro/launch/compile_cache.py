"""Where JAX keeps its persistent compilation cache.

The cache key includes the cache's path, so the path must not move between
runs: it is either the directory ``JAX_COMPILATION_CACHE_DIR`` names (which
JAX reads itself) or the fixed ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import os
import pathlib

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    import jax

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
