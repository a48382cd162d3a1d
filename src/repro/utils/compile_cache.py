"""JAX's persistent compilation cache, kept at one fixed place.

A replica's spawn time is mostly compile time, and every process of a
checkout compiles the same serving steps: a cache on disk turns the second
compile of a step into a load.  The cache key includes the directory, so the
directory must not move between runs.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout root (src/repro/utils/ -> three levels up)
CHECKOUT_ROOT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as is (JAX reads it
    itself; nothing else is set here).  Otherwise the cache is
    ``<checkout>/.jax_cache``.  Call before the first compile; touching the
    config starts no backend."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


__all__ = ["CHECKOUT_ROOT", "enable_compile_cache"]
