"""JAX's persistent compilation cache, at one fixed place.

A cold process compiles every program again; a 40-layer train step or
serving decode takes tens of seconds to a minute each.  With the cache on,
a later process that builds the same program on the same backend reads
the compiled executable back instead.

Call :func:`enable_compile_cache` from a program's entry point (never at
import): if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it
and nothing is set here; otherwise the cache lives at ``<checkout>/.jax_cache``
(listed in ``.gitignore``).  The path is part of the cache key, so it is
fixed rather than temporary.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
