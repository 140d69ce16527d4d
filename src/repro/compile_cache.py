"""JAX's persistent compilation cache, switched on by entry points.

Importing the library leaves the cache alone; a script that compiles
for the device calls :func:`enable_compile_cache` once, before its
first compile. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and nothing is set here. Otherwise the cache lives at one fixed
path inside the checkout (``<repo>/.jax_cache``, git-ignored): the path
is part of the cache's key, so it is never built from a temp name, a
pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
