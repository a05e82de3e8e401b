"""JAX's persistent compilation cache for the entry points.

The CLI, the launchers and ``chip_smoke.py`` call :func:`enable` at
start-up (never at import), so a second run of the same program on the
same device reuses the first run's compiled executables.
"""
from __future__ import annotations

import os

import jax

# <checkout>/.jax_cache: a fixed path, because the path is part of the
# cache key — a cache directory that moves never hits
CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory.  If ``JAX_COMPILATION_CACHE_DIR`` is set,
    JAX already reads it and nothing is set in code; otherwise the cache
    goes to ``<checkout>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE)
    return CHECKOUT_CACHE
