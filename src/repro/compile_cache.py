"""Persistent XLA compilation cache for the entry points.

``enable_compile_cache()`` is called by ``chip_smoke.py`` and the
``benchmarks/`` mains, never on import.  Where ``JAX_COMPILATION_CACHE_DIR``
is set, JAX already keeps its cache there and nothing is changed; otherwise
the cache goes to ``.jax_cache/`` at the root of the checkout — a fixed
path, because the path is part of the cache key, so a directory that moved
between runs would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    path = os.environ.get(ENV)
    if path:
        return path
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
