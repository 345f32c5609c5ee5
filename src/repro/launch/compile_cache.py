"""JAX's persistent compilation cache at one fixed place.

The cache key includes the directory, so a path that moves between runs
never hits.  `JAX_COMPILATION_CACHE_DIR`, when set, is the directory
(JAX reads it itself); otherwise the cache lives in the checkout at
`.jax_cache/` (git-ignored).  Entry points call `enable_compile_cache()`
at start-up — never at import time.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    return os.environ.get(ENV) or str(CHECKOUT_CACHE)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at `compile_cache_dir()`
    and return that directory."""
    import jax
    path = compile_cache_dir()
    if not os.environ.get(ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
