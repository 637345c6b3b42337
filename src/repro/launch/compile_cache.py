"""JAX's persistent compilation cache, placed for the launchers.

Called by the entry points (serve, train, the benchmark driver and
chip_smoke.py) at start-up, never at import and never from the tests. When
JAX_COMPILATION_CACHE_DIR is set, JAX already caches there and nothing else
is set. Otherwise the cache goes to the fixed directory `.jax_cache` at the
root of the checkout (listed in .gitignore): the path is part of the
cache's key, so it must not depend on a temporary name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    import jax
    path = os.environ.get(ENV) or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
