"""JAX's persistent compilation cache, at one fixed place per checkout.

Entry points call :func:`enable_compile_cache` once at start-up, never at
import time.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already
read it and this sets nothing.  Otherwise the cache goes to
``<repo>/experiments/cache/jax_compile`` (gitignored): the directory is part
of each entry's key, so it must not depend on the process id, the time or the
temp dir, or no later run would ever hit it.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CACHE_ENV", "default_cache_dir", "enable_compile_cache"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def default_cache_dir() -> Path:
    """``<repo>/experiments/cache/jax_compile`` for this checkout."""
    return Path(__file__).resolve().parents[3] / "experiments" / "cache" / "jax_compile"


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache; returns the directory in use."""
    if os.environ.get(CACHE_ENV):
        return os.environ[CACHE_ENV]
    import jax

    path = default_cache_dir()
    jax.config.update("jax_compilation_cache_dir", str(path))
    return str(path)
