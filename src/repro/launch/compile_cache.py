"""Persistent XLA compilation cache shared by the entry points.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
module changes nothing.  Otherwise the cache goes to the fixed path
``<repo>/.jax_cache``: the directory is part of the cache key, so a path
that moved between runs would never hit.  Only entry points (launchers,
benchmarks, ``chip_smoke.py``) call :func:`enable`; importing a library
module never touches the cache.
"""

from __future__ import annotations

import os
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"
ENV = "JAX_COMPILATION_CACHE_DIR"


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)


__all__ = ["DEFAULT_DIR", "ENV", "enable"]
