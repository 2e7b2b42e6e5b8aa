"""Persistent XLA compilation cache for the entry points.

A cold run on the chip compiles every program; JAX's persistent cache
lets a later run, or a second process of the same run, load them back.
The cache key includes its directory, so the directory must not move
between runs: it is either the one the environment names or one fixed
path inside the checkout, never a temporary, per-process or timestamped
name.
"""

from __future__ import annotations

import os
from pathlib import Path

#: Cache directory when ``JAX_COMPILATION_CACHE_DIR`` is unset: fixed,
#: inside the checkout (git-ignored).
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing here overrides it; otherwise the cache goes to
    :data:`DEFAULT_DIR`.  Call it before the first compile."""
    import jax
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
