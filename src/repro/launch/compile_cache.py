"""JAX's persistent compilation cache, one place for every entry point.

`enable()` runs before an entry point's first JAX call. When
JAX_COMPILATION_CACHE_DIR is set, JAX reads it itself and nothing here
overrides it. Otherwise the cache lives at the fixed `artifacts/jax_cache`
of this checkout (ignored by git): the directory is part of what a later
run must find again, so it is never a temp, pid- or time-derived path.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / "artifacts" / "jax_cache"


def enable() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
