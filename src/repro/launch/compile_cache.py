"""JAX's persistent compilation cache, kept in one fixed place.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it by itself and this
module sets nothing.  Otherwise the cache lives at ``<repo>/.jax_cache``: a
fixed path, never derived from a temp name, a pid or the time, because a
cache that moves is never found again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.
    Call before the first compile of the process."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
