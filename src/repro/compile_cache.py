"""JAX's persistent compilation cache for the repo's entry points.

Call `enable_compile_cache()` once, before the first compile, from a
program's entry point (`chip_smoke.py`, `benchmarks/run.py`) — never at
import time. Where `JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it
and the helper sets no other directory; otherwise the cache lives at one
fixed, git-ignored path inside the checkout (`<repo>/.jax_cache`): the path
is part of the cache key, so a directory that moved between runs would
never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_DIR", "enable_compile_cache"]

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return the directory it uses."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    # the frontier-expansion kernel compiles in about a second: cache it too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
