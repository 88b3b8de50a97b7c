"""JAX's persistent compilation cache, kept at one fixed directory.

A process that calls ``enable_compile_cache`` before its first compile
reads back every program an earlier run on the same checkout compiled:

* if ``JAX_COMPILATION_CACHE_DIR`` is set, the cache lives there and no
  other directory is set;
* otherwise it lives at ``<repo>/.jax_cache``.  The directory is part of
  what a later run must find again, so it never depends on a temp dir, a
  pid or the clock.

The minimum compile time drops to zero, so the many small programs of
this system (one per kernel shape, super-step and publish) are cached too.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["DEFAULT_CACHE_DIR", "enable_compile_cache"]

DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> Path:
    """Turn on the persistent compilation cache; returns its directory."""
    import jax

    path = Path(os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
