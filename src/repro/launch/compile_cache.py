"""Where JAX keeps its persistent compilation cache.

A full-width step compiles for tens of seconds, so every entry point
keeps its compiled programs across runs.  ``JAX_COMPILATION_CACHE_DIR``,
when set, is left to JAX, which reads it itself.  Otherwise the cache
lives at one fixed directory of the checkout: the path is part of the
cache key, so a directory that moved between runs would never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout's cache directory (git-ignored)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory.  Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
