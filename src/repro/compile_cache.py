"""JAX's persistent compilation cache, placed from outside.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
helper leaves it alone. Otherwise the cache lives at the fixed
``<repo>/.jax_cache`` (listed in ``.gitignore``): the directory is part of
the cache key, so a path that moved between runs would never hit.
"""

from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
