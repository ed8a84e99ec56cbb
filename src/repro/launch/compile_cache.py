"""Persistent compilation cache: one fixed place per checkout.

JAX keys a cached executable partly by where the cache lives, so a
directory that moves between runs never hits. Entry points call
`enable_compile_cache()` once, before their first compile; importing a
module never touches the cache.
"""
from __future__ import annotations

import os
import pathlib

import jax

# <checkout>/.jax_cache (listed in .gitignore)
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Returns the cache directory in use.

    Where JAX_COMPILATION_CACHE_DIR is set, JAX already reads it and this
    sets nothing; otherwise the cache goes to CACHE_DIR."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
