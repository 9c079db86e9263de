"""Persistent XLA compilation cache, placed from outside or in the checkout.

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()   # before the first jit compiles

When ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and this
sets nothing.  Otherwise the cache goes to ``<checkout>/.jax_cache`` — a
fixed path, because the directory is part of what a later process must
find again (a path built from a tempdir, pid or time never hits).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
