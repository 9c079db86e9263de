"""Persistent XLA compilation cache, placed from outside or in the checkout.

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()   # before the first jit compiles

When ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and this
sets no directory.  Otherwise the cache goes to ``<checkout>/.jax_cache``
— a fixed path, because the directory is part of what a later process
must find again (a path built from a tempdir, pid or time never hits).

Either way the ops' metadata is part of the cache key: an executable
keeps the metadata it was compiled with, so without it a program whose
named scopes (`inner_dgd`, `dihgp`, `outer_step`, `gossip`) changed
would load an executable whose device trace shows the old ones.  So
that the key still does not depend on where a checkout lies, on which
line an op sits or from where a program is called, the ops' locations
carry their scope path and no source frames.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key",
                      True)
    jax.config.update("jax_traceback_in_locations_limit", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
