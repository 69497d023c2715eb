"""JAX persistent compilation cache, set up by the entry points.

Entry points (``chip_smoke.py``, ``examples/train_lm.py``) call
:func:`setup_compile_cache` once at start; importing the library never
touches the cache. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
reads it and no other path is set here. Otherwise the cache lives at the
fixed ``<repo>/.jax_cache`` (gitignored): the directory is part of what a
later process must find again, so it is never a temp, pid or time-based path.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def setup_compile_cache(repo_root: str) -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    cache_dir = os.environ.get(ENV_VAR)
    if not cache_dir:
        cache_dir = os.path.join(os.path.abspath(repo_root), ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir
