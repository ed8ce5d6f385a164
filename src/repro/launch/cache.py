"""JAX's persistent compilation cache for the entry points.

Call :func:`use_compile_cache` at the start of a run, never at import.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing else
is set here.  Otherwise the cache lives at ``<repo>/.jax_cache``: a fixed
path, since the directory is part of what the cache is keyed on.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns it."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
