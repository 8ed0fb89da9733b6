"""JAX's persistent compilation cache for the CLI entry points and the
chip smoke.

    from repro.launch import cache
    cache.enable_compile_cache()        # before the first compile

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache
there and nothing is set in code.  Otherwise the cache lives at the fixed
path ``<repo root>/.jax_cache`` (gitignored): the directory is part of
every entry's key, so a path that moved between runs would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
