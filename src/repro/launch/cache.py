"""JAX's persistent compilation cache for the launchers and ``chip_smoke.py``.

The cache key includes the directory, so it lives at one fixed path: a
directory that moves between runs never hits.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: ``<repo>/.jax_cache`` (listed in ``.gitignore``).
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here. Otherwise the cache goes to ``<repo>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
