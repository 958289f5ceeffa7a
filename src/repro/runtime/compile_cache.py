"""Where JAX keeps its persistent compilation cache.

`enable_compile_cache` is called once by every entry point (``chip_smoke.py``,
``benchmarks/run.py``, the examples) before anything compiles. When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no other path
is set here. Otherwise the cache lives at the fixed ``<repo>/.jax_cache``
(gitignored): the directory is part of the cache key, so a path that moved
from run to run would never hit.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; return it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
