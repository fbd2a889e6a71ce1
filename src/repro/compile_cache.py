"""Where JAX keeps its persistent compilation cache for this repo's programs.

Only entry points call :func:`configure_compile_cache` (``chip_smoke.py``,
``benchmarks/run.py``, the examples); importing the library never touches
the cache, and neither do the tests.

The cache key includes the directory, so the path is fixed: a directory
built from a temp name, a pid or the time would never hit.
"""
from __future__ import annotations

import os

import jax

#: repository root (this file lives at <repo>/src/repro/compile_cache.py)
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def configure_compile_cache() -> str:
    """Place the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps the cache
    there and nothing is changed. Otherwise the cache goes to
    ``<repo>/.jax_cache``.
    """
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
