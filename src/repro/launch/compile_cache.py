"""JAX's persistent compilation cache for the entry points.

The directory is part of the cache key, so it must not move between
runs: ``$JAX_COMPILATION_CACHE_DIR`` when it is set, otherwise the fixed
``<checkout>/.jax_cache`` (listed in ``.gitignore``).  The entry points
(``chip_smoke.py``, ``launch/serve.py``, ``launch/train.py``,
``benchmarks/run.py``) call :func:`enable_compile_cache` before their
first compile; library code and the tests never do.
"""

from __future__ import annotations

import os

import jax

__all__ = ["CACHE_ENV_VAR", "default_cache_dir", "enable_compile_cache"]

CACHE_ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def default_cache_dir() -> str:
    """``<checkout>/.jax_cache``: this file is ``<checkout>/src/repro/
    launch/compile_cache.py``."""
    here = os.path.dirname(os.path.abspath(__file__))
    return os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        here))), ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory."""
    path = os.environ.get(CACHE_ENV_VAR) or default_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
