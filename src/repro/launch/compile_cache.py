"""JAX's persistent compilation cache, for the program's entry points.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, ``bench/harness.py``)
call :func:`enable_compile_cache` once at start-up; importing the library
never does.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps
its cache there and nothing is set in code.  Otherwise the cache goes to a
fixed ``.jax_cache/`` at the root of the checkout: the directory is part of
the cache key, so a temporary or per-process path would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: ``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/launch/``).
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory: ``$JAX_COMPILATION_CACHE_DIR`` where set, else
    :data:`DEFAULT_DIR`."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
