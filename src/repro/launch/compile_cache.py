"""JAX persistent compilation cache placement for the command-line entry points.

`enable_compile_cache()` is called by the entry points (``chip_smoke.py``,
``python -m repro.launch.serve_gp``, ``python -m benchmarks.run``) before they
compile anything, and never on import.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; no other directory
  is configured here.
* unset: the cache lives at ``<checkout>/.jax_cache``, resolved from this
  file's location (not the working directory). The path is part of every
  cache key, so it must not move between runs: never a temp name, a pid or
  a timestamp.

Either way every compilation is cached (the default skips programs that
compile in under a second — most of a GP serving run's kernels and solves).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: ``<checkout>/.jax_cache`` — this file is ``<checkout>/src/repro/launch/``.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    path = os.environ.get(CACHE_ENV)
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
