"""JAX's persistent compilation cache, placed for the repo's entry points.

Only entry points call :func:`enable_compile_cache` (the CLI, the benchmark
runner and ``chip_smoke.py``); importing a library module never changes
JAX's configuration.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["DEFAULT_CACHE_DIR", "enable_compile_cache"]

#: where the cache lives when ``JAX_COMPILATION_CACHE_DIR`` is unset. The
#: directory is part of the cache key, so it is one fixed path at the repo
#: root, never a temporary or per-process one.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is left for JAX to read; else
    the cache goes to :data:`DEFAULT_CACHE_DIR`. Every program is cached,
    however fast it compiled: the stream engine launches many small kernel
    shapes (padded batch x neighbour width), each under JAX's default
    one-second threshold.
    """
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir
