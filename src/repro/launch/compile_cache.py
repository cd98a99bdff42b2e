"""JAX's persistent compilation cache, placed for the entry points.

The engines compile once per bucket shape, so a cold process pays a compile
for every (plan key, bucket) it meets.  The entry points (``launch/serve.py``,
``launch/serve_lm.py``, ``launch/train.py``, ``benchmarks/run.py`` and
``chip_smoke.py``) call :func:`enable_compile_cache` once at start-up so a
second run finds those compiles again.  Tests do not: they keep JAX's own
default.

Where the cache lives is decided outside the program: when
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing here
overrides it.  Otherwise the cache goes to the fixed path
``<checkout>/.jax_cache`` (listed in ``.gitignore``) — never a path built
from a temporary name, a pid or a time, because the directory is part of
the cache's key and one that moves never hits.
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["CACHE_ENV", "DEFAULT_CACHE_DIR", "enable_compile_cache"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# src/repro/launch/compile_cache.py -> the checkout root
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on for this process; returns its directory.

    Every compile is cached, however short: the bucket lattice is many
    small compiles, each below JAX's default one-second floor.
    """
    if not os.environ.get(CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax.config.jax_compilation_cache_dir
