"""Persistent XLA compile cache shared by the program's entry points.

Each entry point (``chip_smoke.py``, ``python -m repro.launch.serve``,
``python -m benchmarks.run``) calls :func:`enable_compile_cache` before its
first compile, so a second process finds the executables — Pallas kernels
included — that an earlier one compiled.
"""

from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["CACHE_DIR", "enable_compile_cache"]

# A fixed path inside the checkout (listed in .gitignore): the directory is
# part of the cache key, so it must not move between runs.
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, names the directory and JAX
    reads it itself; otherwise the cache lives in :data:`CACHE_DIR`. The
    minimum compile time is lowered so that sub-second kernel compiles are
    kept too.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
    return path
