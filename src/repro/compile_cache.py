"""Where JAX keeps compiled programs between runs of this repo.

Every entry point (``chip_smoke.py``, ``repro.launch.serve``,
``benchmarks/run.py``) calls :func:`use_compile_cache` from its ``main``.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module places nothing. Otherwise the cache goes to :data:`CACHE_DIR`, a
fixed directory inside the checkout that git ignores: the path is part of
the cache key, so a directory that moved between runs would never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_DIR", "use_compile_cache"]

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on; returns its directory.
    Programs are cached however quickly they compiled, so the small
    Pallas kernels hit on the next run too."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    where = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not where:
        where = str(CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", where)
    return where
