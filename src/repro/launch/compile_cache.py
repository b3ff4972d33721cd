"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``python -m repro.launch.serve``,
``python -m benchmarks.run``) call :func:`enable` once, before their
first compile.  Library code and tests never turn the cache on.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# One fixed directory inside the checkout (listed in .gitignore): a cache
# at a temp or per-process path would never be found again.
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compile cache on and return its directory:
    ``$JAX_COMPILATION_CACHE_DIR`` where that is set (JAX reads the
    variable itself, so nothing else is set here), else ``CACHE_DIR``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
