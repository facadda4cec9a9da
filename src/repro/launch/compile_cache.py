"""Where JAX keeps its persistent compile cache, for every entry point.

Each entry point (``chip_smoke.py``, ``repro.launch.serve``,
``benchmarks/run.py``) calls :func:`configure_compile_cache` before its
first compile.  The directory is part of the cache key, so it is a fixed
path: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads
that variable itself, so nothing else is set), and ``.jax_cache/`` at the
root of the checkout otherwise.  The directory is listed in ``.gitignore``.
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["DEFAULT_CACHE_DIR", "configure_compile_cache"]

#: src/repro/launch/compile_cache.py -> the checkout root, three levels up
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Point JAX's persistent compile cache at its directory; return it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
