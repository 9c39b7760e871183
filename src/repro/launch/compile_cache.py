"""JAX's persistent compilation cache for the repository's entry points.

``chip_smoke.py``, ``benchmarks/run.py`` and the examples call
:func:`enable_compile_cache` once at start-up; nothing calls it at
library import.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
keeps its cache there and this sets no other directory.  Otherwise the
cache lives at a fixed path inside the checkout (``<repo>/.jax_cache``,
listed in ``.gitignore``): the path is part of the cache key, so it
never names a temporary directory, a process id or the time.
"""
from __future__ import annotations

import os
import pathlib

__all__ = ["CACHE_DIR", "enable_compile_cache"]

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
