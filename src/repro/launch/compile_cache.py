"""JAX's persistent compilation cache for the repository's entry points.

Every entry point (``chip_smoke.py``, ``repro.launch.serve``,
``benchmarks/run.py``) calls ``enable_compile_cache()`` before it compiles
anything, so a second run in the same checkout loads its programs instead
of compiling them again.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: fixed in-checkout cache directory, used when JAX_COMPILATION_CACHE_DIR is
#: not set. The path is part of the cache key, so it never depends on a temp
#: name, a pid or the time.
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    nothing is changed."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
