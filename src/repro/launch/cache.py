"""JAX's persistent compilation cache, placed from outside the program.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
nothing here changes it.  Otherwise the cache goes to one fixed
directory inside the checkout (``<repo>/.jax_cache``, git-ignored): the
directory is part of every cache key, so it never carries a temp name,
a pid or a time.  Entry points (``chip_smoke.py``, ``benchmarks/run.py``)
call ``enable_compile_cache`` before their first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
