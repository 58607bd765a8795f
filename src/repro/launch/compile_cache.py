"""One persistent JAX compilation cache that can be placed from outside.

Entry points (``launch.serve``, ``benchmarks.run``, ``chip_smoke.py``)
call :func:`use_compile_cache` before their first compile.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this sets
no other directory; otherwise the cache lives in ``.jax_cache/`` at the
root of the checkout.  The path is part of the cache's key, so it is
fixed: never a temporary name, a pid or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory."""
    path = os.environ.get(ENV)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    # every grouped-solve bucket is its own program and most compile in
    # under JAX's default 1 s threshold, which would cache none of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
