"""JAX's persistent compilation cache, placed by the entry points.

Entry points (``chip_smoke.py``, ``repro.launch.serve``,
``repro.launch.train``) call :func:`enable_compile_cache` before their first
compile; importing ``repro`` never does.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Keep compiled executables on disk across processes; return where.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    directory is set here.  Otherwise the cache is the fixed
    ``<checkout>/.jax_cache``, so a later run in the same checkout finds
    what an earlier one compiled.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
