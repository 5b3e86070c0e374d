"""One place that turns on JAX's persistent compilation cache.

Entry points (``chip_smoke.py``, the ``benchmarks/*`` mains, the examples)
call ``enable_compile_cache()`` before their first compile; library modules
never do.  A cold DenseNet-121 whole-run program takes minutes to compile,
so a second process that finds it in the cache starts in seconds.

The directory is ``$JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads
the variable itself; no other directory is set here).  Otherwise it is the
fixed ``<checkout>/.jax_cache``, found from this file's own path: the path
is part of what a later process must find again, so it is never built from
a temp name, a pid or the time.  ``JAX_ENABLE_COMPILATION_CACHE=false``
turns the cache off for a run whose compile times are the measurement.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    os.makedirs(path, exist_ok=True)
    return path


__all__ = ["enable_compile_cache", "DEFAULT_DIR", "ENV_VAR"]
