"""Placement of JAX's persistent compilation cache.

Entry points (``chip_smoke.py``, ``benchmarks.run``, ``repro.launch``)
call :func:`use_compile_cache` once at start-up, never at import time.
The cache's path is part of its key, so it is fixed: the directory named
by ``JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads the variable
itself), else ``.jax_cache`` at the root of the checkout.
"""

from __future__ import annotations

import os
import pathlib

import jax

#: ``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/runtime/``).
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
