"""Placement of JAX's persistent compilation cache, and a count of the
executables the process builds.

Entry points (``chip_smoke.py``, ``benchmarks.run``, ``repro.launch``)
call :func:`use_compile_cache` once at start-up, never at import time.
The cache's path is part of its key, so it is fixed: the directory named
by ``JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads the variable
itself), else ``.jax_cache`` at the root of the checkout.

:func:`use_compile_cache` also starts the count.  JAX records its
``/jax/core/compile/backend_compile_duration`` event around every
``compile_or_get_cached``, so a fresh compile and a load from the
persistent cache each add one to ``COMPILES[fun_name]``; a hit in JAX's
in-memory caches adds nothing.  While the profiler records, each also
leaves a ``repro.compile`` span, with ``fun_name`` and ``seconds``
metadata, on the host plane of the trace.
"""

from __future__ import annotations

import os
import pathlib
import threading

import jax
import jax.monitoring

#: ``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/runtime/``).
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"

#: the event JAX records around building or loading one executable
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

#: executables built or loaded from the persistent cache, by function name
COMPILES: dict[str, int] = {}

_lock = threading.Lock()
_counting = False


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and the count of
    compilations; returns the cache's directory."""
    _count_compiles()
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def _count_compiles() -> None:
    global _counting
    with _lock:
        if _counting:
            return
        _counting = True
    jax.monitoring.register_event_time_span_listener(_on_compile)


def _on_compile(event: str, start: float, end: float, **meta) -> None:
    if event != COMPILE_EVENT:
        return
    name = str(meta.get("fun_name", ""))
    with _lock:
        COMPILES[name] = COMPILES.get(name, 0) + 1
    if jax.profiler.TraceAnnotation.is_enabled():
        with jax.profiler.TraceAnnotation("repro.compile", fun_name=name,
                                          seconds=end - start):
            pass
