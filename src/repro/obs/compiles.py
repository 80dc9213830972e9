"""Compiles counted inside the program.

JAX reports every lowering, backend compile and persistent-cache hit
through ``jax.monitoring``. One listener per process (JAX's own compile
caches are process-wide, so the counts are too) turns them into:

- a ``jit.compile`` span per backend compile, recorded through
  ``Tracer.complete`` on the current tracer (a no-op unless tracing is on),
  with the compiled function's ``fun_name``: an in-window compile then
  names its own device idle gap;
- counters read by ``compile_counts()``: ``lowerings``, ``compiles``
  (backend compile requests, a persistent-cache hit among them),
  ``cache_hits`` and ``by_name`` (compiles per ``fun_name``). A
  ``DiskJoinIndex`` session shows them as ``metrics_snapshot()["jit"]``.

The listener runs only on compile events, never on a dispatch.
``install()`` registers it once; the verify engines and index sessions
call it, so every compile of the join and serving paths is counted.
"""
from __future__ import annotations

import collections
import threading
import time

from repro.obs.tracer import get_tracer

LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class _Counts:
    def __init__(self):
        self.lock = threading.Lock()
        self.lowerings = 0
        self.compiles = 0
        self.cache_hits = 0
        self.by_name: collections.Counter = collections.Counter()
        self.installed = False

    def on_duration(self, event: str, duration: float, **kw) -> None:
        if event == LOWER:
            with self.lock:
                self.lowerings += 1
        elif event == BACKEND_COMPILE:
            name = str(kw.get("fun_name", "?"))
            with self.lock:
                self.compiles += 1
                self.by_name[name] += 1
            get_tracer().complete("jit.compile",
                                  time.perf_counter() - duration, duration,
                                  fun_name=name)

    def on_event(self, event: str, **kw) -> None:
        if event == CACHE_HIT:
            with self.lock:
                self.cache_hits += 1


_COUNTS = _Counts()


def install() -> None:
    """Register the process's compile listener (once)."""
    with _COUNTS.lock:
        if _COUNTS.installed:
            return
        _COUNTS.installed = True
    import jax
    jax.monitoring.register_event_duration_secs_listener(
        _COUNTS.on_duration)
    jax.monitoring.register_event_listener(_COUNTS.on_event)


def compile_counts() -> dict:
    """The process's compile counters since ``install()`` (which this
    calls): ``lowerings``, ``compiles``, ``cache_hits``, ``by_name``."""
    install()
    with _COUNTS.lock:
        return {"lowerings": _COUNTS.lowerings,
                "compiles": _COUNTS.compiles,
                "cache_hits": _COUNTS.cache_hits,
                "by_name": dict(_COUNTS.by_name)}
