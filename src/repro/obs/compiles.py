"""Process-wide compile counter fed by ``jax.monitoring``.

One listener, registered once per process (``install``), receives the
duration events JAX emits while it builds a program:

* ``/jax/core/compile/jaxpr_trace_duration`` — tracing the Python
  function to a jaxpr (paid again on every call that re-traces);
* ``/jax/core/compile/jaxpr_to_mlir_module_duration`` — lowering;
* ``/jax/core/compile/backend_compile_duration`` — the XLA compile, or
  the load from the persistent compilation cache, which runs inside it;
* ``/jax/compilation_cache/cache_retrieval_time_sec`` — that load on its
  own (counted apart, never added to ``seconds`` a second time).

``totals()`` is the running process-wide total: ``count`` programs
compiled or loaded and the ``seconds`` of tracing, lowering and
compiling, on the host clock of the thread that built them.  It is
always on and costs nothing between compiles.  ``watch`` additionally
keeps every record while a caller (``run_cluster``) runs, each with its
``fun_name`` and the caller's progress when it happened, and puts it
on the trace ring as a ``compile.<kind>`` span while tracing is on.
"""
from __future__ import annotations

import threading
import time

from jax import monitoring

from . import trace

KINDS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
CACHE_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"

_lock = threading.Lock()
_count = 0                  # backend compiles (cache loads included)
_seconds = 0.0              # trace + lower + backend seconds
_cache_loads = 0
_cache_s = 0.0
_watches: tuple = ()
_installed = False


def totals() -> dict:
    """The process's running compile totals."""
    with _lock:
        return {"count": _count, "seconds": _seconds,
                "cache_loads": _cache_loads, "cache_s": _cache_s}


class Watch:
    """The compiles of the process while one caller runs: ``records`` is
    ``[(fun_name, seconds, at), ...]`` in arrival order, ``at`` being
    ``progress()`` when the event arrived."""

    def __init__(self, progress=lambda: 0):
        self.progress = progress
        self.records: list = []

    def _record(self, kind: str, fun_name: str, secs: float):
        at = self.progress()
        self.records.append((fun_name, secs, at))
        if trace.enabled:
            trace.complete("compile." + kind, "compile",
                           time.perf_counter() - secs, secs,
                           fun=fun_name, applied_at=at)

    def close(self):
        global _watches
        with _lock:
            _watches = tuple(w for w in _watches if w is not self)


def watch(progress=lambda: 0) -> Watch:
    """Start keeping this process's compile records (``Watch.close``
    stops it)."""
    global _watches
    install()
    w = Watch(progress)
    with _lock:
        _watches = _watches + (w,)
    return w


def _on_duration(event: str, duration: float, **kwargs):
    global _count, _seconds, _cache_loads, _cache_s
    kind = KINDS.get(event)
    if kind is None:
        if event == CACHE_EVENT:
            with _lock:
                _cache_loads += 1
                _cache_s += duration
        return
    with _lock:
        _seconds += duration
        if kind == "backend":
            _count += 1
        watches = _watches
    name = str(kwargs.get("fun_name", ""))
    for w in watches:
        w._record(kind, name, duration)


def install():
    """Register the listener with ``jax.monitoring``, once per process."""
    global _installed
    with _lock:
        if not _installed:
            monitoring.register_event_duration_secs_listener(_on_duration)
            _installed = True
