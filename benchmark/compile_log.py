"""Compile accounting from ``jax.monitoring`` (copied from
``chip_smoke.py § _CompileLog``, PR 21, so that the program may change
and the yardstick may not): backend-compile seconds (compile or cache
load), trace + lower seconds, persistent-cache requests and hits.

``snapshot()`` returns the running totals; the harness takes one at the
end of set-up and one at each end of the window and reports differences.
"""

from __future__ import annotations

import threading

_BACKEND = "/jax/core/compile/backend_compile_duration"
_TRACE = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
)
_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
_HIT = "/jax/compilation_cache/cache_hits"


class CompileLog:
    def __init__(self):
        self._lock = threading.Lock()  # a program may compile on a worker thread
        self.backend_seconds = 0.0
        self.trace_lower_seconds = 0.0
        self.backend_compiles = 0
        self.requests = 0
        self.hits = 0

    def install(self) -> "CompileLog":
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def _on_duration(self, event, duration, **_kw):
        with self._lock:
            if event == _BACKEND:
                self.backend_seconds += float(duration)
                self.backend_compiles += 1
            elif event in _TRACE:
                self.trace_lower_seconds += float(duration)

    def _on_event(self, event, **_kw):
        with self._lock:
            if event == _REQUEST:
                self.requests += 1
            elif event == _HIT:
                self.hits += 1

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "backend_seconds": self.backend_seconds,
                "trace_lower_seconds": self.trace_lower_seconds,
                "backend_compiles": self.backend_compiles,
                "requests": self.requests,
                "hits": self.hits,
            }


def delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before[k] for k in after}
