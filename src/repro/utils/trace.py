"""Host spans and a compile counter in the JAX profiler's trace.

Spans are `jax.profiler.TraceAnnotation`s: they land in the host plane
of a `jax.profiler.trace(dir)` capture, on the same clock as the device
planes, and cost about a microsecond when nothing is tracing. Stats are
ints already at hand (no formatting on the hot path). The names the
round driver opens are listed in DESIGN.md §19.

The compile counter listens to JAX's own compile event: one count per
executable built or fetched from the persistent cache
(`backend_compile_duration` fires around both; a cache hit is announced
just before on the same thread, so it sets `cached` on the next count).
Each count leaves a marker span `fedmeta.compile` with stats `n` (the
running count), `ms` (the compile's duration) and `cached` (0 or 1).

    >>> with span("fedmeta.example", round=3):
    ...     pass
    >>> compiles() >= 0
    True
"""
from __future__ import annotations

import threading

import jax

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
COMPILE_SPAN = "fedmeta.compile"


def span(name: str, **stats):
    """A host span named `name` with integer `stats`, as a context
    manager."""
    return jax.profiler.TraceAnnotation(name, **stats)


_lock = threading.Lock()
_count = 0
_hit = threading.local()      # a cache retrieval waiting for its count


def compiles() -> int:
    """Executables compiled (or fetched from the persistent cache) by
    this process since the counter was registered."""
    return _count


def _on_duration(event: str, duration: float, **_kw) -> None:
    global _count
    if event == CACHE_RETRIEVAL_EVENT:
        _hit.cached = 1
    elif event == BACKEND_COMPILE_EVENT:
        cached = getattr(_hit, "cached", 0)
        _hit.cached = 0
        with _lock:
            _count += 1
            n = _count
        with span(COMPILE_SPAN, n=n, ms=round(duration * 1e3),
                  cached=cached):
            pass


_registered = False


def _register() -> None:
    """Register the listener once per process."""
    global _registered
    with _lock:
        if not _registered:
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
            _registered = True


_register()
