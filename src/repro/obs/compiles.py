"""Count the programs JAX has to obtain: compiled, or loaded from the
persistent compilation cache.

One ``jax.monitoring`` listener per process (installed when ``repro.obs``
is imported) counts two events:

- ``/jax/core/compile/backend_compile_duration``: fires for every
  program JAX obtains, from the compiler or from the persistent cache,
  and carries the program's ``fun_name``;
- ``/jax/compilation_cache/cache_hits``: the cache loads among them.

A jit call served from JAX's in-memory cache fires neither, so a loop
in steady state reads 0. The counts are always kept; with obs enabled
they are mirrored as ``repro_jit_programs_total`` and
``repro_jit_cache_loads_total`` in the default registry, and every
enabled :class:`~repro.obs.trace.Tracer` alive gets an instant event
``compile`` with the ``fun_name``.
"""
from __future__ import annotations

import threading
import weakref
from typing import Tuple

from jax import monitoring

from repro.obs import metrics as obs_metrics

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_hits"

_lock = threading.Lock()
_programs = 0
_cache_loads = 0
_installed = False
_tracers: "weakref.WeakSet" = weakref.WeakSet()


def _on_duration(event: str, duration: float, **kwargs) -> None:
    global _programs
    if event != COMPILE_EVENT:
        return
    with _lock:
        _programs += 1
        tracers = list(_tracers)
    obs_metrics.counter(
        "repro_jit_programs_total",
        "programs JAX compiled or loaded from the persistent cache").inc()
    for t in tracers:
        t.event("compile", fun_name=str(kwargs.get("fun_name", "")))


def _on_event(event: str, **kwargs) -> None:
    global _cache_loads
    if event != CACHE_LOAD_EVENT:
        return
    with _lock:
        _cache_loads += 1
    obs_metrics.counter(
        "repro_jit_cache_loads_total",
        "programs loaded from the persistent compilation cache").inc()


def install() -> None:
    """Register the listeners, once per process."""
    global _installed
    with _lock:
        if _installed:
            return
        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_event)
        _installed = True


def jit_programs() -> Tuple[int, int]:
    """``(programs, cache_loads)`` obtained since the listeners were
    installed. Take the difference of two readings to count a region."""
    with _lock:
        return _programs, _cache_loads


def watch(tracer) -> None:
    """Give ``tracer`` a ``compile`` instant for every program obtained
    while it is alive (held weakly)."""
    with _lock:
        _tracers.add(tracer)
