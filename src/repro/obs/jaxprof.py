"""Scoped ``jax.profiler`` capture + device memory snapshots.

The XLA profiler is process-global and heavyweight, so this wrapper
keeps it strictly opt-in (``--prof``). A capture that was asked for and
cannot start raises: a run that silently drops its trace would be read
as a run without one. Captures are keyed to obs spans by emitting a
matching instant event on the tracer, so the Perfetto timeline and the
XLA trace directory line up by name.
"""
from __future__ import annotations

import contextlib
import os
from typing import Optional

import jax

from repro.obs.trace import Tracer


def device_memory_snapshot() -> dict:
    """Per-device memory stats (empty dict where the backend doesn't
    report any, e.g. CPU)."""
    out = {}
    for d in jax.local_devices():
        stats = d.memory_stats()
        if stats:
            out[str(d)] = {k: int(v) for k, v in stats.items()
                           if isinstance(v, (int, float))}
    return out


class JaxProfiler:
    """Start/stop wrapper around ``jax.profiler`` trace capture.

    ``scope(name)`` is the span-keyed form: it emits ``prof:<name>``
    instants on the tracer and snapshots device memory on entry/exit
    (attached to the event args), so a Perfetto view of the obs trace
    points at the matching XLA capture under ``out_dir``.
    """

    def __init__(self, out_dir: Optional[str],
                 tracer: Optional[Tracer] = None):
        self.out_dir = out_dir
        self.tracer = tracer
        self.active = False

    def start(self) -> bool:
        if self.out_dir is None or self.active:
            return False
        os.makedirs(self.out_dir, exist_ok=True)
        jax.profiler.start_trace(self.out_dir)
        self.active = True
        return True

    def stop(self) -> None:
        if not self.active:
            return
        try:
            jax.profiler.stop_trace()
        finally:
            self.active = False

    @contextlib.contextmanager
    def scope(self, name: str):
        """Profile one region, keyed to the obs trace by name."""
        started = self.start()
        if self.tracer is not None:
            self.tracer.event(f"prof:{name}", phase="start",
                              mem=device_memory_snapshot())
        try:
            yield self
        finally:
            if self.tracer is not None:
                self.tracer.event(f"prof:{name}", phase="stop",
                                  mem=device_memory_snapshot())
            if started:
                self.stop()
