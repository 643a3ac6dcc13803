"""Scoped ``jax.profiler`` capture.

The XLA profiler is process-global and heavyweight, so this wrapper
keeps it strictly opt-in (``--prof``). A capture that was asked for and
cannot start raises: a run that silently drops its trace would be read
as a run without one. The spans of an enabled ``Tracer`` are profiler
annotations (``repro.<name>``), so a capture holds them beside the
device's operations, on one clock.
"""
from __future__ import annotations

import contextlib
import os
from typing import Optional

import jax


class JaxProfiler:
    """Start/stop wrapper around ``jax.profiler`` trace capture;
    ``scope()`` captures one region."""

    def __init__(self, out_dir: Optional[str]):
        self.out_dir = out_dir
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
    def scope(self):
        """Profile one region."""
        started = self.start()
        try:
            yield self
        finally:
            if started:
                self.stop()
