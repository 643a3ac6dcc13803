"""Median wait from a request's scheduled arrival to the start of the
prefill step that admitted it (benchmark spans around ``Server.step``),
over the window's admitted requests."""
from benchmarks.chip import readers


def read(reading):
    v = readers.queue_wait_pctl(reading, 50.0)
    return None if v is None else 1e3 * v
