"""Prefill's share of the server's step time over the window, from the
``Server`` prefill and decode time counters."""
from benchmarks.chip import readers


def read(reading):
    pre = readers.counter_delta(reading, "prefill_time_s")
    dec = readers.counter_delta(reading, "decode_time_s")
    return 100.0 * pre / (pre + dec) if pre + dec > 0 else None
