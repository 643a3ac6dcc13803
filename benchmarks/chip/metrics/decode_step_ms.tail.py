"""Milliseconds per decode step (one token for every running slot) over
the window, from the ``Server`` decode time and step counters; a
multi-step decode window counts as its number of steps."""
from benchmarks.chip import readers


def read(reading):
    n = readers.counter_delta(reading, "n_decode_steps")
    t = readers.counter_delta(reading, "decode_time_s")
    return 1e3 * t / n if n > 0 else None
