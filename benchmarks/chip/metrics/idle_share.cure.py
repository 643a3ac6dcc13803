"""Share of the traced window in which no operation ran on the device."""
from benchmarks.chip import readers


def read(reading):
    return readers.idle_pct(reading)
