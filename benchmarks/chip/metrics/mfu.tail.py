"""Useful model FLOPs of the traced slice (real prompt tokens, kept
generated tokens, each attending its own context) over the slice's
length times the chip's bf16 peak."""
from benchmarks.chip import readers


def read(reading):
    steps = readers.traced_steps(reading)
    if not steps:
        return None
    ts, te = reading["slice"]
    f = readers.useful_flops(reading, steps)
    return 100.0 * f / ((te - ts) * reading["peaks"]["bf16_flops_per_s"])
