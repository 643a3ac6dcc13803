"""Roofline share of the ``paged_attention`` kernel in the traced slice:
the least time the chip needs for the decode attention's live K/V bytes
and FLOPs (``counts.paged_attention``; memory-bound at these shapes)
over the kernel's device time in the trace."""
from benchmarks.chip import counts, readers


def read(reading):
    steps = readers.traced_steps(reading)
    red = reading.get("trace")
    if not steps or red is None or not red.op_s.get("paged_attention"):
        return None
    work = readers.paged_attention_work(reading, steps)
    if work is None:
        return None
    share, _ = counts.roofline_share(*work, red.op_s["paged_attention"],
                                     reading["peaks"])
    return share
