"""Mean seconds per pass in ``repro.core.calibrate`` (benchmark span,
ending in the host transfer of the statistics)."""


def read(reading):
    s = reading["spans"]["calibrate"]
    return sum(s) / len(s) if s else None
