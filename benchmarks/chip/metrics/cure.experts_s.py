"""Mean host seconds per pass that CURe spends on expert weights: the
program's ``compress.class`` spans whose ``experts`` attribute is above 0
(a shape class that holds expert items) plus its ``compress.experts``
spans (the expert stacks' assembly and fold). Read from the tracer a
traced run hands ``compress_model``; None where there is none, or where
the program records no such span."""


def read(reading):
    spans = reading.get("program_spans")
    passes = reading.get("passes")
    if not spans or not passes:
        return None
    got = [s["dur"] for s in spans
           if s["name"] == "compress.experts"
           or (s["name"] == "compress.class"
               and s["attrs"].get("experts", 0) > 0)]
    return sum(got) / passes if got else None
