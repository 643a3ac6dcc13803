"""Mean seconds per pass in ``repro.core.compress_model`` with the fold
(benchmark span, ending in ``block_until_ready`` on the folded weights)."""


def read(reading):
    s = reading["spans"]["compress"]
    return sum(s) / len(s) if s else None
