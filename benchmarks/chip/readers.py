"""Arithmetic the per-layer readers (``metrics/<name>.py``) share.

A reader gets the ``reading`` dict its cell's mode filled and returns a
number, or None where it finds nothing to read (an untraced run, a slice
without decode steps). It never returns 0 for a share of a roofline or
of a peak that it could not measure.
"""
from __future__ import annotations

from benchmarks.chip import counts, driver


def traced_steps(reading: dict):
    """Steps of a serving window that lie wholly in the traced slice."""
    sl = reading.get("slice")
    if sl is None:
        return None
    ts, te = sl
    return [s for s in reading["window"].steps if s.t0 >= ts and s.t1 <= te]


def idle_pct(reading: dict):
    red = reading.get("trace")
    return None if red is None else 100.0 * red.idle_share


def counter_delta(reading: dict, name: str) -> float:
    w = reading["window"]
    return w.counters1[name] - w.counters0[name]


def useful_flops(reading: dict, steps) -> float:
    """FLOPs of real prompt tokens and of kept generated tokens, each
    attending its own context; padding and frozen rows do not count."""
    cfg = reading["cfg"]
    f = 0
    for s in steps:
        for _, n in s.admitted:
            f += counts.prompt_flops(cfg, n)
        for _, ctx, got in s.decoded:
            f += counts.decode_flops(cfg, ctx, got)
    return float(f)


def paged_attention_work(reading: dict, steps):
    """(flops, bytes) the decode steps' paged attention needed: each kept
    token's query against its live context, in every layer."""
    cfg = reading["cfg"]
    pc = reading["pool"]
    hd = cfg.resolved_head_dim
    r = pc.rank(hd)
    ctxs = [ctx + j + 1 for s in steps for _, ctx, got in s.decoded
            for j in range(got)]
    if not ctxs:
        return None
    fl, nb = counts.paged_attention(ctxs, cfg.n_kv_heads,
                                    cfg.n_heads // cfg.n_kv_heads, r)
    return fl * cfg.n_layers, nb * cfg.n_layers


def queue_wait_pctl(reading: dict, p: float) -> float:
    w = reading["window"]
    waits = [w.queue_wait[r] for r in w.rids if r in w.queue_wait]
    return driver.pctl(waits, p) if waits else None
