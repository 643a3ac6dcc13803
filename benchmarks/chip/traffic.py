"""The one traffic generator: reads a mix file (``traffic/<name>.json``)
and a cell's fixed rate, and makes the open-loop request stream of a run.

Every seed gets the same prompt lengths, output lengths and gaps between
arrivals (quantiles of the mix's distributions, in one fixed shuffled
order), and its own token ids. So a seed changes what the requests say,
not when they come or how long they are. Gaps are exponential (Poisson
arrivals); lengths are lognormal by median and sigma, clipped to
``[lo, hi]``.
"""
from __future__ import annotations

import dataclasses
import math
import statistics
from typing import List

import numpy as np


@dataclasses.dataclass
class Request:
    arrival_s: float          # scheduled arrival, from the window's start
    prompt: np.ndarray        # int32 token ids
    max_new_tokens: int


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(dist: dict, n: int) -> np.ndarray:
    """n lengths at the distribution's quantiles, clipped (sorted)."""
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    z = np.array([statistics.NormalDist().inv_cdf(x)
                  for x in _quantiles(n)])
    xs = dist["median"] * np.exp(dist["sigma"] * z)
    return np.clip(np.rint(xs), dist["lo"], dist["hi"]).astype(np.int64)


def gaps(mix: dict, rate: float, n: int) -> np.ndarray:
    """n gaps between arrivals at the quantiles of the arrival process."""
    if mix["arrival"] != "poisson":
        raise ValueError(f"unknown arrival process {mix['arrival']!r}")
    return -np.log1p(-_quantiles(n)) / rate


def count(rate: float, seconds: float) -> int:
    """Requests generated for a window: enough to outlast it."""
    return int(math.ceil(rate * seconds * 1.5)) + 8


def generate(mix: dict, rate: float, seconds: float, seed: int,
             vocab: int) -> List[Request]:
    n = count(rate, seconds)
    order = np.random.default_rng(0)
    g = order.permutation(gaps(mix, rate, n))
    plen = order.permutation(lengths(mix["prompt"], n))
    olen = order.permutation(lengths(mix["output"], n))
    arrivals = np.cumsum(g)
    rng = np.random.default_rng(int(seed))
    return [Request(float(arrivals[i]),
                    rng.integers(0, vocab, int(plen[i]), dtype=np.int32),
                    int(olen[i])) for i in range(n)]


def buckets(mix: dict, lo: int, hi: int) -> List[int]:
    """Power-of-two prefill lengths the mix's prompts can fall into
    (the program pads a prefill to the next power of two, at least
    ``lo`` and at most ``hi``)."""
    def bucket(x):
        b = lo
        while b < x:
            b *= 2
        return min(b, hi)
    out, b = [], bucket(mix["prompt"]["lo"])
    while True:
        out.append(b)
        if b >= bucket(mix["prompt"]["hi"]):
            return out
        b = min(b * 2, hi)
