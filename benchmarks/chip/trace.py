"""Profiler capture and the reduction from a device trace to metrics.

A traced run wraps part of its window in ``jax.profiler`` tracing and
names its own host work with ``TraceAnnotation``s (``chipbench.<name>``),
which land on the same clock as the device's operations. The reduction
reads the ``.xplane.pb`` with ``jax.profiler.ProfileData`` and gives:

- busy seconds: the union of the intervals in which an operation ran on
  a device (plane ``/device:TPU:<n>``, line ``XLA Ops``), inside the
  traced window, averaged over the devices used;
- seconds per operation, by stable name (``%paged_attention.3 = ...`` is
  ``paged_attention``), loops and branches left out (their bodies count);
- the idle gaps between operations, each attributed to the innermost host
  annotation that covers its midpoint (``none`` where no annotation does).
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re
import shutil
from typing import Dict, List, Tuple

PREFIX = "chipbench."
_SUFFIX = re.compile(r"\.\d+$")
# operations that only contain others (a loop, a branch): their time is
# their body's, which the trace lists too
CONTAINERS = ("while", "cond", "conditional", "call")


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class TraceData:
    devices: Dict[str, List[Event]]   # device plane -> its XLA ops
    host: List[Event]                 # chipbench.* annotations


def stable_name(op: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion``."""
    head = op.split(" = ", 1)[0].strip().lstrip("%")
    return _SUFFIX.sub("", head)


def annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(PREFIX + name)


class Capture:
    """Start and stop the profiler around part of a window."""

    def __init__(self, directory: str):
        self.dir = directory
        self.on = False
        self._ann = None

    def start(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        jax.profiler.start_trace(self.dir)
        self._ann = annotate("window")
        self._ann.__enter__()
        self.on = True

    def stop(self):
        import jax
        if not self.on:
            return
        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.on = False

    def load(self) -> TraceData:
        paths = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            raise FileNotFoundError(f"no trace under {self.dir}")
        return load(paths[0])

    def remove(self):
        shutil.rmtree(self.dir, ignore_errors=True)


def load(path: str) -> TraceData:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices[plane.name] = [
                        Event(e.name, e.start_ns, e.duration_ns)
                        for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        host.append(Event(e.name[len(PREFIX):],
                                          e.start_ns, e.duration_ns))
    return TraceData(devices=devices, host=host)


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                     # mean over devices
    op_s: Dict[str, float]            # stable op name -> seconds, mean
    op_count: Dict[str, int]
    gaps_s: Dict[str, float]          # host activity -> idle seconds, mean
    n_devices: int

    @property
    def idle_share(self) -> float:
        return max(0.0, 1.0 - self.busy_s / self.window_s)

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gaps_s.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def _window(td: TraceData) -> Tuple[float, float]:
    wins = [e for e in td.host if e.name == "window"]
    if not wins:
        raise ValueError("trace holds no chipbench.window annotation")
    w = max(wins, key=lambda e: e.dur_ns)
    return w.start_ns, w.end_ns


class _Spans:
    """The benchmark's host annotations (which do not nest, apart from
    the window around them all), searchable by time."""

    def __init__(self, host: List[Event]):
        self.ev = sorted((e for e in host if e.name != "window"),
                         key=lambda e: e.start_ns)
        self.starts = [e.start_ns for e in self.ev]

    def at(self, t: float) -> str:
        i = bisect.bisect_right(self.starts, t) - 1
        # an annotation nested in another ends first: look back a little
        for e in self.ev[max(0, i - 4):i + 1][::-1]:
            if e.start_ns <= t < e.end_ns:
                return e.name
        return "none"


def reduce(td: TraceData) -> Reduction:
    """Busy, per-operation and idle seconds inside the traced window."""
    w0, w1 = _window(td)
    if not td.devices:
        raise ValueError("trace holds no device operations")
    busy, op_s, op_n = 0.0, collections.Counter(), collections.Counter()
    gaps = collections.Counter()
    spans = _Spans(td.host)
    for ops in td.devices.values():
        iv = [(max(e.start_ns, w0), min(e.end_ns, w1)) for e in ops
              if e.end_ns > w0 and e.start_ns < w1]
        u = _union(iv)
        busy += sum(b - a for a, b in u)
        for e in ops:
            name = stable_name(e.name)
            if w0 <= e.start_ns < w1 and name not in CONTAINERS:
                op_s[name] += min(e.end_ns, w1) - e.start_ns
                op_n[name] += 1
        edges = [w0] + [x for ab in u for x in ab] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps[spans.at((a + b) / 2)] += b - a
    n = len(td.devices)
    return Reduction(
        window_s=(w1 - w0) * 1e-9, busy_s=busy / n * 1e-9,
        op_s={k: v / n * 1e-9 for k, v in op_s.items()},
        op_count={k: v // n for k, v in op_n.items()},
        gaps_s={k: v / n * 1e-9 for k, v in gaps.items()}, n_devices=n)
