"""Windowed open-loop driver and the latency arithmetic of a serving cell.

The driver submits each request at its scheduled arrival, whether or not
earlier ones have finished, and steps the server in between. A request
injected late keeps its scheduled arrival, so lateness counts as queue
wait (copied in spirit from ``repro.obs.loadgen.drive``, which has no
window). Arrivals stop when the window closes; the driver then steps on,
with no new arrivals, until every request of the window has finished or
``drain_s`` has passed.

Per step it records what the benchmark needs to count work: the kind of
step, its host-clock span, which requests it admitted, and for every
request in flight how many tokens the step gave it from which context.
Per request, TTFT is counted from the scheduled arrival and TPOT from the
first and the last token (``repro.obs.slo.request_metrics`` arithmetic),
never from ``Server.stats()``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import time
from typing import Callable, Dict, List, Optional, Tuple

FAILURE_REASONS = ("rejected", "shed", "timeout", "cancelled")


def pctl(xs: List[float], p: float) -> float:
    """Nearest-rank percentile (as ``repro.obs.slo._pctl``)."""
    if not xs:
        return math.nan
    xs = sorted(xs)
    idx = min(len(xs) - 1, max(0, math.ceil(p / 100.0 * len(xs)) - 1))
    return xs[idx]


@dataclasses.dataclass
class Step:
    kind: str                   # prefill | decode | idle
    t0: float
    t1: float
    admitted: List[Tuple[int, int]]          # (rid, prompt length)
    decoded: List[Tuple[int, int, int]]      # (rid, context, tokens)


@dataclasses.dataclass
class Window:
    t0: float
    t1: float                   # when arrivals stopped
    t_drained: float
    rids: List[int]             # requests that arrived in the window
    steps: List[Step]
    queue_wait: Dict[int, float]             # rid -> seconds
    late_max_s: float           # how late the generator ran at worst
    counters0: dict
    counters1: dict


def server_counters(server) -> dict:
    return {"prefill_time_s": server.prefill_time_s,
            "decode_time_s": server.decode_time_s,
            "n_prefill_steps": server.n_prefill_steps,
            "n_decode_steps": server.n_decode_steps,
            "tokens": server.tokens_generated}


def _in_flight(server) -> Dict[int, Tuple[int, int]]:
    """rid -> (context length, tokens so far) for every slot in use."""
    out = {}
    for slot in server.scheduler.slots:
        if slot is not None:
            out[slot.req.rid] = (slot.ctx_len, len(slot.req.out_tokens))
    return out


def run_window(server, requests, seconds: float, *,
               drain_s: float = 60.0,
               clock: Callable[[], float] = time.perf_counter,
               sleep: Callable[[float], None] = time.sleep,
               annotate: Optional[Callable[[str], object]] = None,
               on_tick: Optional[Callable[[float], None]] = None) -> Window:
    """Drive ``server`` with ``requests`` (``traffic.Request``, sorted by
    arrival) for ``seconds``, then drain. ``annotate(name)`` returns a
    context manager naming host work in a profiler trace; ``on_tick(now)``
    is called once per loop turn (trace start/stop)."""
    from repro.serving.sampling import SamplingParams

    ann = annotate or (lambda name: contextlib.nullcontext())
    greedy = SamplingParams(temperature=0.0)
    rids: List[int] = []
    waiting = set()             # submitted, not yet admitted
    steps: List[Step] = []
    queue_wait: Dict[int, float] = {}
    late_max = 0.0
    c0 = server_counters(server)
    t0 = clock()
    t_end = t0 + seconds
    i, n = 0, len(requests)
    closed = False
    t_closed = t_end
    while True:
        now = clock()
        if on_tick is not None:
            on_tick(now)
        if not closed and now >= t_end:
            closed, t_closed = True, now
        if closed:
            pending = [r for r in rids if r not in server.finished]
            if not pending or now >= t_closed + drain_s:
                break
        with ann("submit"):
            while (not closed and i < n
                   and t0 + requests[i].arrival_s <= now
                   and t0 + requests[i].arrival_s < t_end):
                r = requests[i]
                due = t0 + r.arrival_s
                late_max = max(late_max, now - due)
                rid = server.submit(r.prompt.tolist(), r.max_new_tokens,
                                    sampling=greedy, arrival=due)
                rids.append(rid)
                waiting.add(rid)
                i += 1
        before = _in_flight(server)
        s0 = clock()
        n_pre = server.n_prefill_steps
        with ann("step"):
            ran = server.step()
        s1 = clock()
        if ran:
            after = {}
            for slot in server.scheduler.slots:
                if slot is not None:
                    after[slot.req.rid] = len(slot.req.out_tokens)
            kind = "prefill" if server.n_prefill_steps > n_pre else "decode"
            admitted, decoded = [], []
            if kind == "prefill":
                for rid in sorted(waiting):
                    req = server.finished.get(rid)
                    if rid in after or (req is not None
                                        and req.ttft is not None):
                        req = req or _slot_request(server, rid)
                        admitted.append((rid, len(req.prompt)))
                        queue_wait[rid] = s0 - req.arrival
                        waiting.discard(rid)
            else:
                for rid, (ctx, n_out) in before.items():
                    got = (after[rid] if rid in after
                           else len(server.finished[rid].out_tokens)) - n_out
                    if got > 0:
                        decoded.append((rid, ctx, got))
            steps.append(Step(kind, s0, s1, admitted, decoded))
        else:
            steps.append(Step("idle", s0, s1, [], []))
            nxt = (t0 + requests[i].arrival_s
                   if not closed and i < n else None)
            due = [x for x in (nxt, None if closed else t_end)
                   if x is not None]
            wake = min(due) if due else clock() + 0.001
            with ann("sleep"):
                sleep(max(0.0, wake - clock()))
    return Window(t0=t0, t1=t_closed, t_drained=clock(), rids=rids,
                  steps=steps, queue_wait=queue_wait, late_max_s=late_max,
                  counters0=c0, counters1=server_counters(server))


def _slot_request(server, rid):
    for slot in server.scheduler.slots:
        if slot is not None and slot.req.rid == rid:
            return slot.req
    raise KeyError(rid)


# ---------------------------------------------------------------------------
# per-request latency
# ---------------------------------------------------------------------------

def request_latency(req) -> Tuple[Optional[float], Optional[float]]:
    """(TTFT, TPOT) seconds of one request; None where not recorded."""
    if req is None or req.ttft is None:
        return None, None
    n = len(req.out_tokens)
    if req.finish_time is None or n < 2:
        return req.ttft, None
    decode = max(0.0, req.finish_time - req.arrival - req.ttft)
    return req.ttft, decode / (n - 1)


def latency_tails(server, win: Window, p: float = 90.0) -> dict:
    """TTFT and TPOT percentiles over every request of the window. A
    value that never came (a failed request, a first token or a last
    token still missing when the drain ended) counts as missing: it
    takes the time waited until the drain ended, a lower bound, so it
    sorts into the tail."""
    ttfts, tpots, failed = [], [], 0
    for rid in win.rids:
        req = server.finished.get(rid)
        if req is None:                       # still running or queued
            req = _slot_or_queued(server, rid)
        bad = req is None or req.finish_reason in FAILURE_REASONS
        waited = max(0.0, win.t_drained - (req.arrival if req else win.t1))
        ttft, tpot = (None, None) if bad else request_latency(req)
        done = not bad and req.finish_time is not None
        failed += not done
        ttfts.append(waited if ttft is None else ttft)
        if not done or len(req.out_tokens) >= 2:      # one token: no gap
            tpots.append(waited if tpot is None else tpot)
    return {"ttft_s": pctl(ttfts, p), "tpot_s": pctl(tpots, p),
            "attempted": len(win.rids), "failed": failed}


def _slot_or_queued(server, rid):
    for slot in server.scheduler.slots:
        if slot is not None and slot.req.rid == rid:
            return slot.req
    for req in server.scheduler.queue:
        if req.rid == rid:
            return req
    return None
