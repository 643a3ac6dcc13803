"""The windowed open-loop driver and the percentile arithmetic, on a
smoke-sized olmo through the real ``Server``, with an injected clock."""
import types

import numpy as np
import pytest

import chipbench_tiny
from benchmarks.chip import driver, harness, traffic, weights


class FakeClock:
    """Advances a fixed step per reading; sleeping advances it too."""

    def __init__(self, step=1e-3):
        self.t, self.step = 100.0, step

    def __call__(self):
        self.t += self.step
        return self.t

    def sleep(self, s):
        self.t += max(0.0, s)


@pytest.fixture(scope="module")
def smoke():
    from repro.serving import PagedConfig
    cfg_file = dict(chipbench_tiny._load(
        chipbench_tiny.ROOT, "benchmarks/chip/configs/olmo-1b.json"),
        **chipbench_tiny.TINY)
    cfg = harness.model_config(cfg_file)
    return cfg, weights.make(cfg_file, 3), PagedConfig.sized_for(128, 4)


def _server(smoke, clock, monkeypatch):
    from repro.serving import Server, server as server_mod
    monkeypatch.setattr(server_mod, "time",
                        types.SimpleNamespace(perf_counter=clock))
    cfg, params, pc = smoke
    return Server(params, cfg, pc, max_concurrency=4)


def _requests(n, gap, plen=8, out=4):
    rng = np.random.default_rng(0)
    return [traffic.Request(i * gap, rng.integers(0, 256, plen,
                                                  dtype=np.int32), out)
            for i in range(n)]


def test_window_submits_only_what_arrives_in_it(smoke, monkeypatch):
    clock = FakeClock()
    server = _server(smoke, clock, monkeypatch)
    reqs = _requests(20, 0.05)
    win = driver.run_window(server, reqs, 0.5, clock=clock,
                            sleep=clock.sleep)
    arrived = [r for r in reqs if r.arrival_s < 0.5]
    assert len(win.rids) == len(arrived)
    # every request of the window was drained to its end
    assert all(server.finished[r].finish_reason == "length"
               for r in win.rids)
    # stamped with its scheduled arrival, never the submission time
    for rid, r in zip(win.rids, arrived):
        assert server.finished[rid].arrival == pytest.approx(win.t0
                                                             + r.arrival_s)
    kinds = {s.kind for s in win.steps}
    assert {"prefill", "decode"} <= kinds
    admitted = [rid for s in win.steps for rid, _ in s.admitted]
    assert sorted(admitted) == sorted(win.rids)
    assert set(win.queue_wait) == set(win.rids)
    assert all(w >= 0 for w in win.queue_wait.values())


def test_decoded_tokens_add_up(smoke, monkeypatch):
    clock = FakeClock()
    server = _server(smoke, clock, monkeypatch)
    win = driver.run_window(server, _requests(6, 0.02, out=6), 0.2,
                            clock=clock, sleep=clock.sleep)
    for rid in win.rids:
        got = sum(n for s in win.steps for r, _, n in s.decoded if r == rid)
        # the first token comes from prefill, the rest from decode steps
        assert got == len(server.finished[rid].out_tokens) - 1
    ctxs = [(c, n) for s in win.steps for r, c, n in s.decoded
            if r == win.rids[0]]
    # each decode step starts where the previous one left off
    for (c0, n0), (c1, _) in zip(ctxs, ctxs[1:]):
        assert c1 == c0 + n0


def test_tails_count_every_window_request(smoke, monkeypatch):
    clock = FakeClock()
    server = _server(smoke, clock, monkeypatch)
    win = driver.run_window(server, _requests(12, 0.01), 0.1, clock=clock,
                            sleep=clock.sleep)
    tails = driver.latency_tails(server, win, 90.0)
    ttfts = [driver.request_latency(server.finished[r])[0] for r in win.rids]
    tpots = [driver.request_latency(server.finished[r])[1] for r in win.rids]
    assert tails["attempted"] == len(win.rids)
    assert tails["failed"] == 0
    assert tails["ttft_s"] == driver.pctl(ttfts, 90.0)
    assert tails["tpot_s"] == driver.pctl(tpots, 90.0)


def test_request_latency_arithmetic():
    req = types.SimpleNamespace(ttft=0.5, arrival=10.0, finish_time=12.5,
                                out_tokens=[1, 2, 3, 4, 5])
    assert driver.request_latency(req) == (0.5, 2.0 / 4)
    one = types.SimpleNamespace(ttft=0.2, arrival=0.0, finish_time=0.2,
                                out_tokens=[7])
    assert driver.request_latency(one) == (0.2, None)


@pytest.mark.parametrize("xs,p,want", [
    (list(range(1, 11)), 90.0, 9), (list(range(1, 11)), 50.0, 5),
    ([5.0], 90.0, 5.0), ([4, 1, 3, 2], 50.0, 2), (list(range(100)), 90, 89),
])
def test_nearest_rank_percentile(xs, p, want):
    assert driver.pctl(xs, p) == want
