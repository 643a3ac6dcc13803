"""repro.obs: registry semantics, percentile correctness, cardinality
guard, disabled-mode zero-cost path, Chrome-trace export, Prometheus
exposition, and end-to-end serving instrumentation (spec on and off)
plus the ``launch/serve.py --obs --trace`` smoke."""
import glob
import json
import math
import os

import jax
import numpy as np
import pytest

from repro import obs
from repro.configs import get_smoke
from repro.models import init_params
from repro.obs.metrics import (
    MAX_LABEL_SETS, NULL, Histogram, Registry, log_buckets)
from repro.obs.trace import NULL_CTX, NULL_TRACER, Tracer
from repro.serving import PagedConfig, SamplingParams, Server


@pytest.fixture(scope="module")
def olmo():
    cfg = get_smoke("olmo-1b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------

def test_counter_gauge_semantics():
    reg = Registry()
    c = reg.counter("c", "help")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("g")
    g.set(4)
    g.inc()
    g.dec(2)
    assert g.value == 3.0
    # idempotent getters: same name -> same instrument
    assert reg.counter("c") is c
    # kind mismatch raises
    with pytest.raises(ValueError):
        reg.gauge("c")


def test_histogram_buckets_and_exact_stats():
    reg = Registry()
    h = reg.histogram("h", buckets=(1.0, 2.0, 4.0))
    for v in (0.5, 1.5, 3.0, 100.0):
        h.observe(v)
    assert h.count == 4
    assert h.sum == pytest.approx(105.0)
    assert h.min == 0.5 and h.max == 100.0
    # bucket_counts are per-bucket (cumulative only at exposition)
    assert h.bucket_counts == [1, 1, 1, 1]
    snap = h.snapshot()
    assert snap["type"] == "histogram" and snap["count"] == 4


def test_histogram_percentiles_exact_below_reservoir():
    h = Histogram("p", buckets=log_buckets())
    xs = list(range(1, 101))              # 1..100
    np.random.RandomState(0).shuffle(xs)
    for v in xs:
        h.observe(float(v))
    assert h.percentile(50) == 50.0
    assert h.percentile(90) == 90.0
    assert h.percentile(99) == 99.0
    ps = h.percentiles()
    assert ps == {"p50": 50.0, "p90": 90.0, "p99": 99.0}


def test_histogram_reservoir_stays_bounded():
    h = Histogram("r", buckets=(1.0,), reservoir_size=64)
    for v in range(1000):
        h.observe(float(v))
    assert len(h._reservoir) == 64
    assert h.count == 1000
    # percentiles remain sane estimates from the uniform subsample
    assert 200 < h.percentile(50) < 800


def test_label_cardinality_guard_raises():
    reg = Registry()
    fam = reg.counter("lab", labels=("who",))
    for i in range(MAX_LABEL_SETS):
        fam.labels(who=f"u{i}").inc()
    with pytest.raises(ValueError):
        fam.labels(who="overflow")
    # extra label names also raise
    with pytest.raises(ValueError):
        fam.labels(who="u0", extra="x")


def test_label_overflow_drop_degrades_to_null():
    reg = Registry()
    fam = reg.histogram("shapes", labels=("shape",), overflow="drop")
    for i in range(MAX_LABEL_SETS):
        fam.labels(shape=f"{i}x{i}").observe(1.0)
    assert fam.labels(shape="too-many") is NULL
    fam.labels(shape="too-many").observe(1.0)   # silently dropped


def test_disabled_registry_allocates_nothing():
    reg = Registry(enabled=False)
    # every getter returns THE shared NULL singleton — no instrument,
    # no child, no per-call allocation
    assert reg.counter("x") is NULL
    assert reg.histogram("y") is NULL
    assert reg.counter("x", labels=("a",)).labels(a=1) is NULL
    reg.counter("x").inc()
    reg.histogram("y").observe(0.5)
    assert reg.snapshot() == {}
    assert NULL.value == 0.0


def test_snapshot_shape():
    reg = Registry()
    reg.counter("a").inc(2)
    reg.histogram("b", labels=("k",)).labels(k="v").observe(1.0)
    snap = reg.snapshot()
    assert snap["a"] == {"type": "counter", "value": 2.0}
    assert snap["b"]["type"] == "labeled_histogram"
    assert snap["b"]["children"]["k=v"]["count"] == 1


# ---------------------------------------------------------------------------
# exports
# ---------------------------------------------------------------------------

def test_prometheus_exposition():
    reg = Registry()
    reg.counter("req_total", "requests").inc(3)
    h = reg.histogram("lat_s", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    fam = reg.gauge("occ", labels=("pool",))
    fam.labels(pool="kv").set(7)
    text = obs.to_prometheus(reg)
    assert "# TYPE req_total counter" in text
    assert "req_total 3.0" in text
    # cumulative buckets + +Inf == count
    assert 'lat_s_bucket{le="0.1"} 1' in text
    assert 'lat_s_bucket{le="1"} 2' in text
    assert 'lat_s_bucket{le="+Inf"} 3' in text
    assert "lat_s_count 3" in text
    assert 'occ{pool="kv"} 7.0' in text


def _parse_prometheus(text):
    """Strict text-format parser: every line must be a well-formed
    comment (`# HELP name text` / `# TYPE name type`) or a sample
    (`name{labels} value`), with label values unescaped per the spec.
    Returns (types, helps, samples[(name, labels-dict, value)])."""
    types, helps, samples = {}, {}, []
    valid_types = {"counter", "gauge", "histogram", "summary",
                   "untyped"}
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("#"):
            parts = line.split(" ", 3)
            assert len(parts) >= 4 and parts[1] in ("HELP", "TYPE"), line
            if parts[1] == "TYPE":
                assert parts[3] in valid_types, line
                types[parts[2]] = parts[3]
            else:
                helps[parts[2]] = parts[3]
            continue
        # sample: name[{labels}] value
        m_name, rest = line.split("{", 1) if "{" in line \
            else (line.split(" ", 1)[0], None)
        labels = {}
        if rest is not None:
            body, tail = rest.rsplit("} ", 1)
            i = 0
            while i < len(body):
                eq = body.index("=", i)
                key = body[i:eq]
                assert body[eq + 1] == '"', line
                j, val = eq + 2, []
                while body[j] != '"':
                    if body[j] == "\\":
                        nxt = body[j + 1]
                        val.append({"n": "\n", "\\": "\\",
                                    '"': '"'}[nxt])
                        j += 2
                    else:
                        val.append(body[j])
                        j += 1
                labels[key] = "".join(val)
                i = j + 1
                if i < len(body) and body[i] == ",":
                    i += 1
            value = tail
        else:
            value = line.split(" ", 1)[1]
        float(value)                     # must parse
        samples.append((m_name, labels, float(value)))
    return types, helps, samples


def test_prometheus_strict_roundtrip_with_escaping():
    """Hostile label values and help text survive exposition: a strict
    parser recovers the exact original strings."""
    reg = Registry()
    hostile = 'a"b\\c\nd'
    reg.counter("esc_total", 'help with \\ and\nnewline',
                labels=("path",)).labels(path=hostile).inc(2)
    g = reg.gauge("plain", "plain help")
    g.set(1.5)
    text = obs.to_prometheus(reg)
    types, helps, samples = _parse_prometheus(text)
    assert types["esc_total"] == "counter"
    assert types["plain"] == "gauge"
    # HELP escapes backslash + newline (spec: \\ and \n)
    assert helps["esc_total"] == "help with \\\\ and\\nnewline"
    by_name = {}
    for name, labels, value in samples:
        by_name.setdefault(name, []).append((labels, value))
    assert by_name["esc_total"] == [({"path": hostile}, 2.0)]
    assert by_name["plain"] == [({}, 1.5)]


def test_prometheus_windowed_histogram_type():
    """Windowed histograms expose as plain `histogram` (the window only
    changes the percentile basis, not the cumulative bucket series)."""
    reg = Registry()
    h = reg.histogram("win_s", "windowed", buckets=(0.1, 1.0), window=4)
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    text = obs.to_prometheus(reg)
    types, _helps, samples = _parse_prometheus(text)
    assert types["win_s"] == "histogram"
    buckets = {lbl["le"]: v for n, lbl, v in samples
               if n == "win_s_bucket"}
    assert buckets == {"0.1": 1.0, "1": 2.0, "+Inf": 3.0}
    assert ("win_s_count", {}, 3.0) in samples
    # labeled windowed family maps the same way
    fam = reg.histogram("win_fam_s", labels=("k",), window=4)
    fam.labels(k="a").observe(1.0)
    types, _h, _s = _parse_prometheus(obs.to_prometheus(reg))
    assert types["win_fam_s"] == "histogram"


def test_jsonl_log_roundtrip(tmp_path):
    p = str(tmp_path / "events.jsonl")
    log = obs.JsonlLog(p)
    log.log("request", rid=1, tokens=4)
    log.log("stats", tok_s=12.5)
    log.close()
    lines = [json.loads(x) for x in open(p).read().splitlines()]
    assert [e["kind"] for e in lines] == ["request", "stats"]
    assert lines[0]["rid"] == 1 and "ts" in lines[0]


def test_write_all_artifact_set(tmp_path):
    reg = Registry()
    reg.counter("a").inc()
    tr = Tracer()
    with tr.span("stage"):
        pass
    written = obs.write_all(str(tmp_path), registry=reg, tracer=tr)
    assert set(written) == {"metrics", "prometheus", "trace"}
    assert json.load(open(written["metrics"]))["a"]["value"] == 1.0
    assert json.load(open(written["trace"]))["traceEvents"]


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

def test_chrome_trace_roundtrip_and_nesting():
    tr = Tracer(process="test")
    tr.name_track(1, "req 0")
    with tr.span("outer", track=1):
        with tr.span("inner", track=1) as s:
            s.set(k=3)
        tr.event("tick", track=1, n=1)
    doc = json.loads(json.dumps(tr.to_chrome_trace()))
    evs = doc["traceEvents"]
    X = {e["name"]: e for e in evs if e["ph"] == "X"}
    assert set(X) == {"outer", "inner"}
    # well-nested: inner lies within [outer.ts, outer.ts + outer.dur]
    o, i = X["outer"], X["inner"]
    assert o["ts"] <= i["ts"]
    assert i["ts"] + i["dur"] <= o["ts"] + o["dur"] + 1e-3
    assert i["args"] == {"k": 3}
    names = {e["args"]["name"] for e in evs if e["ph"] == "M"
             and e["name"] == "thread_name"}
    assert {"engine", "req 0"} <= names
    assert any(e["ph"] == "i" and e["name"] == "tick" for e in evs)


def test_tracer_durations_and_decorator():
    tr = Tracer()

    @tr.wrap("work")
    def work():
        return 42

    assert work() == 42 and work() == 42
    d = tr.durations()
    assert set(d) == {"work"} and d["work"] >= 0.0


def test_disabled_tracer_is_null():
    tr = Tracer(enabled=False)
    assert tr.span("x") is NULL_CTX
    tr.add_span("x", 0.0, 1.0)
    tr.event("y")
    assert tr.spans == [] and tr.events == []
    assert NULL_TRACER.span("z") is NULL_CTX


def test_disabled_tracer_makes_no_profiler_call(monkeypatch):
    from repro.obs import trace as trace_mod
    calls = []

    def annotation(name):
        calls.append(name)
        return NULL_CTX
    monkeypatch.setattr(trace_mod, "TraceAnnotation", annotation)
    for tr in (Tracer(enabled=False), NULL_TRACER):
        with tr.span("x", k=1) as s:
            s.set(m=2)
        assert tr.spans == []
    assert calls == []
    with Tracer().span("y"):
        pass
    assert calls == ["repro.y"]


def _profiled_events(logdir):
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events
            if e.name.startswith("repro.")]


def test_cure_spans_nest_in_the_profiler_capture(tmp_path, tiny_cfg,
                                                 tiny_params):
    """calibrate and compress_model under jax.profiler: every span is a
    repro.* annotation on the capture's host plane, a dotted name lies
    inside an annotation of its parent name, and the .wait spans (host
    blocked on the device) are there."""
    from conftest import make_batch
    from repro.configs.base import CURConfig
    from repro.core import calibrate, compress_model
    tr = Tracer()
    ccfg = CURConfig(r_max=8, n_compress_layers=2, fold_u=True)
    with jax.profiler.trace(str(tmp_path)):
        with tr.span("calibrate"):
            calib = calibrate(tiny_params, tiny_cfg,
                              [make_batch(tiny_cfg, 2, 16)] * 2, tracer=tr)
        with tr.span("compress"):
            compress_model(tiny_params, tiny_cfg, ccfg, calib, tracer=tr)
    events = _profiled_events(str(tmp_path))
    names = [n[len("repro."):] for n, _, _ in events]
    assert sorted(names) == sorted(s["name"] for s in tr.spans)
    for want in ("calibrate.batch", "calibrate.wait", "compress.distances",
                 "compress.unroll", "compress.class", "compress.class.stack",
                 "compress.class.wait", "compress.fold",
                 "compress.fold.wait"):
        assert want in names
    for name, t0, t1 in events:
        parent = "repro." + name[len("repro."):].rsplit(".", 1)[0]
        if parent == name:
            continue
        assert any(p == parent and p0 <= t0 and t1 <= p1
                   for p, p0, p1 in events), name
    for s in tr.spans:
        if s["name"] == "compress.class":
            assert {"m", "n", "r", "k", "programs"} <= set(s["attrs"])


def test_jit_programs_counts_each_program_once():
    x = jax.numpy.arange(3.0)
    tr = Tracer()
    reg = obs.default_registry()
    was = reg.enabled
    reg.enable()
    try:
        before = obs.jit_programs()
        c0 = reg.counter("repro_jit_programs_total").value

        @jax.jit
        def triple_plus_one(v):
            return v * 3.0 + 1.0
        triple_plus_one(x).block_until_ready()
        mid = obs.jit_programs()
        triple_plus_one(x).block_until_ready()
        after = obs.jit_programs()
        c1 = reg.counter("repro_jit_programs_total").value
    finally:
        if not was:
            reg.disable()
    assert mid[0] - before[0] == 1
    assert after == mid
    assert c1 - c0 == 1
    assert [e["attrs"]["fun_name"] for e in tr.events
            if e["name"] == "compile"] == ["jit(triple_plus_one)"]
    y = x + 1.0
    before = obs.jit_programs()
    obs.compiles.install()                      # idempotent
    triple_plus_one(y).block_until_ready()      # same shape: no program
    assert obs.jit_programs() == before


# ---------------------------------------------------------------------------
# end-to-end serving instrumentation
# ---------------------------------------------------------------------------

def _drive(params, cfg, *, spec: bool, tracer=None):
    pc = PagedConfig.sized_for(40, 4)
    srv = Server(params, cfg, pc, max_concurrency=4,
                 draft_params=params if spec else None,
                 spec_k=2 if spec else 0, tracer=tracer)
    rng = np.random.RandomState(0)
    for i in range(5):
        srv.submit(rng.randint(0, cfg.vocab_size, size=7).tolist(),
                   max_new_tokens=6,
                   sampling=SamplingParams(temperature=0.0, seed=i))
    srv.drain()
    return srv


@pytest.mark.parametrize("spec", [False, True])
def test_server_histograms_populate(olmo, spec):
    cfg, params = olmo
    srv = _drive(params, cfg, spec=spec)
    snap = srv.obs.snapshot()
    assert snap["repro_serving_ttft_s"]["count"] == 5
    assert snap["repro_serving_tpot_s"]["count"] > 0
    assert snap["repro_serving_tokens_generated_total"]["value"] == 30
    assert snap["repro_serving_requests_completed_total"]["value"] == 5
    # pool gauges: occupancy returns to zero after drain, but traffic
    # counters prove the allocator recorded
    assert snap["repro_serving_pool_blocks_used"]["value"] == 0
    assert snap["repro_serving_pool_alloc_total"]["value"] > 0
    assert snap["repro_serving_pool_free_total"]["value"] > 0
    if spec:
        assert snap["repro_serving_spec_windows_total"]["value"] > 0
        assert snap["repro_serving_spec_accept_rate"]["count"] > 0
        assert snap["repro_serving_pool_fork_total"]["value"] > 0
    st = srv.stats()
    for k in ("ttft_p50_s", "ttft_p99_s", "tpot_p50_s", "tpot_p99_s",
              "tokens_per_s_busy", "busy_time_s", "pool_blocks_used",
              "jit_cache"):
        assert k in st
    assert st["tokens_generated"] == 30 and st["completed"] == 5
    assert 0.0 < st["ttft_p50_s"] <= st["ttft_max_s"]
    assert st["busy_time_s"] <= max(st["elapsed_s"], st["busy_time_s"])
    assert st["tokens_per_s_busy"] >= st["tokens_per_s"] * 0.99


def test_server_request_lifecycle_spans(olmo):
    cfg, params = olmo
    tr = Tracer(process="test-serve")
    srv = _drive(params, cfg, spec=False, tracer=tr)
    del srv
    names = {s["name"] for s in tr.spans}
    assert {"queued", "request", "prefill", "decode_window"} <= names
    # every request lane got its whole-lifetime span
    reqs = [s for s in tr.spans if s["name"] == "request"]
    assert len(reqs) == 5
    assert all(s["track"] >= 1 and s["dur"] > 0 for s in reqs)
    # export parses
    doc = json.loads(json.dumps(tr.to_chrome_trace()))
    assert len(doc["traceEvents"]) > 10


def test_serve_cli_obs_smoke(tmp_path):
    """launch/serve.py --obs --trace writes a non-empty, parseable
    Chrome trace + metrics artifacts (the CI tier-1 smoke)."""
    from repro.launch.serve import main as serve_main
    out = str(tmp_path / "obs")
    stats, _ = serve_main([
        "--arch", "olmo-1b", "--smoke", "--n-requests", "4",
        "--new-tokens", "4", "--max-concurrency", "2",
        "--obs", "--trace", "--obs-out", out])
    try:
        assert stats["completed"] == 4
        assert stats["ttft_p99_s"] >= stats["ttft_p50_s"] > 0.0
        trace = json.load(open(os.path.join(out, "trace.json")))
        assert len(trace["traceEvents"]) > 0
        assert any(e.get("ph") == "X" for e in trace["traceEvents"])
        metrics = json.load(open(os.path.join(out, "metrics.json")))
        assert metrics["repro_serving_ttft_s"]["count"] == 4
        events = [json.loads(x) for x in
                  open(os.path.join(out, "events.jsonl"))]
        assert [e["kind"] for e in events].count("request") == 4
        assert events[-1]["kind"] == "stats"
        assert os.path.exists(os.path.join(out, "metrics.prom"))
    finally:
        # --obs flips the process-wide default registry on; leave the
        # suite the way we found it
        obs.default_registry().reset()
        obs.disable()


def test_stats_shape_backward_compatible(olmo):
    cfg, params = olmo
    srv = _drive(params, cfg, spec=False)
    st = srv.stats()
    legacy = {"completed", "tokens_generated", "elapsed_s",
              "tokens_per_s", "ttft_mean_s", "ttft_max_s",
              "queue_depth_mean", "queue_depth_max", "n_prefill_steps",
              "n_decode_steps", "n_preemptions", "cache_bytes",
              "prefill_time_s", "decode_time_s", "decode_tok_s",
              "gathered_bytes_per_step", "spec_k", "n_spec_windows",
              "n_spec_fallbacks", "spec_accept_rate",
              "spec_draft_time_s", "spec_verify_time_s"}
    assert legacy <= set(st)
    # legacy attribute views still read correctly
    assert srv.tokens_generated == st["tokens_generated"]
    assert srv.n_decode_steps == st["n_decode_steps"]
    assert math.isclose(srv.decode_time_s, st["decode_time_s"])
