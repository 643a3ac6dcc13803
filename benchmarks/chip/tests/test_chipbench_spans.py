"""``cure_spans.py``: the program's ``repro.*`` spans and ``cure_*``
scopes reduced beside the device's operations, on synthesized traces and
on a tiny CURe captured on the CPU; and the benchmark's own trace loader,
which keeps only its ``chipbench.*`` annotations when the program's spans
lie inside them."""
import glob
import os

import pytest

import chipbench_tiny
from benchmarks.chip import cure_spans as cs
from benchmarks.chip import trace as tr

MS = 1_000_000  # ns


def _ev(name, t0, t1):
    return tr.Event(name, t0 * MS, (t1 - t0) * MS)


def test_innermost_at_any_depth():
    spans = [_ev("a", 0, 100)] + [_ev("a" + ".b" * d, d, 60 - d)
                                  for d in range(1, 7)]
    spans.append(_ev("c", 70, 80))
    got = cs.innermost(spans, [x * MS for x in (0.5, 3.5, 6.5, 30, 55.5,
                                                 65, 75, 90, 120)])
    assert got == ["a", "a.b.b.b", "a.b.b.b.b.b.b", "a.b.b.b.b.b.b",
                   "a.b.b.b.b", "a", "c", "a", None]


def _synth():
    P = "repro."
    host = [_ev("chipbench.window", 0, 100),
            _ev("chipbench.calibrate", 0, 30),
            _ev("chipbench.compress", 30, 100),
            _ev(P + "calibrate.batch", 1, 12),
            _ev(P + "calibrate.wait", 20, 30),
            _ev(P + "compress.class", 32, 96),
            _ev(P + "compress.class.wait", 60, 95)]
    # six levels deep inside compress.class, as host work
    host += [_ev(P + "compress.class" + ".x" * d, 32 + d, 50 - d)
             for d in range(1, 7)]
    dev = [(_ev("%fusion.1 = f32[] fusion()", 2, 20), ""),         # calib
           (_ev("%custom-call.4 = f32[] custom-call()", 25, 30), ""),
           (_ev("%fusion.9 = f32[] fusion()", 40, 44), "cure_svd"),
           (_ev("%while.2 = f32[] while()", 50, 80), ""),           # body:
           (_ev("%solve.3 = f32[] custom-call()", 50, 60), "cure_deim"),
           (_ev("%fusion.7 = f32[] fusion()", 62, 70), "cure_link"),
           (_ev("%copy.1 = f32[] copy()", 70, 72), ""),
           (_ev("%fusion.8 = f32[] fusion()", 75, 80), "cure_check")]
    return {"/device:TPU:0": dev}, host


def test_reduce_spans_by_scope_and_by_wait():
    devices, host = _synth()
    red = cs.reduce_spans(devices, host, passes=1)
    # busy: [2,20] [25,30] [40,44] [50,80]: 18 + 5 + 4 + 30 ms
    assert red["busy_s"] == pytest.approx(0.057)
    assert red["idle_s"] == pytest.approx(0.043)
    assert red["idle_share"] == pytest.approx(43.0)
    # gaps: [0,2] in calibrate.batch -> host; [20,25] calibrate.wait;
    # [30,40] at 35 three deep in compress.class.x.. -> host; [44,50] at
    # 47 two deep -> host; [80,100] at 90 compress.class.wait
    split = red["idle_split_s"]
    assert split["wait"] == pytest.approx(0.025)
    assert split["host"] == pytest.approx(0.018)
    assert split["wait"] + split["host"] == pytest.approx(red["idle_s"])
    assert red["idle_by_span_s"] == pytest.approx({
        "repro.compress.class.wait": 0.020,
        "repro.compress.class.x.x.x": 0.010,
        "repro.compress.class.x.x": 0.006,
        "repro.calibrate.wait": 0.005, "repro.calibrate.batch": 0.002})
    assert red["scope_s"] == pytest.approx(
        {"cure_svd": 0.004, "cure_deim": 0.010, "cure_link": 0.008,
         "cure_check": 0.005})
    # ops starting in chipbench.compress, the while left out (its body
    # counts): 4 + 10 + 8 + 2 + 5
    assert red["compress_device_s"] == pytest.approx(0.029)
    assert red["compress_unscoped_s"] == [["copy", pytest.approx(0.002)]]
    assert red["spans"] == 10 and red["spans_outside"] == 0


def test_reduce_spans_per_pass_and_outside():
    devices, host = _synth()
    host.append(_ev("repro.compress.unroll", 99, 101))    # leaves window
    red = cs.reduce_spans(devices, host, passes=2)
    assert red["window_s"] == pytest.approx(0.050)
    assert red["scope_s"]["cure_deim"] == pytest.approx(0.005)
    assert red["spans_outside"] == 1
    with pytest.raises(ValueError):
        cs.reduce_spans({}, host, passes=1)
    with pytest.raises(ValueError):
        cs.reduce_spans(devices, host[1:], passes=1)


def test_scope_ops_by_program_and_instruction():
    """An op takes the scope of its instruction in the program whose
    module event holds it; the same instruction name in another program
    can differ; a program missing from the tables falls back to the
    tables of its function name where they agree."""
    tables = {"jit_f(1)": {"fusion.3": "cure_svd", "solve.2": "cure_deim"},
              "jit_f(2)": {"fusion.3": "cure_link", "solve.2": "cure_deim"},
              "jit_g(7)": {"add.1": "cure_check"}}
    modules = [_ev("jit_f(1)", 0, 10), _ev("jit_f(2)", 10, 20),
               _ev("jit_f(9)", 20, 30), _ev("jit_g(8)", 30, 40)]
    ops = [_ev("%fusion.3 = f32[2] fusion(x)", 1, 2),
           _ev("%fusion.3 = f32[2] fusion(x)", 11, 12),
           _ev("%fusion.3 = f32[2] fusion(x)", 21, 22),   # f(1), f(2) differ
           _ev("%solve.2 = f32[2] custom-call(y)", 23, 24),  # both agree
           _ev("%add.1 = f32[] add(a, b)", 31, 32),        # one g table
           _ev("%copy.4 = f32[] copy(a)", 33, 34),
           _ev("%fusion.3 = f32[2] fusion(x)", 50, 51)]    # no module
    assert cs.scope_ops(ops, modules, tables) == [
        "cure_svd", "cure_link", "", "cure_deim", "cure_check", "", ""]
    assert cs.instruction("%fusion.12 = f32[] fusion()") == "fusion.12"


def test_hlo_scopes_of_a_cpu_capture(tmp_path):
    """The capture's own HLO names each instruction's scope, the pinv's
    SVD under the scope it was called from."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with jax.named_scope("cure_svd"):
            s = jnp.linalg.svd(x, compute_uv=False)
        with jax.named_scope("cure_link"):
            y = jnp.linalg.pinv(x) @ x
        return s.sum() + y.sum()
    x = jnp.eye(16) + 1.0
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        f(x).block_until_ready()
    tables = cs.hlo_scopes(_xplane(str(tmp_path)))
    mine = [t for name, t in tables.items() if name.startswith("jit_f(")]
    assert len(mine) == 1
    assert set(mine[0].values()) == {"cure_svd", "cure_link"}
    assert any(i.startswith(("svd", "custom-call")) and s == "cure_link"
               for i, s in mine[0].items())


def _xplane(d):
    path, = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    return path


def test_benchmark_loader_keeps_only_its_annotations(tmp_path):
    """The program's spans inside the benchmark's annotations, six deep:
    the benchmark's own loader sees its annotations alone, so its idle
    gaps are attributed as they were before the program had spans."""
    import jax
    from repro.obs import Tracer
    t = Tracer()
    x = jax.numpy.ones((64, 64))
    jax.jit(lambda a: a @ a)(x).block_until_ready()
    cap = tr.Capture(str(tmp_path))
    cap.start()
    with tr.annotate("compress"):
        with t.span("compress"):
            with t.span("compress.class"):
                with t.span("compress.class.a"), t.span("compress.class.b"):
                    with t.span("compress.class.c"):
                        with t.span("compress.class.wait"):
                            jax.jit(lambda a: a @ a)(x).block_until_ready()
    cap.stop()
    td = tr.load(_xplane(str(tmp_path)))
    assert sorted(e.name for e in td.host) == ["compress", "window"]
    _, host, _ = cs.load(_xplane(str(tmp_path)))
    names = {e.name for e in host}
    assert {"chipbench.window", "chipbench.compress",
            "repro.compress.class.wait"} <= names
    assert len(names) == 8
    # the gaps of a synthesized device trace over the captured host
    # annotations go to the benchmark's names only
    w = next(e for e in td.host if e.name == "window")
    td.devices = {"/device:TPU:0": [
        tr.Event("%fusion.1 = f32[] fusion()", w.start_ns + w.dur_ns / 3,
                 w.dur_ns / 10)]}
    assert set(tr.reduce(td).gaps_s) <= {"compress", "none"}


def test_measure_on_a_tiny_cure(tmp_path):
    """The tool end to end on the CPU at the tests' size: the cost pairs
    run, the traced pass obtains no program, and every repro.* span lies
    inside its pass's chipbench annotation."""
    cfg_file = dict(chipbench_tiny._load(
        chipbench_tiny.ROOT, "benchmarks/chip/configs/olmo-1b.json"),
        **chipbench_tiny.TINY)
    wl = dict(chipbench_tiny._load(chipbench_tiny.CHIP, "workloads",
                                   "olmo-1b.cure.json"), r_max=8, layers=1)
    mix = {"sequences": 8, "length": 32, "batch": 4}
    out = cs.measure(cfg_file, mix, wl, 2 ** 33 + 5, passes=1, cost=1,
                     trace_dir=str(tmp_path / "t"))
    assert [len(v) for v in out["cure_s"].values()] == [1, 1]
    assert out["programs"]["programs"] == 0
    assert out["programs"]["fun_names"] == []
    for name in ("calibrate.batch", "calibrate.wait", "compress.class",
                 "compress.class.wait", "compress.fold.wait"):
        assert out["span_s"][name] > 0
    devices, host, _ = cs.load(_xplane(out["trace"].dir))
    assert devices == {}                          # no device plane here
    w = next(e for e in host if e.name == "chipbench.window")
    devices = {"/device:TPU:0": [(tr.Event(
        "%fusion.1 = f32[] fusion()", w.start_ns, w.dur_ns / 2), "")]}
    red = cs.reduce_spans(devices, host, passes=1)
    assert red["spans"] > 10 and red["spans_outside"] == 0
