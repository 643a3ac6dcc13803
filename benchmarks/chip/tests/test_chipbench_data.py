"""The benchmark's data: BENCHMARK.json against its contract, every file
it names present, the configuration exactly the program's, the weights
in the program's layout, and traffic that gives every seed the same work."""
import os
import re

import jax
import numpy as np
import pytest

import chipbench_tiny
from benchmarks.chip import harness, traffic, weights

ROOT = chipbench_tiny.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return harness.load_benchmark()


def test_benchmark_keys_and_names(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = ([c["name"] for c in bench["configs"]]
             + [w["name"] for w in bench["workloads"]]
             + [m["name"] for m in bench["end_to_end"] + bench["per_layer"]])
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_every_named_file_is_there(bench):
    chip = chipbench_tiny.CHIP
    for c in bench["configs"]:
        assert c["file"].startswith("benchmarks/chip/")
        assert os.path.exists(os.path.join(ROOT, c["file"]))
    for w in bench["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        wl = chipbench_tiny._load(chip, "workloads", w["name"] + ".json")
        assert os.path.exists(os.path.join(chip, "modes",
                                           wl["mode"] + ".py"))
        assert os.path.exists(os.path.join(chip, "traffic",
                                           w["traffic"] + ".json"))
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(chip, "metrics",
                                           m["name"] + ".py"))


def test_every_cell_reports_setup_another_metric_and_a_layer(bench):
    for w in bench["workloads"]:
        e2e = [m["name"] for m in harness.end_to_end_for(bench, w["name"])]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert harness.per_layer_for(bench, w["name"])


def test_configuration_is_the_programs(bench):
    from repro.configs import olmo_1b
    cfg = chipbench_tiny._load(ROOT, "benchmarks/chip/configs/olmo-1b.json")
    assert harness.model_config(cfg) == olmo_1b.CONFIG
    assert cfg["reduced"] == []


def test_weights_have_the_programs_layout():
    from repro.models import init_params
    cfg_file = dict(chipbench_tiny._load(
        ROOT, "benchmarks/chip/configs/olmo-1b.json"), **chipbench_tiny.TINY)
    cfg = harness.model_config(cfg_file)
    want = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    got = weights.make(cfg_file, 2 ** 40 + 3)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.shape == b.shape and a.dtype == b.dtype
    again = weights.make(cfg_file, 2 ** 40 + 3)
    assert all(np.array_equal(a, b) for a, b in
               zip(jax.tree.leaves(got), jax.tree.leaves(again)))


def test_traffic_gives_every_seed_the_same_work():
    """Seeds share lengths and arrivals, in one order, and differ only
    in token ids."""
    mix = chipbench_tiny._load(chipbench_tiny.CHIP, "traffic", "chat.json")
    a = traffic.generate(mix, 2.0, 45, 11, 50304)
    b = traffic.generate(mix, 2.0, 45, 2 ** 35 + 1, 50304)
    assert len(a) == len(b) == traffic.count(2.0, 45)
    for key in (lambda r: len(r.prompt), lambda r: r.max_new_tokens,
                lambda r: r.arrival_s):
        assert list(map(key, a)) == list(map(key, b))
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    again = traffic.generate(mix, 2.0, 45, 11, 50304)
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, again))
    lens = [len(r.prompt) for r in a]
    assert min(lens) >= 64 and max(lens) <= 3072
    assert np.median(lens) == pytest.approx(768, rel=0.1)
    assert lens != sorted(lens)


def test_buckets_cover_the_mix():
    mix = chipbench_tiny._load(chipbench_tiny.CHIP, "traffic", "chat.json")
    assert traffic.buckets(mix, 64, 4096) == [64, 128, 256, 512, 1024,
                                              2048, 4096]


def test_poisson_arrivals_keep_the_rate():
    g = traffic.gaps({"arrival": "poisson"}, 2.0, 4000)
    assert np.mean(g) == pytest.approx(0.5, rel=0.01)
    assert np.std(g) / np.mean(g) == pytest.approx(1.0, rel=0.05)
    with pytest.raises(ValueError):
        traffic.gaps({"arrival": "gamma"}, 2.0, 10)


def test_cur_weights_have_the_layout_curing_serves():
    """The served form made from the seed has the tree CURe + fold gives
    the program (one group per layer, folded {CU, R} leaves)."""
    from repro.configs.base import CURConfig
    from repro.core import calibrate, compress_model
    cfg_file = dict(chipbench_tiny._load(
        ROOT, "benchmarks/chip/configs/olmo-1b.json"), **chipbench_tiny.TINY)
    cfg = harness.model_config(cfg_file)
    dense = weights.make(cfg_file, 1)
    tokens = np.random.default_rng(0).integers(0, 256, (2, 16))
    calib = calibrate(dense, cfg, [{"tokens": tokens}])
    ccfg = CURConfig(r_max=8, n_compress_layers=2, fold_u=True)
    cured, ccfg_model, info = compress_model(dense, cfg, ccfg, calib)
    cur = {"layers": sorted(info.layers), "targets": ["wq", "wk", "w_gate"],
           "rank": 8, "kv_rank": 8}
    made, proj = weights.make_cur(cfg_file, cur, 1)
    assert jax.tree.structure(made) == jax.tree.structure(cured)
    for a, b in zip(jax.tree.leaves(made), jax.tree.leaves(cured)):
        assert a.shape == b.shape and a.dtype == b.dtype
    assert harness.model_config(cfg_file, unrolled=True) == ccfg_model
    assert proj["qk"].shape == (cfg.n_layers, 8)
    assert proj["uk"].shape == (cfg.n_layers, 8, cfg.head_dim)
