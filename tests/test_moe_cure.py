"""CURing a latent-attention mixture-of-experts model (moonlight-16b-a3b
smoke): every held expert's gate is its own work item on its own routed
activations, the results fold back into one CUR stack per layer, plans
tell experts apart by their rank keys, and a dense model's work list and
key stream are what they were."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import get_smoke
from repro.configs.base import CURConfig
from repro.core import calibrate, compress_model
from repro.core.compress import (
    _cur_work_list, rank_key, resolve_rank, unroll_params, unrolled_config)
from repro.core.calibrate import iter_layer_params
from repro.models import forward, init_params
from repro.models.moe import route

from conftest import make_batch


@pytest.fixture(scope="module")
def moe_setup():
    cfg = get_smoke("moonlight-16b-a3b").replace(experts_held=(2, 6))
    params = init_params(jax.random.PRNGKey(0), cfg)
    # a selection bias that matters, as a trained one does
    for gi in range(1, len(cfg.groups)):
        blk = params["groups"][gi][0]
        blk["router_bias"] = 0.1 * jax.random.normal(
            jax.random.PRNGKey(gi), blk["router_bias"].shape)
    calib = calibrate(params, cfg, [make_batch(cfg, 4, 32, seed=s)
                                    for s in (1, 2)])
    return cfg, params, calib


def test_every_held_expert_is_a_work_item(moe_setup):
    cfg, params, calib = moe_setup
    work = _cur_work_list(params, cfg, CURConfig(r_max=8), calib, {1, 2})
    got = [(it.layer, it.name, it.expert) for it in work]
    want = []
    for li in (1, 2):
        want += [(li, "wq", None), (li, "wk_b", None)]
        want += [(li, "w_gate", e) for e in range(2, 6)]
        want += [(li, "shared.w_gate", None)]
    assert got == want
    stack = params["groups"][1][0]["w_gate"][0]      # layer 1, (4, D, F)
    for it in work[2:6]:
        np.testing.assert_array_equal(it.W, stack[it.expert - 2])
        np.testing.assert_array_equal(
            it.act, calib.act_sq[1]["w_gate"][it.expert - 2])
    # each expert sees its own routed tokens, not every token
    rows = calib.act_sq[1]["w_gate"]
    assert rows.shape == (4, cfg.d_model)
    assert np.all(rows.sum(1) < calib.act_sq[1]["shared.w_gate"].sum())
    assert len({float(r.sum()) for r in rows}) == 4
    # one key each, split in network order
    key = jax.random.PRNGKey(0)
    for it in work:
        key, sub = jax.random.split(key)
        np.testing.assert_array_equal(it.key, sub)


def test_dense_work_list_and_key_stream_unchanged(tiny_cfg, tiny_params):
    """The work list of a model without experts is the per-layer walk
    it always was: one item per target in network order, the weight
    sliced from its layer, and keys split in that order."""
    calib = calibrate(tiny_params, tiny_cfg, [make_batch(tiny_cfg, 2, 16)])
    ccfg = CURConfig(r_max=16, seed=3)
    work = _cur_work_list(tiny_params, tiny_cfg, ccfg, calib, {1, 2})
    key = jax.random.PRNGKey(3)
    want = []
    for li, _, lp in iter_layer_params(tiny_params, tiny_cfg):
        if li not in (1, 2):
            continue
        for t in tiny_cfg.cur_targets:
            key, sub = jax.random.split(key)
            want.append((li, t, lp[t], calib.act_sq[li][t], sub,
                         resolve_rank(*lp[t].shape, li, t, ccfg)))
    assert len(work) == len(want)
    for it, (li, t, W, act, sub, r) in zip(work, want):
        assert (it.layer, it.name, it.expert, it.rank) == (li, t, None, r)
        np.testing.assert_array_equal(it.W, W)
        np.testing.assert_array_equal(it.act, act)
        np.testing.assert_array_equal(it.key, sub)


def test_experts_fold_into_one_stack_per_layer(moe_setup):
    cfg, params, calib = moe_setup
    ccfg = CURConfig(r_max=8, n_compress_layers=2, fold_u=True)
    cparams, ccfg_model, info = compress_model(params, cfg, ccfg, calib)
    assert info.layers == [1, 2]
    assert len(info.weights) == 2 * 7
    layers = {li: lp for li, _, lp in iter_layer_params(params, cfg)}
    for li in info.layers:
        blk = cparams["groups"][li][0]
        assert set(blk["w_gate"]) == {"CU", "R"}
        assert blk["w_gate"]["CU"].shape == (1, 4, cfg.d_model, 8)
        assert blk["w_gate"]["R"].shape == (1, 4, 8, cfg.moe_d_ff)
        assert set(blk["shared"]["w_gate"]) == {"CU", "R"}
        assert not isinstance(blk["shared"]["w_up"], dict)
        # each expert's R is the rows of its own weight that it selected
        stack = layers[li]["w_gate"]
        for w in info.weights:
            if w.layer == li and w.expert is not None:
                np.testing.assert_array_equal(
                    blk["w_gate"]["R"][0, w.expert - 2],
                    stack[w.expert - 2][w.rows])
    # the input tree is left as it was
    assert not isinstance(params["groups"][1][0]["w_gate"], dict)
    assert not isinstance(params["groups"][1][0]["shared"]["w_gate"], dict)
    batch = make_batch(cfg, 2, 16, seed=5)
    out = forward(cparams, ccfg_model, batch)
    assert bool(jnp.isfinite(out).all())
    ref = forward(params, cfg, batch)
    assert float(jnp.abs(out - ref).max()) > 0


@pytest.mark.parametrize("svd", ["exact", "randomized"])
def test_loop_and_batched_agree_on_experts(moe_setup, svd):
    cfg, params, calib = moe_setup
    outs = {}
    for pipe in ("loop", "batched"):
        ccfg = CURConfig(r_max=8, n_compress_layers=1, svd=svd,
                         pipeline=pipe)
        outs[pipe] = compress_model(params, cfg, ccfg, calib)
    (pl, _, il), (pb, _, ib) = outs["loop"], outs["batched"]
    assert [w.key for w in il.weights] == [w.key for w in ib.weights]
    for a, b in zip(il.weights, ib.weights):
        np.testing.assert_array_equal(a.rows, b.rows)
        np.testing.assert_array_equal(a.cols, b.cols)
    li = il.layers[0]
    for name in ("w_gate", "wq"):
        la, lb = pl["groups"][li][0][name], pb["groups"][li][0][name]
        np.testing.assert_allclose(la["U0"], lb["U0"], rtol=2e-3, atol=2e-3)
        np.testing.assert_array_equal(la["R"], lb["R"])


def test_rank_keys_tell_experts_apart(moe_setup):
    cfg, params, calib = moe_setup
    assert rank_key(3, "wq") == "3:wq"
    assert rank_key(3, "shared.w_gate") == "3:shared.w_gate"
    assert rank_key(3, "w_gate", 5) == "3:w_gate[5]"
    # a plan that lists no expert leaves the experts dense
    ccfg = CURConfig(r_max=8, ranks={"1:wq": 4, "1:shared.w_gate": 8})
    cparams, _, info = compress_model(params, cfg, ccfg, calib, layers=[1])
    assert [w.key for w in info.weights] == ["1:wq", "1:shared.w_gate"]
    blk = cparams["groups"][1][0]
    assert not isinstance(blk["w_gate"], dict)
    assert blk["wq"]["U0"].shape == (1, 4, 4)
    # experts at planned ranks of their own: the stack pads to the largest
    ranks = {rank_key(1, "w_gate", e): 2 * (e - 1) for e in range(2, 6)}
    cparams, _, info = compress_model(
        params, cfg, CURConfig(r_max=8, ranks=ranks), calib, layers=[1])
    assert {w.key: w.rank for w in info.weights} == ranks
    stack = cparams["groups"][1][0]["w_gate"]
    assert stack["C"].shape[-1] == 8
    assert float(jnp.abs(stack["C"][0, 0, :, 2:]).max()) == 0.0
    # a stack is listed whole or not at all
    with pytest.raises(ValueError, match="held experts"):
        compress_model(params, cfg, CURConfig(
            r_max=8, ranks={rank_key(1, "w_gate", 2): 4}), calib, layers=[1])


def test_experts_are_traced_and_counted(moe_setup):
    cfg, params, calib = moe_setup
    tracer = obs.Tracer(enabled=True)
    reg = obs.default_registry()
    obs.enable()
    try:
        c = reg.counter("repro_compress_expert_weights_total", "")
        before = c.value
        compress_model(params, cfg, CURConfig(r_max=8, fold_u=True), calib,
                       layers=[1, 2], tracer=tracer)
        assert c.value - before == 8
    finally:
        obs.disable()
    names = [s["name"] for s in tracer.spans]
    assert "compress.experts" in names and "compress.experts.wait" in names
    experts = [s["attrs"]["experts"]
               for s in tracer.spans if s["name"] == "compress.class"]
    assert sorted(experts) == [0, 0, 0, 8]


def test_unroll_reuses_one_layer_groups(moe_setup, tiny_cfg, tiny_params):
    cfg, params, _ = moe_setup
    one = unroll_params(params, cfg)             # one layer to a group
    new = unroll_params(one, unrolled_config(cfg))
    for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(one)):
        assert a is b
    assert new["groups"][1][0] is not one["groups"][1][0]
    # a stacked group is sliced, one layer to a group
    new = unroll_params(tiny_params, tiny_cfg)
    assert len(new["groups"]) == tiny_cfg.n_layers
    np.testing.assert_array_equal(new["groups"][2][0]["wq"][0],
                                  tiny_params["groups"][0][0]["wq"][2])


def test_sigmoid_routing_picks_by_bias_and_gates_by_score():
    cfg = get_smoke("moonlight-16b-a3b")
    x = jax.random.normal(jax.random.PRNGKey(0), (32, cfg.d_model))
    router = jax.random.normal(jax.random.PRNGKey(1),
                               (cfg.d_model, cfg.n_experts)) * 0.1
    g0, e0 = route(x, router, 3, cfg)
    np.testing.assert_allclose(g0.sum(-1), cfg.moe_routed_scale, rtol=1e-5)
    bias = jnp.zeros(cfg.n_experts).at[5].set(10.0)
    g1, e1 = route(x, router, 3, cfg, bias)
    assert bool((e1 == 5).any(-1).all())          # the bias picks expert 5
    s = jax.nn.sigmoid(x @ router)
    picked = jnp.take_along_axis(s, e1, -1)        # gates from scores alone
    np.testing.assert_allclose(
        g1, cfg.moe_routed_scale * picked / picked.sum(-1, keepdims=True),
        rtol=1e-5)


def test_cure_cli_moonlight_smoke(tmp_path):
    from repro.launch.cure import main
    report = main([
        "--arch", "moonlight-16b-a3b", "--smoke", "--layers", "2",
        "--r-max", "8", "--calib-batches", "1", "--calib-batch", "2",
        "--calib-len", "32", "--n-requests", "2", "--prompt-len", "8",
        "--new-tokens", "4", "--ckpt-dir", str(tmp_path / "ckpt"),
        "--report", str(tmp_path / "cure.json")])
    data = json.loads((tmp_path / "cure.json").read_text())
    assert data["n_weights"] == 2 * (3 + 8)
    keys = list(data["plan"]["ranks"])
    li = data["layers_compressed"][0]
    assert f"{li}:w_gate[7]" in keys and f"{li}:shared.w_gate" in keys
    assert f"{li}:wk_b" in keys
    assert [w["key"] for w in data["weights"]] == keys
    assert data["generate"]["engine"] == "legacy"
    assert data["generate"]["tokens"] > 0
    assert report["params"]["saved_deployed"] > 0


def test_a_class_too_large_for_one_call_runs_in_chunks(moe_setup,
                                                       monkeypatch):
    """Chunks give the selections and links one call gives."""
    from repro.core import compress
    cfg, params, calib = moe_setup
    ccfg = CURConfig(r_max=8, n_compress_layers=2)
    _, _, whole = compress_model(params, cfg, ccfg, calib)
    monkeypatch.setattr(compress, "CLASS_MAX_ELEMENTS",
                        3 * cfg.d_model * cfg.moe_d_ff)
    tracer = obs.Tracer(enabled=True)
    _, _, parts = compress_model(params, cfg, ccfg, calib, tracer=tracer)
    chunks = {s["attrs"]["experts"]: s["attrs"]["chunks"]
              for s in tracer.spans if s["name"] == "compress.class"}
    assert chunks[8] == 3                 # 8 expert gates: 3, 3 and 2
    for a, b in zip(whole.weights, parts.weights):
        assert a.key == b.key
        np.testing.assert_array_equal(a.rows, b.rows)
        np.testing.assert_array_equal(a.cols, b.cols)
        assert a.fro_err == pytest.approx(b.fro_err, rel=1e-5)
