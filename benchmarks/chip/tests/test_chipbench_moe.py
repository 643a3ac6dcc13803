"""The latent-attention MoE configuration against its plain reference
(``reference/mla_moe.py``) at a tiny size on the CPU, and the comparison
that decides its cell's ``correct``: sound runs pass, faults planted
under the timed path fail, and the control reads above the program."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chipbench_moe_tiny
import chipbench_tiny
from benchmarks.chip import weights_moe
from benchmarks.chip.modes import cure_moe
from benchmarks.chip.reference import mla_moe as mref

TARGETS = ("wq", "wk_b", "w_gate", "shared.w_gate")


def _file(**kw):
    cfg = chipbench_tiny._load(chipbench_tiny.ROOT, "benchmarks/chip/configs",
                               chipbench_moe_tiny.CONFIG + ".json")
    cfg.update(chipbench_moe_tiny.TINY)
    cfg.update(kw)
    return cfg


def _tokens(cfg_file, b, s, seed=0):
    return np.random.default_rng(seed).integers(
        0, cfg_file["vocab_size"], (b, s)).astype(np.int32)


def test_configuration_is_the_published_one():
    """The program's keys hold the catalog's published values, and the
    program builds the Moonlight block from them, cut only to the held
    share."""
    from repro.configs import moonlight_16b_a3b as ml
    f = chipbench_tiny._load(chipbench_tiny.ROOT, "benchmarks/chip/configs",
                             chipbench_moe_tiny.CONFIG + ".json")
    pairs = {"d_model": "hidden_size", "n_layers": "num_hidden_layers",
             "n_heads": "num_attention_heads",
             "n_kv_heads": "num_key_value_heads",
             "d_ff": "intermediate_size", "moe_d_ff": "moe_intermediate_size",
             "n_experts": "n_routed_experts",
             "n_experts_per_tok": "num_experts_per_tok",
             "moe_routed_scale": "routed_scaling_factor",
             "norm_eps": "rms_norm_eps",
             "mlp_act": "hidden_act"}
    for mine, published in pairs.items():
        assert f[mine] == f[published], mine
    assert f["tie_embeddings"] == f["tie_word_embeddings"]
    assert f["moe_scoring"] == f["scoring_func"] == "sigmoid"
    # the program's sigmoid routing is noaux_tc with one group, its gates
    # renormalised over the top-k
    assert f["topk_method"] == "noaux_tc" and f["n_group"] == 1
    assert f["norm_topk_prob"] is True and f["moe_router_bias"] is True
    assert f["q_lora_rank"] is None
    assert f["reduced"] == ["n_experts_held"] and f["n_experts_held"] == 8
    cfg = cure_moe.model_config(f, TARGETS)
    assert cfg.blocks == ml.CONFIG.blocks
    assert cfg.replace(groups=(), scan_layers=True, experts_held=(),
                       name=ml.CONFIG.name, fsdp=True, moe_impl="a2a") \
        == ml.CONFIG.replace(groups=())
    assert cfg.held_experts == (0, 8)
    assert 3.3e9 < cfg.param_count() < 3.4e9        # one chip's share
    assert 15.9e9 < ml.CONFIG.param_count() < 16.0e9   # the whole model


def test_weights_have_the_programs_layout():
    from repro.models import init_params
    f = _file()
    cfg = cure_moe.model_config(f, TARGETS)
    want = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    got = weights_moe.make(f, 2 ** 40 + 3)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.shape == b.shape and a.dtype == b.dtype
    # a share holds the same experts whatever its size
    more = weights_moe.make(dict(f, n_experts_held=8), 2 ** 40 + 3)
    np.testing.assert_array_equal(got["groups"][1][0]["w_gate"][0],
                                  more["groups"][1][0]["w_gate"][0, :4])


@pytest.mark.parametrize("held", [4, 16])
def test_forward_logits_match_the_reference(held):
    """float32 on both sides, so the only differences are summation
    order and the exp/rsqrt of the two backends' fusions: 2e-4 of the
    logits' scale (about 1) leaves room for that, while one expert's
    output missing or doubled moves them by more than 1e-2."""
    from repro.models import forward
    f = _file(n_experts_held=held)
    cfg = cure_moe.model_config(f, TARGETS)
    w = weights_moe.make(f, 7)
    tokens = _tokens(f, 2, 24)
    got = forward(w, cfg, {"tokens": jnp.asarray(tokens)})
    d = mref.Dims.of(f)
    for b in range(2):
        want = mref.logits(w, jnp.asarray(tokens[b]), d)
        np.testing.assert_allclose(np.asarray(got[b]), np.asarray(want),
                                   rtol=0, atol=2e-4)


def test_prefill_then_decode_match_the_reference():
    """The latent cache: prefill 16 tokens, decode 8 one at a time, each
    step's logits against the reference's full forward (the same float32
    tolerance as the forward)."""
    from repro.models import decode_step, init_cache, prefill
    f = _file()
    cfg = cure_moe.model_config(f, TARGETS)
    w = weights_moe.make(f, 8)
    tokens = _tokens(f, 2, 24, seed=1)
    d = mref.Dims.of(f)
    want = np.stack([np.asarray(mref.logits(w, jnp.asarray(t), d))
                     for t in tokens])
    cache = init_cache(cfg, 2, 24)
    logits, cache = prefill(w, cfg, {"tokens": jnp.asarray(tokens[:, :16])},
                            cache)
    np.testing.assert_allclose(np.asarray(logits), want[:, 15], atol=2e-4)
    for t in range(16, 24):
        logits, cache = decode_step(
            w, cfg, {"tokens": jnp.asarray(tokens[:, t:t + 1])}, cache,
            jnp.full((2, 1), t, jnp.int32))
        np.testing.assert_allclose(np.asarray(logits), want[:, t], atol=2e-4,
                                   err_msg=f"position {t}")


def test_expert_shares_sum_to_the_uncut_layer():
    """Eight chips' shares of an MoE layer: the routed parts of the eight
    shares, with the shared experts counted once, add up to what the
    uncut reference gives for the whole layer."""
    from repro.models.mlp import mlp_forward
    from repro.models.moe import moe_dense
    f = _file(n_experts_held=16)
    cfg = cure_moe.model_config(f, TARGETS)
    block = jax.tree.map(lambda a: a[0],
                         weights_moe.make(f, 9)["groups"][1][0])
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 12, f["d_model"]))
    shares = []
    for chip in range(8):
        lo, hi = 2 * chip, 2 * chip + 2
        part = dict(block, **{k: block[k][lo:hi]
                              for k in ("w_gate", "w_up", "w_down")})
        shares.append(moe_dense(h, part, cfg.replace(experts_held=(lo, hi))))
    shared = mlp_forward(h, block["shared"], cfg)
    total = sum(shares) - 7 * shared
    d = mref.Dims.of(f, held=(0, 16))
    want = jax.vmap(lambda x: mref.moe(x, block, d)[0])(h)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=2e-5)
    # one chip's share alone is not the layer
    assert float(jnp.abs(shares[0] - want).max()) > 1e-2


def test_calibration_sums_match_the_reference():
    """Every layer's sums, each held expert's over its own routed tokens
    (float32 both sides: 1e-5 relative is summation order)."""
    from repro.core import calibrate
    f = _file()
    cfg = cure_moe.model_config(f, TARGETS)
    w = weights_moe.make(f, 10)
    tokens = _tokens(f, 8, 32, seed=2)
    calib = calibrate(w, cfg, [{"tokens": jnp.asarray(tokens[i:i + 4])}
                               for i in (0, 4)])
    sums, hidden = mref.calibrate(w, tokens, mref.Dims.of(f), 4)
    for li in range(f["n_layers"]):
        assert set(calib.act_sq[li]) == set(sums[li])
        for name, want in sums[li].items():
            np.testing.assert_allclose(calib.act_sq[li][name], want,
                                       rtol=1e-5, err_msg=f"{li} {name}")
    assert calib.act_sq[1]["w_gate"].shape == (4, f["d_model"])
    np.testing.assert_allclose(calib.hidden, hidden, rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# the comparison that decides ``correct``
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return chipbench_moe_tiny.build(str(tmp_path_factory.mktemp("moe")))


@pytest.fixture
def fresh(monkeypatch):
    """Faults are planted in module globals that jitted programs close
    over: restore them, and drop compiled programs, around the test."""
    from repro.core import angular, compress
    calibrate = importlib.import_module("repro.core.calibrate")
    for mod, name in ((compress, "cur_from_indices"),
                      (compress, "select_indices"),
                      (angular, "select_layers"),
                      (calibrate, "_routed_sq_sum")):
        monkeypatch.setattr(mod, name, getattr(mod, name))
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_sound_run_is_correct(root, fresh):
    line = chipbench_moe_tiny.run(root, 2 ** 33 + 17, 2.0)
    assert line["correct"] is True, line["checks"]
    assert list(line)[-1] == "checks"
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"cure_s", "setup_s"}


@pytest.mark.parametrize("fault,number", [
    ("all_tokens", "act_err"), ("answer", "r_exact"),
    ("selection", "sel_growth"), ("layers", "layers_differ")])
def test_broken_timed_path_is_not_correct(root, fresh, fault, number):
    """Routed experts given every token's activations (calibration as it
    was before experts had their own), a wrong row of R, the first r
    indices, and the layers of largest distance each fail their number."""
    line = chipbench_moe_tiny.run(root, 5, 1.0, fault=fault)
    check = line["checks"][number]
    assert check["value"] > check["limit"], line["checks"]
    assert line["correct"] is False


def test_control_reads_far_above_the_program(root, fresh):
    for seed in (1, 2):
        ctx = chipbench_moe_tiny.context(root, seed, 0.5, control=True)
        out = cure_moe.run(ctx)
        prog = {c.name: c.value for c in out.checks}
        ctl = out.reading["control"]
        limits = ctx.workload["limits"]
        assert ctl["cu_err"] > limits["cu_err"] > prog["cu_err"], seed
        assert ctl["act_err"] >= 3 * prog["act_err"], seed


def test_experts_reader_reads_the_programs_spans(root, fresh):
    """A traced run's tracer spans give ``cure.experts_s``; a run
    without them gives nothing."""
    from benchmarks.chip import harness
    reader = harness.load_module(
        chipbench_tiny.CHIP + "/metrics/cure.experts_s.py",
        "cure.experts_s")
    assert reader.read({"program_spans": None, "passes": 2}) is None
    spans = [{"name": "compress.class", "dur": 1.0, "attrs": {"experts": 8}},
             {"name": "compress.class", "dur": 5.0, "attrs": {"experts": 0}},
             {"name": "compress.experts", "dur": 0.5, "attrs": {"k": 2}},
             {"name": "compress.fold", "dur": 9.0, "attrs": {}}]
    assert reader.read({"program_spans": spans, "passes": 1}) == 1.5
    ctx = chipbench_moe_tiny.context(root, 3, 0.5)
    from repro import obs
    tracer = obs.Tracer(enabled=True)
    cfg = cure_moe.model_config(ctx.config_file, ctx.workload["targets"])
    params = weights_moe.make(ctx.config_file, 3)
    tokens = _tokens(ctx.config_file, 4, 16)
    cure_moe.one_pass(params, cfg, [{"tokens": jnp.asarray(tokens)}],
                      cure_moe.cure.cur_config(ctx.workload),
                      lambda name: cure_moe.cure._Null(), tracer)
    got = reader.read({"program_spans": tracer.spans, "passes": 1})
    assert got is not None and got > 0
    classes = [s for s in tracer.spans if s["name"] == "compress.class"]
    assert sorted(s["attrs"]["experts"] for s in classes) == [0, 0, 0, 4]
