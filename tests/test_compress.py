"""CURing compression pipeline: structure preservation, Eq. 2 savings,
selection-method quality ordering (paper App. D.2), fold equivalence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import CURConfig
from repro.core import calibrate, compress_model
from repro.core.compress import compress_weight, fold_cur, select_indices
from repro.models import forward, init_params
from repro.models.layers import apply_w, cur_materialize, w_shape

from conftest import make_batch


def _structured_lowrank(params, cfg, rank=8, noise=0.02):
    """Deterministically project every CUR-target weight to rank-``rank``
    plus small noise — the structure trained nets exhibit and the paper's
    compression assumes. Random-init weights are full-rank, which made the
    quality thresholds below flaky; this keeps them honest (strict
    inequalities, fixed seeds) on a fixture that CUR can actually fit."""
    new = {k: v for k, v in params.items() if k != "groups"}
    new["groups"] = []
    for gi, group in enumerate(params["groups"]):
        ng = []
        for pi, block in enumerate(group):
            nb = dict(block)
            for ti, t in enumerate(cfg.cur_targets):
                if t not in nb:
                    continue
                W = nb[t]                      # leading reps axis

                def lowrank(w, key):
                    U, s, Vt = jnp.linalg.svd(w.astype(jnp.float32),
                                              full_matrices=False)
                    wlr = (U[:, :rank] * s[:rank]) @ Vt[:rank]
                    scale = noise * s[0] / np.sqrt(w.shape[0])
                    return (wlr + scale * jax.random.normal(key, w.shape)
                            ).astype(w.dtype)

                base = jax.random.fold_in(
                    jax.random.fold_in(
                        jax.random.fold_in(jax.random.PRNGKey(17), gi),
                        pi), ti)
                nb[t] = jnp.stack([
                    lowrank(W[i], jax.random.fold_in(base, i))
                    for i in range(W.shape[0])])
            ng.append(nb)
        new["groups"].append(ng)
    return new


@pytest.fixture(scope="module")
def structured_params(tiny_cfg, tiny_params):
    return _structured_lowrank(tiny_params, tiny_cfg)


@pytest.fixture(scope="module")
def compressed(tiny_cfg, structured_params):
    calib = calibrate(structured_params, tiny_cfg,
                      [make_batch(tiny_cfg, 2, 32)])
    ccfg = CURConfig(r_max=16, n_compress_layers=2)
    return compress_model(structured_params, tiny_cfg, ccfg, calib)


def test_io_dims_preserved(tiny_cfg, tiny_params, compressed):
    """The paper's structural claim: compressed layers keep (m, n)."""
    new_params, new_cfg, info = compressed
    for w in info.weights:
        block = new_params["groups"][w.layer][0]
        leaf = jax.tree.map(lambda a: a[0], block[w.name])
        assert w_shape(leaf) == w.shape


def test_params_actually_saved(compressed):
    _, _, info = compressed
    assert info.params_saved > 0
    for w in info.weights:
        assert w.params_after < w.params_before
        assert w.rank & (w.rank - 1) == 0


def test_compressed_forward_close_to_original(tiny_cfg, structured_params,
                                              compressed):
    new_params, new_cfg, _ = compressed
    b = make_batch(tiny_cfg, 2, 32, seed=5)
    l0 = forward(structured_params, tiny_cfg, b)
    l1 = forward(new_params, new_cfg, b)
    corr = float(jnp.corrcoef(l0.ravel(), l1.ravel())[0, 1])
    assert corr > 0.8, f"logit correlation too low: {corr}"


def test_cur_rows_cols_are_original_values(tiny_cfg, structured_params,
                                           compressed):
    """C/R are actual columns/rows of W — interpretability property (§6.1).
    Also preserves characteristics like sign patterns."""
    new_params, new_cfg, info = compressed
    w = info.weights[0]
    W = _orig_weight(structured_params, tiny_cfg, w.layer, w.name)
    leaf = jax.tree.map(lambda a: a[0],
                        new_params["groups"][w.layer][0][w.name])
    np.testing.assert_allclose(np.asarray(leaf["C"]), W[:, w.cols],
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(leaf["R"]), W[w.rows, :],
                               rtol=1e-5)


def _orig_weight(params, cfg, layer, name):
    from repro.core.calibrate import iter_layer_params
    for li, spec, lp in iter_layer_params(params, cfg):
        if li == layer:
            return np.asarray(lp[name])
    raise KeyError


def test_fold_u_equivalence():
    key = jax.random.PRNGKey(0)
    W = jax.random.normal(key, (48, 64))
    leaf, _ = compress_weight(W, "wq", 0, CURConfig(r_max=8),
                              np.ones(48), key)
    x = jax.random.normal(jax.random.fold_in(key, 1), (5, 48))
    y1 = apply_w(x, leaf)
    y2 = apply_w(x, fold_cur(leaf))
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-4, atol=1e-4)


def test_selection_quality_ordering():
    """Paper Table 5: WANDA+DEIM approximates W better than random.
    Uses a structured weight whose true rank (6) is within the selection
    rank (8), like trained nets — with true rank above the budget, the
    activation-weighted selection optimizes a different objective than
    the unweighted Frobenius metric and the ordering is not guaranteed."""
    key = jax.random.PRNGKey(42)
    k1, k2, k3 = jax.random.split(key, 3)
    W = (jax.random.normal(k1, (96, 6)) @ jax.random.normal(k2, (6, 80))
         + 0.1 * jax.random.normal(k3, (96, 80)))
    act = np.abs(np.random.RandomState(0).randn(96)) + 0.1
    errs = {}
    for method in ("wanda_deim", "deim", "random"):
        leaf, info = compress_weight(
            W, "w", 0, CURConfig(r_max=8, selection=method), act, k3)
        errs[method] = info.fro_err
    assert errs["wanda_deim"] < errs["random"]
    assert errs["deim"] < errs["random"]


@pytest.mark.parametrize("svd", ["exact", "randomized"])
def test_batched_pipeline_matches_loop(tiny_cfg, structured_params, svd):
    """The tentpole contract: the jitted shape-class-batched pipeline
    produces the SAME row/col selections and link matrices as the
    per-weight reference loop on a fixed seed, per shape-class."""
    calib = calibrate(structured_params, tiny_cfg,
                      [make_batch(tiny_cfg, 2, 32)])
    outs = {}
    for pipeline in ("loop", "batched"):
        ccfg = CURConfig(r_max=16, n_compress_layers=2, svd=svd,
                         pipeline=pipeline)
        outs[pipeline] = compress_model(structured_params, tiny_cfg, ccfg,
                                        calib)
    il, ib = outs["loop"][2], outs["batched"][2]
    assert len(il.weights) == len(ib.weights) > 0
    shapes = set()
    for wl, wb in zip(il.weights, ib.weights):
        assert (wl.layer, wl.name) == (wb.layer, wb.name)
        np.testing.assert_array_equal(wl.rows, wb.rows)
        np.testing.assert_array_equal(wl.cols, wb.cols)
        shapes.add(wl.shape)
        leaf_l = jax.tree.map(
            lambda a: a[0], outs["loop"][0]["groups"][wl.layer][0][wl.name])
        leaf_b = jax.tree.map(
            lambda a: a[0],
            outs["batched"][0]["groups"][wb.layer][0][wb.name])
        np.testing.assert_allclose(np.asarray(leaf_l["U0"]),
                                   np.asarray(leaf_b["U0"]), atol=1e-5)
        np.testing.assert_array_equal(np.asarray(leaf_l["C"]),
                                      np.asarray(leaf_b["C"]))
        np.testing.assert_array_equal(np.asarray(leaf_l["R"]),
                                      np.asarray(leaf_b["R"]))
        assert abs(wl.fro_err - wb.fro_err) < 1e-3 * max(wl.fro_w, 1.0)
    assert len(shapes) >= 2, "want multiple shape-classes exercised"


def test_batched_pipeline_matches_loop_with_rank_overrides(
        tiny_cfg, structured_params):
    """PR 3's equivalence contract extended to per-weight rank overrides
    (CURConfig.ranks): heterogeneous ranks — including two same-shape
    weights at DIFFERENT ranks, which forces the batched pipeline to
    split the (m, n) class by rank — still yield identical selections
    and link matrices across the two pipelines."""
    calib = calibrate(structured_params, tiny_cfg,
                      [make_batch(tiny_cfg, 2, 32)])
    ranks = {"1:wq": 8, "1:wk": 4, "1:w_gate": 16,
             "2:wq": 4, "2:wk": 4, "2:w_gate": 8}
    outs = {}
    for pipeline in ("loop", "batched"):
        ccfg = CURConfig(r_max=16, ranks=ranks, pipeline=pipeline)
        outs[pipeline] = compress_model(structured_params, tiny_cfg, ccfg,
                                        calib, layers=[1, 2])
    il, ib = outs["loop"][2], outs["batched"][2]
    assert len(il.weights) == len(ib.weights) == len(ranks)
    for wl, wb in zip(il.weights, ib.weights):
        key = f"{wl.layer}:{wl.name}"
        assert wl.rank == wb.rank == ranks[key]
        np.testing.assert_array_equal(wl.rows, wb.rows)
        np.testing.assert_array_equal(wl.cols, wb.cols)
        leaf_l = jax.tree.map(
            lambda a: a[0], outs["loop"][0]["groups"][wl.layer][0][wl.name])
        leaf_b = jax.tree.map(
            lambda a: a[0],
            outs["batched"][0]["groups"][wb.layer][0][wb.name])
        np.testing.assert_allclose(np.asarray(leaf_l["U0"]),
                                   np.asarray(leaf_b["U0"]), atol=1e-5)
        np.testing.assert_array_equal(np.asarray(leaf_l["C"]),
                                      np.asarray(leaf_b["C"]))
    # same-shape weights really did land at different ranks
    shapes_at_ranks = {(wl.shape, wl.rank) for wl in il.weights}
    shapes = [s for s, _ in shapes_at_ranks]
    assert any(shapes.count(s) > 1 for s in set(shapes))


def test_fold_param_accounting():
    """Satellite bugfix: params_after must reflect the DEPLOYED form —
    {CU, R} is m r + r n, not the healing-form m r + r^2 + r n."""
    key = jax.random.PRNGKey(0)
    W = jax.random.normal(key, (48, 64))
    act = np.ones(48)
    _, heal = compress_weight(W, "wq", 0, CURConfig(r_max=8), act, key)
    _, fold = compress_weight(W, "wq", 0, CURConfig(r_max=8, fold_u=True),
                              act, key)
    m, n, r = 48, 64, heal.rank
    assert heal.params_after_unfolded == m * r + r * r + r * n
    assert heal.params_after_folded == m * r + r * n
    assert heal.params_after == heal.params_after_unfolded
    assert fold.params_after == fold.params_after_folded
    assert fold.params_after < heal.params_after


def test_compress_info_reports_both_forms(tiny_cfg, structured_params,
                                          compressed):
    _, _, info = compressed                      # fold_u=False fixture
    assert info.params_saved == info.params_saved_unfolded
    assert info.params_saved_folded > info.params_saved_unfolded
    calib = calibrate(structured_params, tiny_cfg,
                      [make_batch(tiny_cfg, 2, 32)])
    _, _, folded = compress_model(
        structured_params, tiny_cfg,
        CURConfig(r_max=16, n_compress_layers=2, fold_u=True), calib)
    assert folded.params_saved == folded.params_saved_folded


def test_bound_labeled_by_matrix():
    """Satellite bugfix: wanda_deim feeds the SVD of the WANDA matrix S,
    so its Theorem 3.1 bound is valid for S — bound_on records that.
    For plain deim the bound is on W itself and must actually hold."""
    key = jax.random.PRNGKey(3)
    W = jax.random.normal(key, (64, 48))
    act = np.abs(np.random.RandomState(0).randn(64)) + 0.1
    _, wd = compress_weight(
        W, "w", 0, CURConfig(r_max=8, selection="wanda_deim"), act, key)
    assert wd.bound_on == "wanda" and np.isfinite(wd.bound)
    leaf, dm = compress_weight(
        W, "w", 0, CURConfig(r_max=8, selection="deim"), act, key)
    assert dm.bound_on == "weight" and np.isfinite(dm.bound)
    err2 = float(jnp.linalg.norm(W - cur_materialize(leaf), ord=2))
    assert err2 <= dm.bound * (1 + 1e-3)
    _, rnd = compress_weight(
        W, "w", 0, CURConfig(r_max=8, selection="random"), act, key)
    assert rnd.bound_on == "none" and np.isnan(rnd.bound)


def test_selection_methods_all_run():
    key = jax.random.PRNGKey(1)
    W = jax.random.normal(key, (40, 56))
    act = np.ones(40)
    for method in ("wanda_deim", "wanda", "deim", "weight", "random"):
        p, q, _ = select_indices(W, 8, method, act, key)
        assert len(set(np.asarray(p).tolist())) == 8
        assert len(set(np.asarray(q).tolist())) == 8


def test_randomized_svd_compression_close_to_exact():
    key = jax.random.PRNGKey(9)
    k1, k2, k3 = jax.random.split(key, 3)
    W = (jax.random.normal(k1, (128, 16)) @ jax.random.normal(k2, (16, 96))
         + 0.05 * jax.random.normal(k3, (128, 96)))
    act = np.ones(128)
    _, exact = compress_weight(W, "w", 0,
                               CURConfig(r_max=16, svd="exact"), act, k1)
    _, rand = compress_weight(W, "w", 0,
                              CURConfig(r_max=16, svd="randomized"), act, k1)
    assert rand.fro_err <= exact.fro_err * 2.0


def test_angular_distance_layer_selection(tiny_cfg, tiny_params, compressed):
    _, _, info = compressed
    L = tiny_cfg.n_layers
    assert 0 not in info.layers and (L - 1) not in info.layers
    cands = [info.distances[i] for i in range(1, L - 1)]
    chosen = [info.distances[i] for i in info.layers]
    assert max(chosen) <= max(cands)
    assert sorted(chosen) == sorted(sorted(cands)[:len(chosen)])


def test_each_shape_class_runs_once_per_call(tiny_cfg, structured_params,
                                             monkeypatch):
    """The first call of a class in a process runs it once, as every
    later call does: no warm-up rerun."""
    from repro.core import compress as cmod
    calls = []
    inner = cmod._compress_class_batched

    def counted(*a, **kw):
        calls.append(kw["r"])
        return inner(*a, **kw)
    monkeypatch.setattr(cmod, "_compress_class_batched", counted)
    calib = calibrate(structured_params, tiny_cfg,
                      [make_batch(tiny_cfg, 2, 32)])
    # r_max 4: a rank no other test compiles, so the first call compiles
    ccfg = CURConfig(r_max=4, n_compress_layers=2)
    _, _, info = compress_model(structured_params, tiny_cfg, ccfg, calib)
    n_classes = len({(w.shape, w.rank) for w in info.weights})
    assert len(calls) == n_classes
    compress_model(structured_params, tiny_cfg, ccfg, calib)
    assert len(calls) == 2 * n_classes


def test_fold_dispatches_every_fold_then_waits_once(
        tiny_cfg, structured_params, monkeypatch):
    from repro.core import compress as cmod
    from repro.obs import Tracer
    waits = []
    inner = jax.block_until_ready

    def counted(x):
        waits.append(len(jax.tree.leaves(x)))
        return inner(x)
    monkeypatch.setattr(cmod.jax, "block_until_ready", counted)
    calib = calibrate(structured_params, tiny_cfg,
                      [make_batch(tiny_cfg, 2, 32)])
    ccfg = CURConfig(r_max=8, n_compress_layers=2, fold_u=True)
    tr = Tracer()
    _, _, info = compress_model(structured_params, tiny_cfg, ccfg, calib,
                                tracer=tr)
    assert waits == [len(info.weights)] and len(info.weights) > 1
    fold = [s for s in tr.spans if s["name"] == "compress.fold"]
    assert len(fold) == 1
    assert info.seconds_fold == pytest.approx(fold[0]["dur"], abs=1e-3)


def test_weight_seconds_is_its_class_span_over_k(tiny_cfg,
                                                 structured_params):
    """WeightInfo.seconds is the class's time over its weights, and a
    call whose programs are all compiled obtains none (the compress
    window's target)."""
    from repro.obs import Tracer
    calib = calibrate(structured_params, tiny_cfg,
                      [make_batch(tiny_cfg, 2, 32)])
    ccfg = CURConfig(r_max=8, n_compress_layers=2)
    compress_model(structured_params, tiny_cfg, ccfg, calib)
    tr = Tracer()
    _, _, info = compress_model(structured_params, tiny_cfg, ccfg, calib,
                                tracer=tr)
    classes = [s for s in tr.spans if s["name"] == "compress.class"]
    assert sum(s["attrs"]["k"] for s in classes) == len(info.weights)
    for s in classes:
        a = s["attrs"]
        assert a["programs"] == 0
        ws = [w for w in info.weights
              if w.shape == (a["m"], a["n"]) and w.rank == a["r"]]
        assert len(ws) == a["k"]
        for w in ws:
            assert w.seconds == pytest.approx(s["dur"] / a["k"], abs=1e-3)


def test_compress_chain_is_named_for_the_profiler():
    """Each part of the per-weight chain carries its scope in the
    program's op metadata (debug info, which the compile cache's key
    leaves out)."""
    from repro.core import compress as cmod
    k, m, n = 2, 40, 56
    Ws = jnp.ones((k, m, n), jnp.bfloat16)
    keys = jax.random.split(jax.random.PRNGKey(0), k)
    text = cmod._compress_class_batched.lower(
        Ws, jnp.ones((k, m)), keys, r=8, selection="wanda_deim",
        svd="exact").as_text(debug_info=True)
    for scope in ("cure_svd", "cure_deim", "cure_link", "cure_check"):
        assert scope in text
