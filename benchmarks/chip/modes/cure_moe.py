"""A CURing cell of a latent-attention mixture-of-experts model held as
one chip's share of its experts: calibrate, then compress and fold, as
``repro.launch.cure`` calls them, back to back for the window.

The configuration's layers are built as the program builds a CURed
model, one group per layer (``weights_moe.py``), with the router over
all ``n_experts`` and the stacks of the ``n_experts_held`` held here.
Set-up, the window and ``cure_s`` are those of ``modes/cure.py``; in a
traced run an enabled ``repro.obs.Tracer`` goes to ``compress_model``,
and its spans are what ``cure.experts_s`` reads.

Correctness compares the last pass with the reference
(``reference/mla_moe.py`` for the forward, ``reference/cure.py`` for
CURing), as ``modes/cure.py`` does:

- ``act_err``: every calibration sum, one per layer and target and, for
  an MoE layer's ``w_gate``, one per held expert (over the tokens routed
  to it): the worst relative error of any;
- ``layers_differ``: the layers compressed against those the reference's
  angular distances pick (exact);
- ``sel_growth``, ``r_exact``, ``cu_err`` on ``check_weights`` weights
  drawn from the seed, one of each target kind first (``wq``, ``wk_b``,
  a held expert's ``w_gate``, ``shared.w_gate``).
"""
from __future__ import annotations

import dataclasses
import gc
import os
import types

import numpy as np

from benchmarks.chip import harness, weights_moe
from benchmarks.chip import trace as tr
from benchmarks.chip.modes import cure
from benchmarks.chip.reference import cure as ref
from benchmarks.chip.reference import mla_moe as mref


def model_config(cfg_file: dict, targets):
    """The program's ``ModelConfig``: every key the dataclass knows as it
    stands, the first ``first_k_dense_replace`` layers MLA + MLP and the
    rest MLA + MoE, one group per layer, the held share ``[0,
    n_experts_held)``, and the CURing targets of the cell."""
    from repro.configs.base import MLA, MLP, MOE, BlockSpec, ModelConfig
    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    kw = {k: v for k, v in cfg_file.items() if k in fields}
    k0, L = cfg_file["first_k_dense_replace"], cfg_file["n_layers"]
    kw["groups"] = ((((BlockSpec(MLA, MLP),), 1),) * k0
                    + (((BlockSpec(MLA, MOE),), 1),) * (L - k0))
    kw["scan_layers"] = False
    kw["experts_held"] = (0, cfg_file["n_experts_held"])
    kw["cur_targets"] = tuple(targets)
    return ModelConfig(**kw)


def _plant(fault):
    """Test hook: the faults of ``modes/cure.py``, and ``all_tokens``:
    routed experts given the sums over every token (calibration as it
    was before experts had their own)."""
    if fault != "all_tokens":
        return cure._plant(fault)
    import importlib
    import jax.numpy as jnp
    # the module (``repro.core.calibrate`` the attribute is its function)
    calibrate = importlib.import_module("repro.core.calibrate")

    def all_tokens(h, picked):
        sq = jnp.sum(h.astype(jnp.float32) ** 2, axis=(0, 1))
        return jnp.broadcast_to(sq, (picked.shape[1],) + sq.shape)
    calibrate._routed_sq_sum = all_tokens


def one_pass(params, cfg, batches, ccfg, annotate, tracer=None):
    import jax
    from repro.core import calibrate, compress_model
    with annotate("calibrate"):
        calib = calibrate(params, cfg, batches)
    with annotate("compress"):
        cparams, _, info = compress_model(params, cfg, ccfg, calib,
                                          tracer=tracer)
        jax.block_until_ready(cparams)
    return calib, cparams, info


def _leaf(block: dict, name: str):
    for part in name.split("."):
        block = block[part]
    return block


def draw(info, seed: int, n: int):
    """(layer, name, expert or None) of n compressed weights drawn from
    the seed, one of each target kind first (``modes.cure.draw`` over
    (layer, expert) places)."""
    shim = types.SimpleNamespace(weights=[
        types.SimpleNamespace(layer=(w.layer, -1 if w.expert is None
                                     else w.expert), name=w.name)
        for w in info.weights])
    return [(li, name, None if e < 0 else e)
            for (li, e), name in cure.draw(shim, seed, n)]


def collect(calib, cparams, info, sample, first: int) -> cure.PassOut:
    """The last pass's answers, on the host; ``picked`` keyed (layer,
    name, expert)."""
    import jax
    picked = {}
    by_key = {(w.layer, w.name, w.expert): w for w in info.weights}
    for key in sample:
        li, name, e = key
        w = by_key[key]
        leaf = _leaf(cparams["groups"][li][0], name)
        at = (0,) if e is None else (0, e - first)
        cu, r = jax.device_get((leaf["CU"][at], leaf["R"][at]))
        picked[key] = (np.asarray(w.rows), np.asarray(w.cols),
                       np.asarray(cu, np.float64), np.asarray(r, np.float64))
    return cure.PassOut(act_sq=calib.act_sq, layers=list(info.layers),
                        distances=np.asarray(info.distances, np.float64),
                        picked=picked)


def _row(sums: dict, name: str, e, first: int):
    a = sums[name]
    return a if e is None else a[e - first]


def readings(cfg_file: dict, wl: dict, seed: int, tokens: np.ndarray,
             batch: int, got: cure.PassOut, precision: str = "f32") -> dict:
    """Every number compared, for ``got`` against the float32 reference,
    beside ``dist_err`` and ``layer_margin`` (not compared), as
    ``modes.cure.readings`` gives them; ``precision="control"`` reads the
    reference computed one step lower in place of ``got``, and
    ``sel_growth_first_r``."""
    import jax
    d = mref.Dims.of(cfg_file)
    first = d.held[0]
    w = weights_moe.make(cfg_file, seed)
    sums, hidden = mref.calibrate(w, tokens, d, batch)
    if precision == "control":
        csums, chidden = mref.calibrate(w, tokens, d, batch, "control")
    mats = {}
    for (li, name, e) in got.picked:
        leaf = _leaf(w["groups"][li][0], name)[0]
        leaf = leaf if e is None else leaf[e - first]
        mats[(li, name, e)] = np.asarray(jax.device_get(leaf), np.float64)
    del w
    dist = ref.distances(hidden)
    layers = ref.select_layers(dist, wl["layers"])

    def rank(W):
        return ref.rank_for(*W.shape, wl["r_max"])

    if precision == "control":
        cdist = ref.distances(chidden)
        got = cure.PassOut(
            act_sq=csums, layers=ref.select_layers(cdist, wl["layers"]),
            distances=cdist,
            picked={k: ref.cur_weight(
                mats[k], _row(csums[k[0]], k[1], k[2], first),
                rank(mats[k]), "control") for k in got.picked})
    act_err = 0.0
    for li in range(d.n_layers):
        for name, a in got.act_sq[li].items():
            want = np.asarray(sums[li][name], np.float64)
            err = (np.linalg.norm(np.asarray(a, np.float64) - want, axis=-1)
                   / np.linalg.norm(want, axis=-1))
            act_err = max(act_err, float(np.max(err)))
    inner = np.sort(dist[1:-1])
    n = min(wl["layers"], len(inner))
    out = {"act_err": act_err,
           "layers_differ": float(len(set(got.layers) ^ set(layers))),
           "sel_growth": 0.0, "r_exact": 0.0, "cu_err": 0.0,
           "dist_err": float(np.max(np.abs(got.distances - dist))),
           "layer_margin": (float(inner[n] - inner[n - 1])
                            if n < len(inner) else float("inf"))}
    if precision == "control":
        out["sel_growth_first_r"] = 0.0
    for key, (p, q, cu, R) in got.picked.items():
        W = mats[key]
        r = rank(W)
        P, Q = ref.top_subspace(
            ref.scores(W, _row(sums[key[0]], key[1], key[2], first)), r)
        best = (ref.growth(P, ref.deim(P)), ref.growth(Q, ref.deim(Q)))
        out["sel_growth"] = max(out["sel_growth"],
                                ref.growth(P, p) / best[0],
                                ref.growth(Q, q) / best[1])
        if precision == "control":
            out["sel_growth_first_r"] = max(
                out["sel_growth_first_r"],
                ref.growth(P, np.arange(r)) / best[0],
                ref.growth(Q, np.arange(r)) / best[1])
        C = W[:, q]
        want = C @ (np.linalg.pinv(C) @ W @ np.linalg.pinv(W[p, :]))
        out["r_exact"] = max(out["r_exact"],
                             float(np.max(np.abs(R - W[p, :]))))
        out["cu_err"] = max(out["cu_err"], float(
            np.linalg.norm(cu - want) / np.linalg.norm(want)))
    return out


def run(ctx: harness.Context) -> harness.Outcome:
    import jax
    from repro import obs
    wl = ctx.workload
    cfg = model_config(ctx.config_file, wl["targets"])
    params = weights_moe.make(ctx.config_file, ctx.seed)
    tokens = cure.calib_tokens(ctx.config_file, ctx.traffic, ctx.seed)
    b = ctx.traffic["batch"]
    batches = [{"tokens": jax.device_put(tokens[i:i + b])}
               for i in range(0, len(tokens), b)]
    ccfg = cure.cur_config(wl)
    _plant(ctx.fault)
    plain = (lambda name: cure._Null())
    one_pass(params, cfg, batches, ccfg, plain)          # warm: set-up
    setup_s = ctx.clock() - ctx.t_process

    cap = tracer = None
    annotate = plain
    if ctx.trace:
        cap = tr.Capture(os.path.join(harness.SCRATCH_DIR, "trace",
                                      ctx.cell["name"]))
        annotate = tr.annotate
        tracer = obs.Tracer(enabled=True, process="chipbench")
        cap.start()
    spans = {"calibrate": [], "compress": []}

    def timed(name):
        return cure._Span(name, annotate(name), spans, ctx.clock)

    t0 = ctx.clock()
    n = 0
    while True:
        out = one_pass(params, cfg, batches, ccfg, timed, tracer)
        n += 1
        if ctx.clock() - t0 >= ctx.seconds:
            break
    t1 = ctx.clock()
    if cap is not None:
        cap.stop()
    mem = harness.memory_peak(ctx.devices)
    calib, cparams, info = out
    got = collect(calib, cparams, info,
                  draw(info, ctx.seed, wl["check_weights"]),
                  cfg.held_experts[0])
    del params, cparams, batches, out, calib
    gc.collect()
    reduction = None
    if cap is not None:
        reduction = tr.reduce(cap.load())
        cap.remove()
    nums = readings(ctx.config_file, wl, ctx.seed, tokens, b, got)
    checks = [harness.Check(k, nums[k], wl["limits"][k])
              for k in wl["limits"]]
    reading = {"spans": spans, "passes": n, "trace": reduction,
               "peaks": ctx.peaks, "workload": wl, "readings": nums,
               "program_spans": tracer.spans if tracer else None}
    if ctx.control:
        reading["control"] = readings(ctx.config_file, wl, ctx.seed, tokens,
                                      b, got, "control")
    return harness.Outcome(
        metrics={"setup_s": setup_s, "cure_s": (t1 - t0) / n},
        attempted=n, failed=0, checks=checks, reading=reading,
        memory_peak_bytes=mem, trace=reduction)
