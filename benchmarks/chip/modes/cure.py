"""A CURing cell: calibrate, then compress and fold, as
``repro.launch.cure`` calls them, back to back for the window.

Set-up makes the weights and the calibration tokens from the seed and
runs one whole pass (it compiles, and ``_compress_batched`` runs each
shape class twice on its first call in a process). The window runs
passes until ``--seconds`` have passed, at least one; ``cure_s`` is the
time from the start of the first to the end of the last, over the
number of passes. Each pass ends in host transfers (the calibration
statistics) and ``block_until_ready`` on the folded weights.

Correctness compares the last pass with the reference
(``reference/cure.py``):

- ``act_err``: the calibration statistics of every layer, the worst
  relative error of any layer's sums;
- ``layers_differ``: the layers the program compressed against those the
  reference's angular distances pick (exact);
- for weights drawn from the seed, ``sel_growth``: the DEIM growth
  factor of the rows and columns the program selected, on the bases of
  the reference's own float64 singular subspaces, over that of the
  reference's own DEIM selection (the worst of rows and columns). The
  indices themselves are not compared: on random weights the singular
  values lie too close for two SVDs to give DEIM the same pivots, while
  a selection that is not DEIM on the leading subspace (the first r
  indices, a sloppy SVD) reads several times higher;
- ``r_exact``: ``R`` against the rows of W the program selected (exact);
- ``cu_err``: the folded ``CU`` against ``W[:, q] C+ W R+`` on the
  columns it selected.
"""
from __future__ import annotations

import dataclasses
import gc
import os

import numpy as np

from benchmarks.chip import harness, weights
from benchmarks.chip import trace as tr
from benchmarks.chip.reference import cure as ref
from benchmarks.chip.reference import decoder as dec

_ATTN_IN = ("wq", "wk", "wv")


def calib_tokens(cfg_file: dict, mix: dict, seed: int) -> np.ndarray:
    rng = np.random.default_rng([int(seed), 2])
    return rng.integers(0, cfg_file["vocab_size"],
                        (mix["sequences"], mix["length"]), dtype=np.int32)


def cur_config(wl: dict):
    from repro.configs.base import CURConfig
    return CURConfig(r_max=wl["r_max"], n_compress_layers=wl["layers"],
                     selection=wl["selection"], svd=wl["svd"],
                     fold_u=True, seed=0)


@dataclasses.dataclass
class PassOut:
    act_sq: list            # per layer: name -> (m,) float
    layers: list            # the layers compressed
    distances: np.ndarray   # (L,) angular distance per layer
    picked: dict            # (layer, name) -> (rows, cols, CU, R) numpy


def _plant(fault):
    """Test hook: break the timed path underneath the harness: a row of
    ``R`` altered (answer), the first r indices selected (selection), or
    the layers of largest distance compressed (layers)."""
    from repro.core import angular, compress
    if fault == "answer":
        inner = compress.cur_from_indices

        def altered(W, p, q):
            C, U, R = inner(W, p, q)
            return C, U, R.at[0].set(W[(p[0] + 1) % W.shape[0]])
        compress.cur_from_indices = altered
    elif fault == "selection":
        import jax.numpy as jnp

        def first(W, r, *args, **kw):
            return jnp.arange(r), jnp.arange(r), {}
        compress.select_indices = first
    elif fault == "layers":
        inner_layers = angular.select_layers

        def farthest(distances, n, *args, **kw):
            return inner_layers(-distances, n, *args, **kw)
        angular.select_layers = farthest
    elif fault is not None:
        raise ValueError(f"no fault {fault!r} for a CURing cell")


def one_pass(params, cfg, batches, ccfg, annotate):
    import jax
    from repro.core import calibrate, compress_model
    with annotate("calibrate"):
        calib = calibrate(params, cfg, batches)
    with annotate("compress"):
        cparams, _, info = compress_model(params, cfg, ccfg, calib)
        jax.block_until_ready(cparams)
    return calib, cparams, info


def collect(calib, cparams, info, sample) -> PassOut:
    """The last pass's answers, on the host."""
    import jax
    picked = {}
    by_key = {(w.layer, w.name): w for w in info.weights}
    for key in sample:
        w = by_key[key]
        leaf = cparams["groups"][key[0]][0][key[1]]
        cu, r = jax.device_get((leaf["CU"][0], leaf["R"][0]))
        picked[key] = (np.asarray(w.rows), np.asarray(w.cols),
                       np.asarray(cu, np.float64), np.asarray(r, np.float64))
    return PassOut(act_sq=calib.act_sq, layers=list(info.layers),
                   distances=np.asarray(info.distances, np.float64),
                   picked=picked)


def draw(info, seed: int, n: int):
    """Weights to compare: n of the compressed ones, drawn from the seed,
    one of each target name first."""
    keys = sorted((w.layer, w.name) for w in info.weights)
    rng = np.random.default_rng([int(seed), 3])
    order = [keys[int(i)] for i in rng.permutation(len(keys))]
    out = []
    for name in sorted({k[1] for k in keys}):
        out.append(next(k for k in order if k[1] == name))
    out += [k for k in order if k not in out]
    return sorted(out[:n])


def readings(cfg_file: dict, wl: dict, seed: int, tokens: np.ndarray,
             batch: int, got: PassOut, precision: str = "f32") -> dict:
    """Every number compared, for ``got`` against the float32 reference,
    and beside them readings that are not compared (``dist_err``, the
    program's worst layer distance error, and ``layer_margin``, the
    reference's gap between the last layer it picks and the next).
    ``precision="control"`` replaces ``got`` by the reference computed one
    step lower (the control) and reads the same numbers for it, and also
    ``sel_growth_first_r``: the selection fault of the first r indices."""
    import jax
    d = dec.Dims.of(cfg_file)
    w = weights.make(cfg_file, seed)
    sq1, sq2, hidden = ref.calibrate(w, tokens, d, batch)
    if precision == "control":
        c1, c2, chidden = ref.calibrate(w, tokens, d, batch, "control")
    stacked = jax.device_get(w["groups"][0][0])
    del w
    dist = ref.distances(hidden)
    layers = ref.select_layers(dist, wl["layers"])

    def weight(li, name):
        return np.asarray(stacked[name][li], np.float64)

    def act(s1, s2, li, name):
        return s1[li] if name in _ATTN_IN else s2[li]

    def rank(W):
        return ref.rank_for(*W.shape, wl["r_max"])

    if precision == "control":
        cdist = ref.distances(chidden)
        got = PassOut(
            act_sq=[{"wq": c1[i], "wk": c1[i], "w_gate": c2[i]}
                    for i in range(d.n_layers)],
            layers=ref.select_layers(cdist, wl["layers"]), distances=cdist,
            picked={(li, name): ref.cur_weight(
                weight(li, name), act(c1, c2, li, name),
                rank(weight(li, name)), "control")
                for li, name in got.picked})
    act_err = max(float(np.linalg.norm(a - act(sq1, sq2, li, name))
                        / np.linalg.norm(act(sq1, sq2, li, name)))
                  for li in range(d.n_layers)
                  for name, a in got.act_sq[li].items())
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
    for (li, name), (p, q, cu, R) in got.picked.items():
        W = weight(li, name)
        r = rank(W)
        P, Q = ref.top_subspace(ref.scores(W, act(sq1, sq2, li, name)), r)
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
    wl = ctx.workload
    cfg = harness.model_config(ctx.config_file)
    params = weights.make(ctx.config_file, ctx.seed)
    tokens = calib_tokens(ctx.config_file, ctx.traffic, ctx.seed)
    b = ctx.traffic["batch"]
    batches = [{"tokens": jax.device_put(tokens[i:i + b])}
               for i in range(0, len(tokens), b)]
    ccfg = cur_config(wl)
    _plant(ctx.fault)
    plain = (lambda name: _Null())
    one_pass(params, cfg, batches, ccfg, plain)          # warm: set-up
    setup_s = ctx.clock() - ctx.t_process

    cap = None
    annotate = plain
    if ctx.trace:
        cap = tr.Capture(os.path.join(harness.SCRATCH_DIR, "trace",
                                      ctx.cell["name"]))
        annotate = tr.annotate
        cap.start()
    spans = {"calibrate": [], "compress": []}

    def timed(name):
        return _Span(name, annotate(name), spans, ctx.clock)

    t0 = ctx.clock()
    n = 0
    while True:
        out = one_pass(params, cfg, batches, ccfg, timed)
        n += 1
        if ctx.clock() - t0 >= ctx.seconds:
            break
    t1 = ctx.clock()
    if cap is not None:
        cap.stop()
    mem = harness.memory_peak(ctx.devices)
    calib, cparams, info = out
    got = collect(calib, cparams, info, draw(info, ctx.seed,
                                             wl["check_weights"]))
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
               "peaks": ctx.peaks, "workload": wl, "readings": nums}
    if ctx.control:
        reading["control"] = readings(ctx.config_file, wl, ctx.seed, tokens,
                                      b, got, "control")
    return harness.Outcome(
        metrics={"setup_s": setup_s, "cure_s": (t1 - t0) / n},
        attempted=n, failed=0, checks=checks, reading=reading,
        memory_peak_bytes=mem, trace=reduction)


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


class _Span:
    """Host-clock span around a call into a layer, also named in the
    profiler trace when one is being taken."""

    def __init__(self, name, ann, spans, clock):
        self.name, self.ann, self.spans, self.clock = name, ann, spans, clock

    def __enter__(self):
        self.ann.__enter__()
        self.t0 = self.clock()
        return self

    def __exit__(self, *exc):
        self.spans[self.name].append(self.clock() - self.t0)
        self.ann.__exit__(*exc)
        return False
