"""The CURing compression pipeline (paper §4).

``compress_model``:
  1. angular-distance layer selection over the calibration hidden states
     (first/last layers excluded),
  2. per selected layer, per target weight: WANDA importance -> SVD
     (exact, or randomized beyond-paper path) -> DEIM row/col indices ->
     C = W[:, q], R = W[p, :], U0 = C+ W R+ , dU = 0,
  3. rebuild the model with per-layer (unrolled) groups so compressed and
     dense layers coexist.

Two execution pipelines (``CURConfig.pipeline``):

``"batched"`` (default) groups the selected weights by shape-class —
the 12 arch configs repeat the same (m, n) per target across layers —
and runs selection + decomposition for each class as ONE jitted, vmapped
call: batched WANDA scores -> batched SVD -> vmapped DEIM -> batched
pinv link solve. One host transfer per class instead of several per
weight; this is what makes one-shot CURing wall-clock competitive
(paper Table 1: Llama3.1-8B in 129 s).

The per-weight chain is named for the profiler (``jax.named_scope``):
``cure_svd`` (the WANDA scores and the selection SVD: the scores fuse
into the SVD's first fusions), ``cure_deim`` (both DEIM calls),
``cure_link`` (U = C+ W R+) and ``cure_check`` (the reconstruction
error and the Theorem 3.1 bound). A ``tracer`` passed to
``compress_model`` records the host sub-steps (``compress.distances``,
``compress.unroll``, ``compress.class`` with ``.stack`` and ``.wait``,
``compress.fold`` with ``.wait``).

``"loop"`` is the original per-weight reference path. Both consume the
same per-weight PRNG key stream (split in network order before
dispatch), so on a fixed seed they produce identical row/col selections
and link matrices — ``tests/test_compress.py`` enforces this.

Selection-strategy ablations (paper App. D.2) are first-class:
``wanda_deim`` (CURing) | ``wanda`` | ``deim`` | ``weight`` | ``random``.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import CURConfig, ModelConfig
from repro.core import angular
from repro.obs import compiles
from repro.obs import metrics as obs_metrics
from repro.obs.trace import NULL_TRACER
from repro.core.calibrate import CalibStats, iter_layer_params
from repro.core.cur import (
    cur_from_indices,
    exact_svd,
    randomized_svd,
    rank_for,
    spectral_error_bound,
)
from repro.core.deim import deim
from repro.core.wanda import wanda_scores


@dataclasses.dataclass
class WeightInfo:
    layer: int
    name: str
    shape: Tuple[int, int]
    rank: int
    rows: np.ndarray
    cols: np.ndarray
    fro_err: float          # ||W - CUR||_F
    fro_w: float            # ||W||_F
    bound: float            # Theorem 3.1 spectral bound (see bound_on)
    seconds: float
    params_before: int
    params_after: int       # the DEPLOYED form: folded iff cur_cfg.fold_u
    params_after_unfolded: int = 0  # m r + r^2 + r n   ({C, U0, dU, R})
    params_after_folded: int = 0    # m r + r n         ({CU, R})
    # which matrix the Theorem 3.1 bound is valid for: the WANDA
    # importance matrix S ("wanda"), the raw weight W ("weight"), or not
    # computed ("none"). wanda_deim selects indices on S's singular
    # vectors, so its bound holds for S — NOT for W.
    bound_on: str = "none"


@dataclasses.dataclass
class CompressInfo:
    distances: np.ndarray
    layers: List[int]
    weights: List[WeightInfo]
    seconds_total: float
    seconds_fold: float = 0.0   # portion spent folding C@U (fold_u only)

    @property
    def params_saved(self) -> int:
        """Savings of the deployed form (folded iff cur_cfg.fold_u)."""
        return sum(w.params_before - w.params_after for w in self.weights)

    @property
    def params_saved_unfolded(self) -> int:
        return sum(w.params_before - w.params_after_unfolded
                   for w in self.weights)

    @property
    def params_saved_folded(self) -> int:
        return sum(w.params_before - w.params_after_folded
                   for w in self.weights)


def _top_k_indices(scores: jnp.ndarray, r: int) -> jnp.ndarray:
    _, idx = jax.lax.top_k(scores, r)
    return jnp.sort(idx)


def select_indices(W: jnp.ndarray, r: int, method: str,
                   act_sq, key, svd_method: str = "exact"):
    """Pick r row indices p and r column indices q of W."""
    svd_fn = (exact_svd if svd_method == "exact"
              else lambda M, rr: randomized_svd(M, rr, key))
    aux = {}
    if method == "wanda_deim":
        with jax.named_scope("cure_svd"):
            S = wanda_scores(W, jnp.asarray(act_sq))
            P, sig, Q = svd_fn(S, min(r + 1, min(W.shape)))
        with jax.named_scope("cure_deim"):
            p, q = deim(P[:, :r]), deim(Q[:, :r])
        aux = {"P": P, "Q": Q, "sig": sig}
    elif method == "wanda":
        S = wanda_scores(W, jnp.asarray(act_sq))
        p = _top_k_indices(jnp.linalg.norm(S, axis=1), r)
        q = _top_k_indices(jnp.linalg.norm(S, axis=0), r)
    elif method == "deim":
        with jax.named_scope("cure_svd"):
            P, sig, Q = svd_fn(W.astype(jnp.float32),
                               min(r + 1, min(W.shape)))
        with jax.named_scope("cure_deim"):
            p, q = deim(P[:, :r]), deim(Q[:, :r])
        aux = {"P": P, "Q": Q, "sig": sig}
    elif method == "weight":
        Wf = W.astype(jnp.float32)
        p = _top_k_indices(jnp.linalg.norm(Wf, axis=1), r)
        q = _top_k_indices(jnp.linalg.norm(Wf, axis=0), r)
    elif method == "random":
        k1, k2 = jax.random.split(key)
        p = jax.random.choice(k1, W.shape[0], (r,), replace=False)
        q = jax.random.choice(k2, W.shape[1], (r,), replace=False)
    else:
        raise ValueError(method)
    return p, q, aux


def _bound_on(selection: str) -> str:
    return {"wanda_deim": "wanda", "deim": "weight"}.get(selection, "none")


def rank_key(layer: int, name: str) -> str:
    """The ``CURConfig.ranks`` / ``CompressionPlan.ranks`` key format."""
    return f"{layer}:{name}"


def resolve_rank(m: int, n: int, layer: int, name: str,
                 cur_cfg: CURConfig) -> int:
    """Per-weight rank: the ``cur_cfg.ranks`` override when present
    (repro.plan allocations), else the uniform Eq. 2 cap."""
    if cur_cfg.ranks:
        r = cur_cfg.ranks.get(rank_key(layer, name))
        if r is not None:
            return int(r)
    return rank_for(m, n, cur_cfg.r_max)


def _validate_ranks(params, cfg: ModelConfig, cur_cfg: CURConfig,
                    layer_set) -> None:
    """Every override key must name a still-dense 2-D weight in the target
    set, lie in a selected layer, and carry a feasible rank."""
    if not cur_cfg.ranks:
        return
    valid: Dict[str, Tuple[int, int]] = {}
    for li, spec, lp in iter_layer_params(params, cfg):
        for t in cfg.cur_targets:
            W = lp.get(t)
            if W is None or isinstance(W, dict) or W.ndim != 2:
                continue
            valid[rank_key(li, t)] = W.shape
    for k, r in cur_cfg.ranks.items():
        if k not in valid:
            raise ValueError(
                f"rank override {k!r} does not name a compressible target "
                f"weight (targets: {cfg.cur_targets})")
        m, n = valid[k]
        if not 1 <= int(r) <= min(m, n):
            raise ValueError(
                f"rank override {k!r}={r} outside [1, min{(m, n)}]")
        if int(k.split(":")[0]) not in layer_set:
            raise ValueError(
                f"rank override {k!r} targets a layer not being compressed "
                f"(selected: {sorted(layer_set)})")


def _param_counts(m: int, n: int, r: int, fold_u: bool):
    """(before, after_unfolded, after_folded, after_deployed)."""
    unfolded = m * r + r * r + r * n
    folded = m * r + r * n
    return m * n, unfolded, folded, (folded if fold_u else unfolded)


def compress_weight(W: jnp.ndarray, name: str, layer: int,
                    cur_cfg: CURConfig, act_sq: Optional[np.ndarray],
                    key, rank: Optional[int] = None) -> Tuple[dict, WeightInfo]:
    """Single-weight reference path (also the ``pipeline="loop"`` body)."""
    t0 = time.perf_counter()
    m, n = W.shape
    r = rank if rank is not None else resolve_rank(m, n, layer, name, cur_cfg)
    p, q, aux = select_indices(W, r, cur_cfg.selection, act_sq, key,
                               cur_cfg.svd)
    C, U, R = cur_from_indices(W.astype(jnp.float32), p, q)
    approx_err = float(jnp.linalg.norm(W.astype(jnp.float32) - C @ U @ R))
    bound = float("nan")
    if "P" in aux and aux["sig"].shape[0] > r:
        bound = float(spectral_error_bound(
            aux["P"][:, :r], aux["Q"][:, :r], aux["sig"], p, q))
    dt = time.perf_counter() - t0
    obs_metrics.histogram(
        "repro_compress_weight_s",
        "per-weight CUR time (loop pipeline / reference path)").observe(dt)
    leaf = {
        "C": C.astype(W.dtype),
        "U0": U.astype(jnp.float32),
        "dU": jnp.zeros_like(U, jnp.float32),
        "R": R.astype(W.dtype),
    }
    before, unfolded, folded, deployed = _param_counts(
        m, n, r, cur_cfg.fold_u)
    info = WeightInfo(
        layer=layer, name=name, shape=(m, n), rank=r,
        rows=np.asarray(p), cols=np.asarray(q),
        fro_err=approx_err, fro_w=float(jnp.linalg.norm(W)),
        bound=bound, seconds=dt,
        params_before=before, params_after=deployed,
        params_after_unfolded=unfolded, params_after_folded=folded,
        bound_on=_bound_on(cur_cfg.selection))
    return leaf, info


# ---------------------------------------------------------------------------
# batched pipeline
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _WorkItem:
    layer: int
    name: str
    W: jnp.ndarray
    act: Optional[np.ndarray]
    key: jax.Array
    rank: int = 0


@functools.partial(jax.jit, static_argnames=("r", "selection", "svd"))
def _compress_class_batched(Ws, acts, keys, *, r: int, selection: str,
                            svd: str):
    """One shape-class: Ws (k, m, n), acts (k, m), keys (k,) PRNG keys.
    vmaps the whole per-weight chain — selection SVD, DEIM, pinv link
    solve, reconstruction error, Theorem 3.1 bound — into one XLA call."""

    def one(W, act, key):
        p, q, aux = select_indices(W, r, selection, act, key, svd)
        with jax.named_scope("cure_link"):
            Wf = W.astype(jnp.float32)
            C, U, R = cur_from_indices(Wf, p, q)
        with jax.named_scope("cure_check"):
            err = jnp.linalg.norm(Wf - C @ U @ R)
            if "P" in aux and aux["sig"].shape[0] > r:
                bound = spectral_error_bound(
                    aux["P"][:, :r], aux["Q"][:, :r], aux["sig"], p, q)
            else:
                bound = jnp.float32(jnp.nan)
            frow = jnp.linalg.norm(W)
        return {"p": p, "q": q, "C": C, "U": U, "R": R, "err": err,
                "frow": frow, "bound": bound}

    return jax.vmap(one)(Ws, acts, keys)


def _compress_batched(work: List[_WorkItem], cur_cfg: CURConfig, tracer):
    """Run the work list grouped by (m, n, r) shape-class; returns
    (leaf, WeightInfo) per item, in work-list order. The rank joins the
    class key so per-weight overrides (``CURConfig.ranks``) batch
    correctly — same-shape weights at different planned ranks land in
    different vmapped calls.

    ``WeightInfo.seconds`` is the class's time over its weights; on a
    process's first call of a class that includes the compile (the
    ``compress.class`` span's ``programs`` attr counts the programs
    obtained)."""
    classes: Dict[Tuple[int, int, int], List[int]] = {}
    for i, it in enumerate(work):
        classes.setdefault(tuple(it.W.shape) + (it.rank,), []).append(i)

    results: List[Optional[Tuple[dict, WeightInfo]]] = [None] * len(work)
    for (m, n, r), idxs in classes.items():
        t0 = time.perf_counter()
        with tracer.span("compress.class", m=m, n=n, r=r,
                         k=len(idxs)) as span:
            if tracer.enabled:
                programs0 = compiles.jit_programs()[0]
            with tracer.span("compress.class.stack"):
                Ws = jnp.stack([work[i].W for i in idxs])
                acts = jnp.stack([
                    jnp.asarray(work[i].act, jnp.float32)
                    if work[i].act is not None
                    else jnp.zeros((m,), jnp.float32) for i in idxs])
                keys = jnp.stack([work[i].key for i in idxs])
            out = _compress_class_batched(
                Ws, acts, keys, r=r, selection=cur_cfg.selection,
                svd=cur_cfg.svd)
            # ONE host transfer per class for the scalar/index fields;
            # the big factors stay device-resident in the returned leaves
            with tracer.span("compress.class.wait"):
                ps, qs, errs, frows, bounds = jax.device_get(
                    (out["p"], out["q"], out["err"], out["frow"],
                     out["bound"]))
            if tracer.enabled:
                span.set(programs=compiles.jit_programs()[0] - programs0)
        dt = (time.perf_counter() - t0) / len(idxs)
        # per-shape-class timing; the label space is open-ended but
        # small in practice, so overflow degrades to NULL instead of
        # raising mid-compression
        obs_metrics.default_registry().histogram(
            "repro_compress_class_s",
            "per-weight seconds by (m,n,r) shape-class (a process's "
            "first call of a class includes its compile)",
            labels=("shape",), overflow="drop").labels(
            shape=f"{m}x{n}r{r}").observe(dt)
        before, unfolded, folded, deployed = _param_counts(
            m, n, r, cur_cfg.fold_u)
        for k, i in enumerate(idxs):
            it = work[i]
            leaf = {
                "C": out["C"][k].astype(it.W.dtype),
                "U0": out["U"][k],
                "dU": jnp.zeros_like(out["U"][k]),
                "R": out["R"][k].astype(it.W.dtype),
            }
            info = WeightInfo(
                layer=it.layer, name=it.name, shape=(m, n), rank=r,
                rows=ps[k], cols=qs[k],
                fro_err=float(errs[k]), fro_w=float(frows[k]),
                bound=float(bounds[k]), seconds=dt,
                params_before=before, params_after=deployed,
                params_after_unfolded=unfolded, params_after_folded=folded,
                bound_on=_bound_on(cur_cfg.selection))
            results[i] = (leaf, info)
    return results


def fold_cur(leaf: dict) -> dict:
    """Deploy-time fold: C' = C @ (U0 + dU) — halves the matmul chain."""
    cu = leaf["C"].astype(jnp.float32) @ (leaf["U0"] + leaf["dU"])
    return {"CU": cu.astype(leaf["C"].dtype), "R": leaf["R"]}


def unrolled_config(cfg: ModelConfig) -> ModelConfig:
    """Per-layer groups so compressed/dense layers can differ in structure."""
    groups = tuple(((spec,), 1) for spec in cfg.blocks)
    return cfg.replace(groups=groups, scan_layers=False)


def unroll_params(params, cfg: ModelConfig):
    """Restructure params to match ``unrolled_config``."""
    new = {k: v for k, v in params.items() if k != "groups"}
    new["groups"] = []
    for li, spec, lp in iter_layer_params(params, cfg):
        stacked = jax.tree.map(lambda a: a[None], lp)
        new["groups"].append([stacked])
    return new


def _cur_work_list(params, cfg: ModelConfig, cur_cfg: CURConfig,
                   calib: CalibStats, layer_set) -> List[_WorkItem]:
    """Enumerate compressible weights in network order, assigning each
    its PRNG key by splitting in that same order — the key stream is
    therefore identical for the loop and batched pipelines."""
    key = jax.random.PRNGKey(cur_cfg.seed)
    work: List[_WorkItem] = []
    for li, spec, lp in iter_layer_params(params, cfg):
        if li not in layer_set:
            continue
        for t in cfg.cur_targets:
            if t not in lp:
                continue
            W = lp[t]
            if isinstance(W, dict):              # already CUR-compressed
                continue                         # (progressive later round)
            if W.ndim != 2:                      # (e.g. MoE expert stacks)
                continue
            if cur_cfg.ranks and rank_key(li, t) not in cur_cfg.ranks:
                # a ranks map IS the complete allocation (a plan): weights
                # it omits — e.g. too small for any profiled rank to save
                # params — stay dense, so the executed compression matches
                # the plan's realized-budget accounting exactly
                continue
            key, sub = jax.random.split(key)
            act = calib.act_sq[li].get(t) if calib.act_sq else None
            if act is None and cur_cfg.selection in ("wanda_deim", "wanda"):
                raise ValueError(
                    f"no calibration activations for layer {li} weight {t}")
            work.append(_WorkItem(li, t, W, act, sub,
                                  resolve_rank(W.shape[0], W.shape[1],
                                               li, t, cur_cfg)))
    return work


def compress_model(params, cfg: ModelConfig, cur_cfg: CURConfig,
                   calib: CalibStats, layers: Optional[List[int]] = None,
                   tracer=None):
    """Returns (new_params, new_cfg, CompressInfo).

    ``tracer`` records the sub-steps (module docstring); the caller's
    span around the call names the stage. ``CompressInfo.seconds_fold``
    is the ``compress.fold`` span: every fold dispatched, then one wait
    for all of them."""
    tracer = tracer or NULL_TRACER
    t_start = time.perf_counter()
    with tracer.span("compress.distances"):
        distances = angular.layer_distances(calib.hidden)
        if layers is None:
            layers = angular.select_layers(
                distances, cur_cfg.n_compress_layers,
                cur_cfg.layer_selection, cur_cfg.seed)
    layer_set = set(layers)
    with tracer.span("compress.unroll"):
        _validate_ranks(params, cfg, cur_cfg, layer_set)
        new_cfg = unrolled_config(cfg)
        new_params = unroll_params(params, cfg)
        work = _cur_work_list(params, cfg, cur_cfg, calib, layer_set)

    if cur_cfg.pipeline == "loop":
        results = [compress_weight(it.W, it.name, it.layer, cur_cfg,
                                   it.act, it.key, rank=it.rank)
                   for it in work]
    elif cur_cfg.pipeline == "batched":
        results = _compress_batched(work, cur_cfg, tracer)
    else:
        raise ValueError(cur_cfg.pipeline)

    # Eq. 2 guard, deployed form
    kept = [(it, leaf, info) for it, (leaf, info) in zip(work, results)
            if info.params_after < info.params_before]
    seconds_fold = 0.0
    if cur_cfg.fold_u:
        t_fold = time.perf_counter()
        with tracer.span("compress.fold", k=len(kept)):
            kept = [(it, fold_cur(leaf), info) for it, leaf, info in kept]
            with tracer.span("compress.fold.wait"):
                jax.block_until_ready([leaf["CU"] for _, leaf, _ in kept])
        seconds_fold = time.perf_counter() - t_fold
    for it, leaf, info in kept:
        block = new_params["groups"][it.layer][0]
        block[it.name] = jax.tree.map(lambda a: a[None], leaf)
    infos: List[WeightInfo] = [info for _, _, info in kept]

    cinfo = CompressInfo(
        distances=distances, layers=sorted(layer_set), weights=infos,
        seconds_total=time.perf_counter() - t_start,
        seconds_fold=seconds_fold)
    obs_metrics.counter(
        "repro_compress_time_s_total",
        "compress_model wall seconds").inc(cinfo.seconds_total)
    obs_metrics.counter(
        "repro_compress_fold_time_s_total",
        "seconds folding C@U").inc(seconds_fold)
    obs_metrics.counter(
        "repro_compress_weights_total",
        "weights CUR-compressed").inc(len(infos))
    return new_params, new_cfg, cinfo
