"""The CURing compression pipeline (paper §4).

``compress_model``:
  1. angular-distance layer selection over the calibration hidden states
     (first/last layers excluded),
  2. per selected layer, per target weight: WANDA importance -> SVD
     (exact, or randomized beyond-paper path) -> DEIM row/col indices ->
     C = W[:, q], R = W[p, :], U0 = C+ W R+ , dU = 0,
  3. rebuild the model with per-layer (unrolled) groups so compressed and
     dense layers coexist.

Targets are names in ``cfg.cur_targets``. A dotted name reaches into a
sub-dict (``shared.w_gate``: the shared experts' gate). In an MoE layer
a target that holds an expert stack ``(E_held, m, n)`` (``w_gate``)
gives one work item per held expert, with its own routed activations
and its own PRNG key; the results are folded back into one CUR stack
per layer (``{"CU": (E, m, r), "R": (E, r, n)}``), which the MoE layer
runs through ``_expert_mm``. Ranks of one stack that differ (a plan)
are padded with zeros to the largest, which leaves the product as it is.

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
``compress.unroll``, ``compress.class`` with ``.stack`` and ``.wait``
and an ``experts`` attribute counting its expert items,
``compress.fold`` with ``.wait``, and ``compress.experts`` with
``.wait``: the expert stacks' assembly and fold).

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
from repro.core.calibrate import CalibStats
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
    # the router's index of the expert this weight belongs to (None for
    # a weight outside an expert stack)
    expert: Optional[int] = None

    @property
    def key(self) -> str:
        return rank_key(self.layer, self.name, self.expert)


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


def rank_key(layer: int, name: str, expert: Optional[int] = None) -> str:
    """The ``CURConfig.ranks`` / ``CompressionPlan.ranks`` key format:
    ``"3:wq"``, ``"3:shared.w_gate"``, and ``"3:w_gate[5]"`` for the
    router's expert 5 of layer 3."""
    if expert is None:
        return f"{layer}:{name}"
    return f"{layer}:{name}[{expert}]"


def resolve_rank(m: int, n: int, layer: int, name: str,
                 cur_cfg: CURConfig, expert: Optional[int] = None) -> int:
    """Per-weight rank: the ``cur_cfg.ranks`` override when present
    (repro.plan allocations), else the uniform Eq. 2 cap."""
    if cur_cfg.ranks:
        r = cur_cfg.ranks.get(rank_key(layer, name, expert))
        if r is not None:
            return int(r)
    return rank_for(m, n, cur_cfg.r_max)


def _get_target(block: dict, name: str):
    """The leaf a target names (``shared.w_gate`` -> block["shared"]
    ["w_gate"]), or None."""
    for part in name.split("."):
        if not isinstance(block, dict) or part not in block:
            return None
        block = block[part]
    return block


def _set_target(block: dict, name: str, leaf) -> None:
    """Put ``leaf`` where ``name`` points, copying the sub-dicts on the
    way so a dict shared with another tree is never written."""
    *outer, last = name.split(".")
    for part in outer:
        block[part] = dict(block[part])
        block = block[part]
    block[last] = leaf


def _layer_targets(params, cfg: ModelConfig):
    """Yield (layer, target, stacked leaf, index in the group's stack) in
    network order, for every still-dense target of every layer, without
    slicing anything: a 2-D weight's leaf is (reps, m, n), an expert
    stack's (reps, E_held, m, n)."""
    li = 0
    for gi, (pattern, reps) in enumerate(cfg.groups):
        gp = params["groups"][gi]
        for r in range(reps):
            for pi, _ in enumerate(pattern):
                for t in cfg.cur_targets:
                    leaf = _get_target(gp[pi], t)
                    # absent, or already CUR-compressed (a progressive
                    # later round)
                    if leaf is None or isinstance(leaf, dict):
                        continue
                    yield li, t, leaf, r
                li += 1


def _held_ids(cfg: ModelConfig):
    return range(*cfg.held_experts)


def _validate_ranks(params, cfg: ModelConfig, cur_cfg: CURConfig,
                    layer_set) -> None:
    """Every override key must name a still-dense target weight (an
    expert by its ``[e]`` key), lie in a selected layer, and carry a
    feasible rank; a layer's expert stack is listed whole or not at
    all."""
    if not cur_cfg.ranks:
        return
    valid: Dict[str, Tuple[int, int]] = {}
    stacks: Dict[str, List[str]] = {}
    for li, t, leaf, _ in _layer_targets(params, cfg):
        if leaf.ndim == 3:
            valid[rank_key(li, t)] = leaf.shape[1:]
        elif leaf.ndim == 4:
            keys = [rank_key(li, t, e) for e in _held_ids(cfg)]
            stacks[rank_key(li, t)] = keys
            valid.update({k: leaf.shape[2:] for k in keys})
    for stack, keys in stacks.items():
        listed = sum(k in cur_cfg.ranks for k in keys)
        if listed not in (0, len(keys)):
            raise ValueError(
                f"rank overrides list {listed} of the {len(keys)} held "
                f"experts of {stack!r}: list all of them or none")
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
    expert: Optional[int] = None     # the router's index, in a stack


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


# The most elements (k m n) that one vmapped class call takes: the largest
# class of olmo-1b's CURe (10 w_gate of 2048 x 8192). A larger class runs
# in equal chunks: one chip's 80 expert gates of Moonlight-16B-A3B (2048 x
# 1408) would need 8 GB of SVD workspace in one call, beside 6.7 GB of
# weights on a 16 GB v5e.
CLASS_MAX_ELEMENTS = 10 * 2048 * 8192


def _compress_batched(work: List[_WorkItem], cur_cfg: CURConfig, tracer):
    """Run the work list grouped by (m, n, r) shape-class; returns
    (leaf, WeightInfo) per item, in work-list order. The rank joins the
    class key so per-weight overrides (``CURConfig.ranks``) batch
    correctly — same-shape weights at different planned ranks land in
    different vmapped calls.

    A class of more than ``CLASS_MAX_ELEMENTS`` runs as that many equal
    chunks, one call each (the span's ``chunks`` attr).

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
        n_experts = sum(work[i].expert is not None for i in idxs)
        n_chunks = -(-len(idxs) * m * n // CLASS_MAX_ELEMENTS)
        size = -(-len(idxs) // n_chunks)
        chunks = [idxs[c:c + size] for c in range(0, len(idxs), size)]
        with tracer.span("compress.class", m=m, n=n, r=r,
                         k=len(idxs), experts=n_experts,
                         chunks=len(chunks)) as span:
            if tracer.enabled:
                programs0 = compiles.jit_programs()[0]
            done = []
            for chunk in chunks:
                with tracer.span("compress.class.stack"):
                    Ws = jnp.stack([work[i].W for i in chunk])
                    acts = jnp.stack([
                        jnp.asarray(work[i].act, jnp.float32)
                        if work[i].act is not None
                        else jnp.zeros((m,), jnp.float32) for i in chunk])
                    keys = jnp.stack([work[i].key for i in chunk])
                out = _compress_class_batched(
                    Ws, acts, keys, r=r, selection=cur_cfg.selection,
                    svd=cur_cfg.svd)
                del Ws
                # ONE host transfer per call for the scalar/index fields;
                # the big factors stay device-resident in the leaves
                with tracer.span("compress.class.wait"):
                    done.append((chunk, out, jax.device_get(
                        (out["p"], out["q"], out["err"], out["frow"],
                         out["bound"]))))
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
        for chunk, out, (ps, qs, errs, frows, bounds) in done:
            for k, i in enumerate(chunk):
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
                    params_after_unfolded=unfolded,
                    params_after_folded=folded,
                    bound_on=_bound_on(cur_cfg.selection), expert=it.expert)
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
    """Restructure params to match ``unrolled_config``. A group that
    already holds one layer hands its leaves over as they are (each
    block in a dict of its own, so writing a compressed leaf into the
    result leaves ``params`` alone); a stacked group is sliced."""
    new = {k: v for k, v in params.items() if k != "groups"}
    new["groups"] = []
    for gi, (pattern, reps) in enumerate(cfg.groups):
        if reps == 1:
            new["groups"].extend([dict(block)]
                                 for block in params["groups"][gi])
            continue
        for r in range(reps):
            for block in params["groups"][gi]:
                lp = jax.tree.map(lambda a: a[r], block)
                new["groups"].append([jax.tree.map(lambda a: a[None], lp)])
    return new


def _cur_work_list(params, cfg: ModelConfig, cur_cfg: CURConfig,
                   calib: CalibStats, layer_set) -> List[_WorkItem]:
    """Enumerate compressible weights in network order, assigning each
    its PRNG key by splitting in that same order — the key stream is
    therefore identical for the loop and batched pipelines."""
    key = jax.random.PRNGKey(cur_cfg.seed)
    work: List[_WorkItem] = []
    for li, t, leaf, r in _layer_targets(params, cfg):
        if li not in layer_set or leaf.ndim not in (3, 4):
            continue
        experts = _held_ids(cfg) if leaf.ndim == 4 else [None]
        for j, e in enumerate(experts):
            if cur_cfg.ranks and rank_key(li, t, e) not in cur_cfg.ranks:
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
            W = leaf[r] if e is None else leaf[r, j]
            if e is not None and act is not None:
                act = act[j]
            work.append(_WorkItem(li, t, W, act, sub,
                                  resolve_rank(W.shape[0], W.shape[1],
                                               li, t, cur_cfg, e), e))
    return work


def _stack_experts(leaves: List[dict]) -> dict:
    """Healing-form leaves of one layer's held experts, in order -> one
    stacked leaf; ranks that differ are zero-padded to the largest."""
    r = max(leaf["R"].shape[0] for leaf in leaves)

    def pad(a, axes):
        if all(a.shape[i] == r for i in axes):
            return a
        return jnp.pad(a, [(0, r - a.shape[i]) if i in axes else (0, 0)
                           for i in range(a.ndim)])
    return {"C": jnp.stack([pad(x["C"], (1,)) for x in leaves]),
            "U0": jnp.stack([pad(x["U0"], (0, 1)) for x in leaves]),
            "dU": jnp.stack([pad(x["dU"], (0, 1)) for x in leaves]),
            "R": jnp.stack([pad(x["R"], (0,)) for x in leaves])}


def _stack_params(stack: dict, fold_u: bool) -> int:
    """Parameters a stacked leaf deploys (padding included)."""
    E, m, r = stack["C"].shape
    n = stack["R"].shape[2]
    return E * _param_counts(m, n, r, fold_u)[3]


def compress_model(params, cfg: ModelConfig, cur_cfg: CURConfig,
                   calib: CalibStats, layers: Optional[List[int]] = None,
                   tracer=None):
    """Returns (new_params, new_cfg, CompressInfo).

    ``tracer`` records the sub-steps (module docstring); the caller's
    span around the call names the stage. ``CompressInfo.seconds_fold``
    is the ``compress.fold`` span: every fold dispatched, then one wait
    for all of them; with expert stacks, and the fold on, it adds the
    ``compress.experts`` span (the stacks' assembly and fold)."""
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
        for it, (_, info) in zip(work, results):
            info.expert = it.expert
    elif cur_cfg.pipeline == "batched":
        results = _compress_batched(work, cur_cfg, tracer)
    else:
        raise ValueError(cur_cfg.pipeline)

    # Eq. 2 guard, deployed form: per weight, and per stack of experts
    kept = [(it, leaf, info) for it, (leaf, info) in zip(work, results)
            if it.expert is None and info.params_after < info.params_before]
    stacks: Dict[Tuple[int, str], list] = {}
    for it, (leaf, info) in zip(work, results):
        if it.expert is not None:
            stacks.setdefault((it.layer, it.name), []).append((leaf, info))
    seconds_fold = 0.0
    if cur_cfg.fold_u:
        t_fold = time.perf_counter()
        with tracer.span("compress.fold", k=len(kept)):
            kept = [(it, fold_cur(leaf), info) for it, leaf, info in kept]
            with tracer.span("compress.fold.wait"):
                jax.block_until_ready([leaf["CU"] for _, leaf, _ in kept])
        seconds_fold = time.perf_counter() - t_fold
    for it, leaf, info in kept:
        _set_target(new_params["groups"][it.layer][0], it.name,
                    jax.tree.map(lambda a: a[None], leaf))
    kept_ids = {id(info) for _, _, info in kept}
    if stacks:
        t_exp = time.perf_counter()
        with tracer.span("compress.experts", k=len(stacks)):
            done = []
            for (li, t), items in stacks.items():
                stack = _stack_experts([leaf for leaf, _ in items])
                before = sum(info.params_before for _, info in items)
                if _stack_params(stack, cur_cfg.fold_u) >= before:
                    continue
                if cur_cfg.fold_u:
                    stack = {"CU": jnp.einsum(
                        "emr,erk->emk", stack["C"].astype(jnp.float32),
                        stack["U0"] + stack["dU"]).astype(stack["C"].dtype),
                        "R": stack["R"]}
                _set_target(new_params["groups"][li][0], t,
                            jax.tree.map(lambda a: a[None], stack))
                done.append(stack)
                kept_ids.update(id(info) for _, info in items)
            with tracer.span("compress.experts.wait"):
                jax.block_until_ready(done)
        if cur_cfg.fold_u:
            seconds_fold += time.perf_counter() - t_exp
        obs_metrics.counter(
            "repro_compress_expert_weights_total",
            "expert weights CUR-compressed (one per held expert and "
            "target)").inc(sum(len(items) for items in stacks.values()
                               if id(items[0][1]) in kept_ids))
    # in work-list (network) order
    infos: List[WeightInfo] = [info for _, info in results
                               if id(info) in kept_ids]

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
