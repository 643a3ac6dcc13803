"""Sensitivity profiling: per-weight error-vs-rank curves in ONE pass.

For every compressible target weight the profiler produces the relative
Frobenius error ``||W - C U R||_F / ||W||_F`` and the Theorem 3.1
spectral bound at every rank of a geometric grid — WITHOUT recompressing
per rank. Two structural facts make that possible:

  1. the selection SVD at the top grid rank contains the leading singular
     vectors of every smaller rank, and
  2. DEIM is prefix-consistent: step ``j`` of the greedy loop only reads
     columns ``<= j`` of the singular-vector block, so
     ``deim(P[:, :r]) == deim(P[:, :r_hi])[:r]`` exactly.

So one SVD + one DEIM sweep at ``r_hi`` yields the *same* row/col
selections ``compress_model`` would make at each grid rank, and the per
rank work collapses to the pinv link solves. Like PR 3's batched
compressor, weights are grouped by (m, n) shape-class and each class runs
as one jitted vmapped call with a single host transfer; activations come
straight from ``core/calibrate`` stats, device-resident.

The selection identity is exact for ``svd="exact"`` (LAPACK computes the
full factorization either way; slicing k columns commutes with slicing
r < k). Under ``svd="randomized"`` the executed compression re-sketches
at each assigned rank with a different projection dimension, so the
curves are (good) estimates rather than the realized errors — plan with
exact SVD when prediction fidelity matters.

Only the DEIM-based selections (``wanda_deim``, ``deim``) are
profile-able this way; the other ablation strategies raise.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import CURConfig, ModelConfig
from repro.core import angular
from repro.core.calibrate import CalibStats
from repro.core.compress import _cur_work_list, rank_key
from repro.core.cur import exact_svd, randomized_svd, spectral_error_bound
from repro.core.deim import deim
from repro.core.wanda import wanda_scores

_PROFILE_SELECTIONS = ("wanda_deim", "deim")


# ---------------------------------------------------------------------------
# provenance hashes
# ---------------------------------------------------------------------------

def config_hash(cfg: ModelConfig) -> str:
    """Stable digest of the model config a plan was computed against."""
    return hashlib.sha256(repr(cfg).encode()).hexdigest()[:16]


def calib_hash(calib: CalibStats) -> str:
    """Digest of the calibration statistics (hidden states + WANDA
    activations + token count) — two runs over the same data agree."""
    h = hashlib.sha256()
    h.update(np.ascontiguousarray(calib.hidden, np.float32).tobytes())
    for layer in calib.act_sq or []:
        for name in sorted(layer):
            h.update(name.encode())
            h.update(np.ascontiguousarray(layer[name], np.float32).tobytes())
    h.update(str(calib.n_tokens).encode())
    return h.hexdigest()[:16]


# ---------------------------------------------------------------------------
# curves
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class WeightCurve:
    """Error-vs-rank curve of one weight. ``grid`` is ascending; entries
    beyond the weight's feasible range (Eq. 2 or min(m, n)) are omitted."""
    layer: int
    name: str
    shape: Tuple[int, int]
    grid: Tuple[int, ...]
    rel_err: np.ndarray          # (len(grid),) ||W - CUR_r||_F / ||W||_F
    # activation-weighted (functional) relative error:
    # ||diag(sqrt(act_sq)) (W - CUR_r)||_F / ||diag(sqrt(act_sq)) W||_F.
    # Under the diagonal input-covariance approximation this tracks the
    # expected OUTPUT distortion E||x W - x CUR_r||^2 of the layer, which
    # is what perplexity responds to — the allocator's default objective.
    func_err: np.ndarray
    bound: np.ndarray            # Theorem 3.1 bound per rank (see bound_on)
    bound_on: str
    fro_w: float                 # ||W||_F
    func_fro_w: float            # ||diag(sqrt(act_sq)) W||_F
    expert: Optional[int] = None  # the router's index, for an expert

    @property
    def key(self) -> str:
        return rank_key(self.layer, self.name, self.expert)


@dataclasses.dataclass
class SensitivityProfile:
    curves: List[WeightCurve]
    grid: Tuple[int, ...]
    selection: str
    svd: str
    seconds: float
    cfg_hash: str
    calib_hash: str
    distances: np.ndarray        # angular layer distances (for layer choice)

    def curve(self, key: str) -> WeightCurve:
        for c in self.curves:
            if c.key == key:
                return c
        raise KeyError(key)

    def to_jsonable(self) -> dict:
        return {
            "grid": list(self.grid),
            "selection": self.selection,
            "svd": self.svd,
            "seconds": round(self.seconds, 4),
            "cfg_hash": self.cfg_hash,
            "calib_hash": self.calib_hash,
            "curves": [{
                "key": c.key, "shape": list(c.shape),
                "grid": list(c.grid),
                "rel_err": [round(float(e), 6) for e in c.rel_err],
                "func_err": [round(float(e), 6) for e in c.func_err],
                "bound": [None if not np.isfinite(b) else round(float(b), 4)
                          for b in c.bound],
                "bound_on": c.bound_on,
            } for c in self.curves],
        }


def default_grid(r_max: int = 256, r_min: int = 4) -> Tuple[int, ...]:
    """Geometric (power-of-two) rank grid, matching Eq. 2's quantization."""
    grid, r = [], r_min
    while r <= r_max:
        grid.append(r)
        r *= 2
    return tuple(grid)


def feasible_grid(m: int, n: int, grid: Sequence[int]) -> Tuple[int, ...]:
    """Grid entries that still SAVE parameters in the healing (unfolded)
    form — m r + r^2 + r n < m n — and fit min(m, n). Using the stricter
    unfolded test keeps every profiled rank deployable under either form
    (``compress_model``'s Eq. 2 guard never drops a planned weight)."""
    return tuple(r for r in sorted(set(int(g) for g in grid))
                 if r <= min(m, n) and m * r + r * r + r * n < m * n)


@functools.partial(jax.jit, static_argnames=("grid", "selection", "svd"))
def _profile_class(Ws, acts, keys, *, grid: Tuple[int, ...], selection: str,
                   svd: str):
    """One shape-class: Ws (k, m, n), acts (k, m), keys (k,).
    Returns rel_err/bound arrays of shape (k, len(grid))."""
    r_hi = grid[-1]

    def one(W, act, key):
        if selection == "wanda_deim":
            S = wanda_scores(W, act)
        else:                                    # "deim"
            S = W.astype(jnp.float32)
        k = min(r_hi + 1, min(W.shape))
        if svd == "exact":
            P, sig, Q = exact_svd(S, k)
        else:
            P, sig, Q = randomized_svd(S, k, key)
        p_hi, q_hi = deim(P[:, :r_hi]), deim(Q[:, :r_hi])
        Wf = W.astype(jnp.float32)
        sa = jnp.sqrt(jnp.maximum(act.astype(jnp.float32), 0.0))[:, None]
        fro_w = jnp.linalg.norm(Wf)
        func_w = jnp.linalg.norm(sa * Wf)
        errs, ferrs, bounds = [], [], []
        for r in grid:                           # static, unrolled in jit
            p, q = p_hi[:r], q_hi[:r]
            C, R = Wf[:, q], Wf[p, :]
            U = (jnp.linalg.pinv(C) @ Wf) @ jnp.linalg.pinv(R)
            D = Wf - C @ U @ R
            errs.append(jnp.linalg.norm(D) / jnp.maximum(fro_w, 1e-30))
            ferrs.append(jnp.linalg.norm(sa * D)
                         / jnp.maximum(func_w, 1e-30))
            if sig.shape[0] > r:
                bounds.append(spectral_error_bound(
                    P[:, :r], Q[:, :r], sig, p, q))
            else:
                bounds.append(jnp.float32(jnp.inf))
        return (jnp.stack(errs), jnp.stack(ferrs), jnp.stack(bounds),
                fro_w, func_w)

    return jax.vmap(one)(Ws, acts, keys)


def profile_sensitivity(params, cfg: ModelConfig, cur_cfg: CURConfig,
                        calib: CalibStats,
                        grid: Optional[Sequence[int]] = None,
                        layers: Optional[Sequence[int]] = None,
                        ) -> SensitivityProfile:
    """Error-vs-rank curves for every compressible target weight of
    ``layers`` (default: every interior layer, first/last excluded like
    the paper's layer rule). One jitted vmapped call per (m, n) class."""
    if cur_cfg.selection not in _PROFILE_SELECTIONS:
        raise ValueError(
            f"sensitivity profiling needs a DEIM-based selection "
            f"{_PROFILE_SELECTIONS}, got {cur_cfg.selection!r}")
    t0 = time.perf_counter()
    if grid is None:
        grid = default_grid(cur_cfg.r_max)
    if layers is None:
        layers = range(1, cfg.n_layers - 1)
    work = _cur_work_list(params, cfg, cur_cfg, calib, set(layers))

    classes: Dict[Tuple[int, int], List[int]] = {}
    for i, it in enumerate(work):
        classes.setdefault(tuple(it.W.shape), []).append(i)

    curves: List[Optional[WeightCurve]] = [None] * len(work)
    for (m, n), idxs in classes.items():
        cls_grid = feasible_grid(m, n, grid)
        if not cls_grid:
            continue                             # nothing saves params here
        Ws = jnp.stack([work[i].W for i in idxs])
        # unit weights when a target has no calibration stats (possible
        # under plain "deim" selection): func_err degrades to rel_err
        acts = jnp.stack([
            jnp.asarray(work[i].act, jnp.float32) if work[i].act is not None
            else jnp.ones((m,), jnp.float32) for i in idxs])
        keys = jnp.stack([work[i].key for i in idxs])
        errs, ferrs, bounds, frows, fws = jax.device_get(_profile_class(
            Ws, acts, keys, grid=cls_grid, selection=cur_cfg.selection,
            svd=cur_cfg.svd))
        bound_on = "wanda" if cur_cfg.selection == "wanda_deim" else "weight"
        for k, i in enumerate(idxs):
            it = work[i]
            curves[i] = WeightCurve(
                layer=it.layer, name=it.name, shape=(m, n), grid=cls_grid,
                rel_err=np.asarray(errs[k], np.float64),
                func_err=np.asarray(ferrs[k], np.float64),
                bound=np.asarray(bounds[k], np.float64),
                bound_on=bound_on, fro_w=float(frows[k]),
                func_fro_w=float(fws[k]), expert=it.expert)

    return SensitivityProfile(
        curves=[c for c in curves if c is not None],
        grid=tuple(int(g) for g in grid),
        selection=cur_cfg.selection, svd=cur_cfg.svd,
        seconds=time.perf_counter() - t0,
        cfg_hash=config_hash(cfg), calib_hash=calib_hash(calib),
        distances=angular.layer_distances(calib.hidden))
