"""Calibration pass (paper §4.1/§4.2): one forward over the calibration set
collecting, per block,

  - the last-token hidden state entering/leaving every block (for
    angular-distance layer selection), and
  - the accumulated squared input activations of every CURing target weight
    (for WANDA importance): over every token for a dense weight, the
    normed latent for MLA's ``wk_b``, and, for an MoE layer's expert
    weights, over the tokens whose top-k picks that expert, one
    ``(E_held, m)`` array a layer (ungated: the gate scales the expert's
    output, not its input). ``shared.*`` targets (the shared experts)
    see every token.

The instrumented forward for one micro-batch is a single jitted function
(cached per config, like the serving step cache), and both accumulators
stay device-resident across batches — hidden-state chunks concatenate on
device and ``act_sq`` accumulates with jnp adds. The ONLY host transfer
is the one ``jax.device_get`` at the end; the seed implementation
``np.asarray``'d every block of every batch, which serialized the whole
pass on host syncs.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ATTN, ATTN_LOCAL, MAMBA, MLA, MLP, MOE
from repro.obs import metrics as obs_metrics
from repro.obs.trace import NULL_TRACER
from repro.models import attention as attn
from repro.models import mamba as mb
from repro.models import mla
from repro.models.layers import norm
from repro.models.mlp import mlp_forward
from repro.models.moe import moe_forward, route_held
from repro.models.model import _embed


@dataclasses.dataclass
class CalibStats:
    hidden: np.ndarray            # (L+1, n_samples, D) last-token states
    # per layer: name -> (m,) sum x^2; (E_held, m) for an MoE layer's
    # expert weights, row e over the tokens routed to held expert e
    act_sq: List[Dict[str, np.ndarray]]
    n_tokens: int
    distances: np.ndarray = None  # filled by compress


def iter_layer_params(params, cfg):
    """Yield (layer_idx, spec, per-layer param dict) in network order."""
    li = 0
    for gi, (pattern, reps) in enumerate(cfg.groups):
        gp = params["groups"][gi]
        for r in range(reps):
            for pi, spec in enumerate(pattern):
                lp = jax.tree.map(lambda a: a[r], gp[pi])
                yield li, spec, lp
                li += 1


# target weight -> which normed input feeds it
_MIXER_TARGETS = {"wq", "wk", "wv", "w_z", "w_x", "w_B", "w_C", "w_dt"}
_MLP_TARGETS = {"w_gate", "w_up"}
_SHARED_TARGETS = {"shared.w_gate", "shared.w_up"}


def _sq_sum(h: jnp.ndarray) -> jnp.ndarray:
    """Sum of squares over all tokens. h: (B, S, m) -> (m,)."""
    return jnp.sum(h.astype(jnp.float32) ** 2, axis=(0, 1))


def _routed_sq_sum(h: jnp.ndarray, picked: jnp.ndarray) -> jnp.ndarray:
    """Per held expert, the sum of squares over the tokens that picked
    it. h (B, S, m), picked (B*S, E_held) bool -> (E_held, m)."""
    sq = (h.astype(jnp.float32) ** 2).reshape(-1, h.shape[-1])
    return jnp.matmul(picked.astype(jnp.float32).T, sq,
                      precision=jax.lax.Precision.HIGHEST)


def _calib_step(params, cfg, batch, mesh=None):
    """Instrumented forward for one micro-batch (mirrors
    ``model.block_forward``). Returns (hs (L+1, B, D) last-token states,
    per-layer act_sq dicts) — all device arrays."""
    x = _embed(params, cfg, batch)
    B, S, D = x.shape
    positions = jnp.broadcast_to(
        jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    hs = [x[:, -1, :]]
    act_sq: List[Dict[str, jnp.ndarray]] = []
    for li, spec, p in iter_layer_params(params, cfg):
        acc: Dict[str, jnp.ndarray] = {}
        h1 = norm(x, p.get("norm1"), cfg)
        for t in cfg.cur_targets:
            if t in _MIXER_TARGETS and t in p:
                acc[t] = _sq_sum(h1)
        if spec.mixer in (ATTN, ATTN_LOCAL):
            win = cfg.window if spec.mixer == ATTN_LOCAL else 0
            a = attn.attn_forward(h1, p, cfg, positions, window=win)
        elif spec.mixer == MLA:
            with jax.named_scope("mla"):
                a, c, _ = mla.mla_attend(h1, p, cfg, positions)
            if "wk_b" in cfg.cur_targets:
                acc["wk_b"] = _sq_sum(c)
        elif spec.mixer == MAMBA:
            a = mb.mamba_forward(h1, p, cfg)
        else:
            raise ValueError(spec.mixer)
        x = x + a
        if spec.mlp in (MLP, MOE):
            h2 = norm(x, p.get("norm2"), cfg)
            picked = None
            if spec.mlp == MOE:
                with jax.named_scope("moe_route"):
                    _, picked = route_held(h2.reshape(-1, D), p, cfg)
            for t in cfg.cur_targets:
                if t in _MLP_TARGETS and t in p:
                    acc[t] = (_sq_sum(h2) if picked is None
                              else _routed_sq_sum(h2, picked))
                elif t in _SHARED_TARGETS and "shared" in p:
                    acc[t] = _sq_sum(h2)
            if spec.mlp == MLP:
                x = x + mlp_forward(h2, p, cfg)
            else:
                x = x + moe_forward(h2, p, cfg, mesh)
        hs.append(x[:, -1, :])
        act_sq.append(acc)
    return jnp.stack(hs), act_sq


# jit cache keyed by cfg (+ mesh identity): one compile per model shape,
# shared across calibrate() calls and batches
_STEP_CACHE: dict = {}


def _jitted_step(cfg, mesh):
    key = (cfg, None if mesh is None else id(mesh))
    if key not in _STEP_CACHE:
        _STEP_CACHE[key] = jax.jit(
            lambda params, batch: _calib_step(params, cfg, batch, mesh))
    return _STEP_CACHE[key]


def calibrate(params, cfg, batches, mesh=None, tracer=None) -> CalibStats:
    """batches: list of batch dicts (each one calibration micro-batch).

    ``tracer`` records the sub-steps (``calibrate.batch`` per micro-batch,
    ``calibrate.wait`` for the final transfer); the caller's span around
    the call names the stage."""
    tracer = tracer or NULL_TRACER
    # default-registry timings (NULL no-ops unless obs is enabled): the
    # first batch carries the jit compile, so the per-batch histogram
    # makes compile-vs-steady cost visible without perturbing the pass
    h_batch = obs_metrics.histogram(
        "repro_compress_calibrate_batch_s",
        "per-micro-batch calibration forward (s); first = compile")
    c_time = obs_metrics.counter(
        "repro_compress_calibrate_time_s_total",
        "total calibration pass seconds")
    c_toks = obs_metrics.counter(
        "repro_compress_calibrate_tokens_total", "calibration tokens")
    t_pass = time.perf_counter()
    step = _jitted_step(cfg, mesh)
    hidden_chunks = []
    act_acc: List[Dict[str, jnp.ndarray]] = [
        dict() for _ in range(cfg.n_layers)]
    n_tokens = 0

    for batch in batches:
        with tracer.span("calibrate.batch"):
            t0 = time.perf_counter()
            shape = (batch["tokens"] if cfg.input_mode == "tokens"
                     else batch["embeds"]).shape
            n_tokens += shape[0] * shape[1]
            hs, act_sq = step(params, batch)
            hidden_chunks.append(hs)                # (L+1, B, D) on device
            for li, acc in enumerate(act_sq):
                for t, sq in acc.items():
                    prev = act_acc[li].get(t)
                    act_acc[li][t] = sq if prev is None else prev + sq
            h_batch.observe(time.perf_counter() - t0)

    with tracer.span("calibrate.wait"):
        hidden, act_np = jax.device_get(
            (jnp.concatenate(hidden_chunks, axis=1), act_acc))
    c_time.inc(time.perf_counter() - t_pass)
    c_toks.inc(n_tokens)
    return CalibStats(hidden=hidden, act_sq=act_np, n_tokens=n_tokens)
