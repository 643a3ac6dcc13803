"""Model assembly: init / forward / loss / prefill / decode over scan groups.

A model's layers are organized as ``cfg.groups = [(pattern, repeats), ...]``
(see DESIGN.md §6). Parameters for a group are a list of per-pattern-position
param dicts whose leaves carry a leading ``repeats`` axis; the group runs as
one ``lax.scan`` (compact HLO at 95-layer scale) or an unrolled loop
(``cfg.scan_layers=False``, used on CPU and for selectively CUR-compressed
models after group splitting).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import (
    ATTN, ATTN_LOCAL, MAMBA, MLA, MLP, MOE, ModelConfig)
from repro.models import attention as attn
from repro.models import mamba as mb
from repro.models import mla
from repro.models.layers import dense_init, embed_init, norm
from repro.models.mlp import mlp_forward
from repro.models.moe import moe_forward

Params = Dict[str, Any]

from jax.ad_checkpoint import checkpoint_name as _checkpoint_name


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_moe_experts(key, cfg, dtype):
    """The router over all n_experts and the stacks of the held ones."""
    E = cfg.n_held_experts
    D = cfg.d_model
    F = cfg.moe_d_ff or cfg.d_ff
    k1, k2, k3, k4 = jax.random.split(key, 4)
    init = jax.vmap(lambda k, m, n: dense_init(k, m, n, dtype),
                    in_axes=(0, None, None))
    p = {
        "router": dense_init(k1, D, cfg.n_experts, jnp.float32),
        "w_gate": init(jax.random.split(k2, E), D, F),
        "w_up": init(jax.random.split(k3, E), D, F),
        "w_down": init(jax.random.split(k4, E), F, D),
    }
    if cfg.moe_router_bias:
        p["router_bias"] = jnp.zeros((cfg.n_experts,), jnp.float32)
    if cfg.n_shared_experts:
        ks = jax.random.split(jax.random.fold_in(key, 7), 3)
        Fs = cfg.n_shared_experts * F
        p["shared"] = {
            "w_gate": dense_init(ks[0], D, Fs, dtype),
            "w_up": dense_init(ks[1], D, Fs, dtype),
            "w_down": dense_init(ks[2], Fs, D, dtype),
        }
    return p


def init_block(key, spec, cfg: ModelConfig) -> Params:
    D = cfg.d_model
    dtype = jnp.dtype(cfg.dtype)
    p: Params = {}
    keys = jax.random.split(key, 12)
    if cfg.parametric_norm:
        p["norm1"] = {"scale": jnp.ones((D,), dtype)}
    if spec.mixer in (ATTN, ATTN_LOCAL):
        H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
        p["wq"] = dense_init(keys[0], D, H * hd, dtype)
        p["wk"] = dense_init(keys[1], D, K * hd, dtype)
        p["wv"] = dense_init(keys[2], D, K * hd, dtype)
        p["wo"] = dense_init(keys[3], H * hd, D, dtype)
        if cfg.qk_norm:
            p["q_norm"] = jnp.ones((hd,), dtype)
            p["k_norm"] = jnp.ones((hd,), dtype)
    elif spec.mixer == MLA:
        H, r = cfg.n_heads, cfg.kv_lora_rank
        dn, dr, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
        p["wq"] = dense_init(keys[0], D, H * (dn + dr), dtype)
        p["wkv_a"] = dense_init(keys[1], D, r + dr, dtype)
        p["kv_norm"] = {"scale": jnp.ones((r,), dtype)}
        p["wk_b"] = dense_init(keys[2], r, H * dn, dtype)
        p["wv_b"] = dense_init(keys[4], r, H * dv, dtype)
        p["wo"] = dense_init(keys[3], H * dv, D, dtype)
    elif spec.mixer == MAMBA:
        di, N, nh = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
        Kc = cfg.ssm_conv
        p["w_z"] = dense_init(keys[0], D, di, dtype)
        p["w_x"] = dense_init(keys[1], D, di, dtype)
        p["w_B"] = dense_init(keys[2], D, N, dtype)
        p["w_C"] = dense_init(keys[3], D, N, dtype)
        p["w_dt"] = dense_init(keys[4], D, nh, dtype)
        p["conv_x"] = dense_init(keys[5], Kc, di, dtype)
        p["conv_x_b"] = jnp.zeros((di,), dtype)
        p["conv_B"] = dense_init(keys[6], Kc, N, dtype)
        p["conv_B_b"] = jnp.zeros((N,), dtype)
        p["conv_C"] = dense_init(keys[7], Kc, N, dtype)
        p["conv_C_b"] = jnp.zeros((N,), dtype)
        # A in [1, 16] (mamba-2 init); dt_bias ~ softplus^-1(U[1e-3, 0.1])
        a0 = jnp.linspace(1.0, 16.0, nh)
        p["A_log"] = jnp.log(a0).astype(jnp.float32)
        p["D"] = jnp.ones((nh,), jnp.float32)
        dt0 = jnp.exp(jax.random.uniform(keys[8], (nh,),
                                         minval=jnp.log(1e-3),
                                         maxval=jnp.log(0.1)))
        p["dt_bias"] = (dt0 + jnp.log(-jnp.expm1(-dt0))).astype(jnp.float32)
        p["norm_z"] = {"scale": jnp.ones((di,), dtype)}
        p["w_out"] = dense_init(keys[9], di, D, dtype)
    if spec.mlp == MLP:
        if cfg.parametric_norm:
            p["norm2"] = {"scale": jnp.ones((D,), dtype)}
        F = cfg.d_ff
        if cfg.gated_mlp:
            p["w_gate"] = dense_init(keys[10], D, F, dtype)
        p["w_up"] = dense_init(keys[11], D, F, dtype)
        p["w_down"] = dense_init(jax.random.fold_in(key, 99), F, D, dtype)
    elif spec.mlp == MOE:
        if cfg.parametric_norm:
            p["norm2"] = {"scale": jnp.ones((D,), dtype)}
        p.update(_init_moe_experts(jax.random.fold_in(key, 98), cfg, dtype))
    return p


def init_params(rng, cfg: ModelConfig) -> Params:
    dtype = jnp.dtype(cfg.dtype)
    params: Params = {"groups": []}
    k_embed, k_head, rng = jax.random.split(rng, 3)
    if cfg.input_mode == "tokens":
        params["embed"] = embed_init(k_embed, cfg.vocab_size, cfg.d_model,
                                     dtype)
    if not (cfg.tie_embeddings and cfg.input_mode == "tokens"):
        params["out_head"] = dense_init(k_head, cfg.d_model, cfg.vocab_size,
                                        dtype)
    if cfg.parametric_norm:
        params["final_norm"] = {"scale": jnp.ones((cfg.d_model,), dtype)}
    for gi, (pattern, reps) in enumerate(cfg.groups):
        gkey = jax.random.fold_in(rng, gi)
        group = []
        for pi, spec in enumerate(pattern):
            pkey = jax.random.fold_in(gkey, pi)
            stacked = jax.vmap(
                lambda k: init_block(k, spec, cfg)
            )(jax.random.split(pkey, reps))
            group.append(stacked)
        params["groups"].append(group)
    return params


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def block_forward(x, p, spec, cfg, positions, mesh=None):
    tag = (_checkpoint_name
           if cfg.remat_policy == "save_mixer_outputs" else
           (lambda v, _name: v))
    h = norm(x, p.get("norm1"), cfg)
    if spec.mixer in (ATTN, ATTN_LOCAL):
        win = cfg.window if spec.mixer == ATTN_LOCAL else 0
        a = attn.attn_forward(h, p, cfg, positions, window=win)
    elif spec.mixer == MLA:
        a = mla.mla_forward(h, p, cfg, positions)
    elif spec.mixer == MAMBA:
        a = mb.mamba_forward(h, p, cfg)
    else:
        raise ValueError(spec.mixer)
    x = x + tag(a, "mixer_out")
    if spec.mlp == MLP:
        h = norm(x, p.get("norm2"), cfg)
        x = x + tag(mlp_forward(h, p, cfg), "mlp_out")
    elif spec.mlp == MOE:
        h = norm(x, p.get("norm2"), cfg)
        x = x + tag(moe_forward(h, p, cfg, mesh), "mlp_out")
    return x


def _embed(params, cfg, batch):
    if cfg.input_mode == "tokens":
        x = params["embed"][batch["tokens"]]
    else:
        x = batch["embeds"].astype(jnp.dtype(cfg.dtype))
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    return x


def _unembed(params, cfg, x):
    if cfg.tie_embeddings and cfg.input_mode == "tokens":
        return x @ params["embed"].T
    return x @ params["out_head"]


def apply_groups(x, params, cfg, positions, mesh=None):
    """Run all layer groups over x."""
    for gi, (pattern, reps) in enumerate(cfg.groups):
        gp = params["groups"][gi]

        def body(xc, layer_params, _pattern=pattern):
            for pi, spec in enumerate(_pattern):
                xc = block_forward(xc, layer_params[pi], spec, cfg,
                                   positions, mesh)
            return xc

        if cfg.scan_layers and reps > 1:
            fn = _maybe_remat(body, cfg)

            def scan_body(xc, lp):
                return fn(xc, lp), None

            x, _ = jax.lax.scan(scan_body, x, gp)
        else:
            # static_loops (dry-run cost compiles) keeps remat so unrolled
            # HLO FLOPs include the recompute the scanned artifact performs
            fn = (_maybe_remat(body, cfg)
                  if cfg.static_loops else body)
            for r in range(reps):
                lp = jax.tree.map(lambda a: a[r], gp)
                x = fn(x, lp)
    return x


def _maybe_remat(body, cfg):
    if not cfg.remat:
        return body
    if cfg.remat_policy == "save_mixer_outputs":
        policy = jax.checkpoint_policies.save_only_these_names(
            "mixer_out", "mlp_out")
        return jax.checkpoint(body, policy=policy)
    return jax.checkpoint(body)


def forward(params, cfg: ModelConfig, batch, mesh=None):
    """Full-sequence forward -> logits (B, S, V)."""
    x = _embed(params, cfg, batch)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    x = apply_groups(x, params, cfg, positions, mesh)
    x = norm(x, params.get("final_norm"), cfg)
    return _unembed(params, cfg, x)


def forward_hidden(params, cfg: ModelConfig, batch, mesh=None):
    """Forward that also returns every block's output hidden state
    (for layer-wise knowledge distillation). Returns (logits, hidden)
    where hidden is (L+1, B, S, D): embedding output + each block."""
    x = _embed(params, cfg, batch)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    collected = [x]
    for gi, (pattern, reps) in enumerate(cfg.groups):
        gp = params["groups"][gi]

        def body(xc, layer_params, _pattern=pattern):
            outs = []
            for pi, spec in enumerate(_pattern):
                xc = block_forward(xc, layer_params[pi], spec, cfg,
                                   positions, mesh)
                outs.append(xc)
            return xc, jnp.stack(outs)

        if cfg.scan_layers and reps > 1:
            x, ys = jax.lax.scan(lambda c, lp: body(c, lp), x, gp)
            collected.append(ys.reshape((-1,) + x.shape))
        else:
            for r in range(reps):
                lp = jax.tree.map(lambda a: a[r], gp)
                x, ys = body(x, lp)
                collected.append(ys)
    hidden = jnp.concatenate(
        [collected[0][None]] + collected[1:], axis=0)
    x = norm(x, params.get("final_norm"), cfg)
    return _unembed(params, cfg, x), hidden


def loss_fn(params, cfg, batch, mesh=None):
    """Mean next-token cross-entropy, vocab-sharding-friendly: the gold
    logit is a one-hot contraction (sharded-reduce + psum under GSPMD)
    instead of a gather, which would all-gather the (B,S,V) logits."""
    logits = forward(params, cfg, batch, mesh).astype(jnp.float32)
    labels = batch["labels"]
    lse = jax.nn.logsumexp(logits, axis=-1)
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=logits.dtype)
    gold = jnp.sum(logits * onehot, axis=-1)
    ll = gold - lse
    mask = batch.get("mask")
    if mask is None:
        return -ll.mean()
    return -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


# ---------------------------------------------------------------------------
# serving: cache init / prefill / decode
# ---------------------------------------------------------------------------

def _init_block_cache(spec, cfg, batch, max_len, dtype):
    if spec.mixer in (ATTN, ATTN_LOCAL):
        win = cfg.window if spec.mixer == ATTN_LOCAL else 0
        return attn.init_attn_cache(cfg, batch, max_len, win, dtype)
    if spec.mixer == MLA:
        return mla.init_mla_cache(cfg, batch, max_len, dtype)
    if spec.mixer == MAMBA:
        return mb.init_mamba_cache(cfg, batch, dtype)
    return {}


def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    dtype = jnp.dtype(cfg.dtype)
    cache = {"groups": []}
    for pattern, reps in cfg.groups:
        group = []
        for spec in pattern:
            one = _init_block_cache(spec, cfg, batch, max_len, dtype)
            stacked = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (reps,) + a.shape).copy(), one)
            group.append(stacked)
        cache["groups"].append(group)
    return cache


def _block_prefill(x, p, c, spec, cfg, positions, mesh=None):
    h = norm(x, p.get("norm1"), cfg)
    if spec.mixer in (ATTN, ATTN_LOCAL):
        win = cfg.window if spec.mixer == ATTN_LOCAL else 0
        a, c = attn.attn_prefill(h, p, cfg, positions, c, window=win)
    elif spec.mixer == MLA:
        a, c = mla.mla_prefill(h, p, cfg, positions, c)
    elif spec.mixer == MAMBA:
        a, c = mb.mamba_prefill(h, p, cfg)
    else:
        raise ValueError(spec.mixer)
    x = x + a
    if spec.mlp == MLP:
        x = x + mlp_forward(norm(x, p.get("norm2"), cfg), p, cfg)
    elif spec.mlp == MOE:
        x = x + moe_forward(norm(x, p.get("norm2"), cfg), p, cfg, mesh)
    return x, c


def _block_decode(x, p, c, spec, cfg, pos, mesh=None):
    h = norm(x, p.get("norm1"), cfg)
    if spec.mixer in (ATTN, ATTN_LOCAL):
        win = cfg.window if spec.mixer == ATTN_LOCAL else 0
        a, c = attn.attn_decode(h, p, cfg, c, pos, window=win)
    elif spec.mixer == MLA:
        a, c = mla.mla_decode(h, p, cfg, c, pos)
    elif spec.mixer == MAMBA:
        a, c = mb.mamba_decode(h, p, cfg, c)
    else:
        raise ValueError(spec.mixer)
    x = x + a
    if spec.mlp == MLP:
        x = x + mlp_forward(norm(x, p.get("norm2"), cfg), p, cfg)
    elif spec.mlp == MOE:
        x = x + moe_forward(norm(x, p.get("norm2"), cfg), p, cfg, mesh)
    return x, c


def _apply_groups_cached(x, params, cache, cfg, block_fn, mesh=None):
    """Shared scan/unroll driver for prefill & decode (cache-threading)."""
    new_cache = {"groups": []}
    for gi, (pattern, reps) in enumerate(cfg.groups):
        gp = params["groups"][gi]
        gc = cache["groups"][gi]

        def body(xc, lp, lc, _pattern=pattern):
            ncs = []
            for pi, spec in enumerate(_pattern):
                xc, nc = block_fn(xc, lp[pi], lc[pi], spec, cfg, mesh)
                ncs.append(nc)
            return xc, ncs

        if cfg.scan_layers and reps > 1:
            def scan_body(xc, lplc):
                lp, lc = lplc
                xc, ncs = body(xc, lp, lc)
                return xc, ncs

            x, ncs = jax.lax.scan(scan_body, x, (gp, gc))
        else:
            per_rep = []
            for r in range(reps):
                lp = jax.tree.map(lambda a: a[r], gp)
                lc = jax.tree.map(lambda a: a[r], gc)
                x, ncs_r = body(x, lp, lc)
                per_rep.append(ncs_r)
            ncs = jax.tree.map(lambda *xs: jnp.stack(xs), *per_rep)
        new_cache["groups"].append(ncs)
    return x, new_cache


def prefill(params, cfg: ModelConfig, batch, cache, mesh=None):
    """Process the prompt; returns (last-position logits (B,V), cache)."""
    x = _embed(params, cfg, batch)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))

    def block_fn(xc, p, c, spec, cfg, mesh):
        return _block_prefill(xc, p, c, spec, cfg, positions, mesh)

    x, new_cache = _apply_groups_cached(x, params, cache, cfg, block_fn, mesh)
    x = norm(x, params.get("final_norm"), cfg)
    logits = _unembed(params, cfg, x[:, -1:, :])[:, 0, :]
    return logits, new_cache


def decode_step(params, cfg: ModelConfig, batch, cache, pos, mesh=None):
    """One decode step. batch: tokens (B,1) or embeds (B,1,D); pos (B,1)
    absolute positions. Returns (logits (B,V), new cache)."""
    x = _embed(params, cfg, batch)

    def block_fn(xc, p, c, spec, cfg, mesh):
        return _block_decode(xc, p, c, spec, cfg, pos, mesh)

    x, new_cache = _apply_groups_cached(x, params, cache, cfg, block_fn, mesh)
    x = norm(x, params.get("final_norm"), cfg)
    logits = _unembed(params, cfg, x)[:, 0, :]
    return logits, new_cache
