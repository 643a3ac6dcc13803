"""Paged model steps: prefill / decode over the block-table KV pool.

Mirrors ``models.model``'s cached forward but threads the shared paged
pool instead of per-sequence dense caches. Both entry points run over a
fixed ``B = max_concurrency`` slot batch (inactive rows are masked), so
each compiles once per prefill bucket and once for decode — the shapes a
continuous-batching scheduler feeds them never change mid-run. Ragged
prompt batches are padded up to power-of-two buckets, which keeps the
folded-CUR weight matmuls on the ``cur_matmul`` pad-and-slice fast path
(MXU-aligned block sizes regardless of admitted batch raggedness).

Attention — prefill AND decode — runs in **rank space** (CURing's
approximate-via-selected-columns framing; Sengupta et al. 2025): the key
link matrix is folded into the query (``q̃ = scale * q @ Ukᵀ``) so scores
are taken directly against the r-dim compressed keys, and the value link
matrix is applied after the softmax (``o = (p @ v_r) @ Uv``) — the
CUR-compressed cache is never re-expanded to full head_dim on any
backend. Every attention call here resolves through the backend registry
(``repro.attention``): decode through the ``paged_decode`` variant
(Pallas block-table kernel behind ``REPRO_PAGED_KERNEL``, else the
gather-based XLA reference), prompt attention through ``paged_prefill``
(``rank_fold`` by default: attend at feature dim r and scatter the same
compressed blocks to the pool in one pass — no full-head-dim KV bytes,
no reconstruct-then-recompress double write, and no last-position splice
because every prompt position already attends the compressed K/V decode
will read; ``REPRO_PREFILL_BACKEND=reconstruct`` keeps the full-head-dim
oracle for calibration/tests). Both decode paths are scan-safe (no host
syncs), so ``paged_decode_scan`` multi-step windows work with the kernel
gated either way.
"""
from __future__ import annotations

from typing import List, Tuple

import jax
import jax.numpy as jnp

from repro.attention import registry as attn_registry
from repro.attention.prefill import (               # noqa: F401 (re-export)
    reconstructed_bytes_per_prefill)
from repro.attention.registry import (              # noqa: F401 (re-export)
    fold_q, resolve_paged, resolve_prefill, unfold_o, use_paged_kernel)
from repro.configs.base import ATTN, ATTN_LOCAL, MLP, MOE, ModelConfig
from repro.models import attention as attn
from repro.models.layers import apply_w, norm
from repro.models.mlp import mlp_forward
from repro.models.model import _embed, _unembed
from repro.models.moe import moe_forward
from repro.serving import paged_cache as pcache


def _paged_attn(qg, k_pool, v_pool, table, ctx_len, uk, uv, scale,
                window: int, kernel=None, q_span: int = 1):
    """Rank-space paged attention for one layer's single-token queries.

    qg (B, K, G, hd) grouped queries; pools (n_blocks, K, bs, r).
    Returns (B, K, G, hd) — rank-space scores/values with the Uk/Uv
    folds, resolved through the registry's ``paged_decode`` variant
    (Pallas block-table kernel when gated on, else the gather-based XLA
    reference — same math, same masking). ``kernel`` pins the dispatch
    explicitly (the Server resolves the env gate ONCE and threads it
    here, so a mid-session env flip cannot make a lazily traced step
    disagree with its jit-cache key); None re-reads the env at trace
    time. ``q_span = S > 1`` is the speculative-verify layout (G = S *
    group, per-row positions ctx + row // group) — the pool read is
    shared across all S positions on both dispatch paths."""
    be = resolve_paged(kernel)
    qf = fold_q(qg, uk, scale)                    # (B, K, G, r)
    o_r = be.fn(qf, k_pool, v_pool, table, ctx_len,
                window=window, q_span=q_span)
    return unfold_o(o_r, uv)                      # (B, K, G, hd)


def gathered_bytes_per_step(cfg: ModelConfig, pc: pcache.PagedConfig,
                            batch: int, kernel=None) -> int:
    """HBM bytes the decode step materializes out of the pool per engine
    step (the ``gather_kv`` cost the kernel path eliminates): 0 when the
    Pallas kernel is gated on, else k+v gathers of the full table window
    for every attention layer. Pass ``kernel`` to describe a specific
    compiled path (the Server pins it at construction) instead of the
    env var's current resolution."""
    if kernel is None:
        kernel = use_paged_kernel()
    if kernel:
        return 0
    L = pcache._attn_layers(cfg)
    r = pc.rank(cfg.resolved_head_dim)
    itemsize = jnp.dtype(cfg.dtype).itemsize
    return 2 * L * batch * pc.max_len * cfg.n_kv_heads * r * itemsize


def iter_blocks(params, cfg: ModelConfig):
    """Yield (layer_idx, spec, per-layer params) in network order —
    scan-stacked groups are unrolled (paged serving traces per layer)."""
    li = 0
    for gi, (pattern, reps) in enumerate(cfg.groups):
        for r in range(reps):
            for pi, spec in enumerate(pattern):
                lp = jax.tree.map(lambda a: a[r], params["groups"][gi][pi])
                yield li, spec, lp
                li += 1


def check_supported(cfg: ModelConfig) -> None:
    if not pcache.supports(cfg):
        raise ValueError(
            f"{cfg.name}: paged serving supports attention mixers only "
            "(mamba state is not paged); use serve.engine.generate")


def _layer_proj(cache: dict, li: int):
    """(qk, uk, qv, uv) for layer li, or Nones when not in CUR-KV mode."""
    proj = cache.get("proj")
    if proj is None:
        return None, None, None, None
    return (proj["qk"][li], proj["uk"][li],
            proj["qv"][li], proj["uv"][li])


def _channel_mix(x, p, spec, cfg, mesh):
    if spec.mlp == MLP:
        x = x + mlp_forward(norm(x, p.get("norm2"), cfg), p, cfg)
    elif spec.mlp == MOE:
        x = x + moe_forward(norm(x, p.get("norm2"), cfg), p, cfg, mesh)
    return x


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------

def paged_prefill(params, cfg: ModelConfig, pc: pcache.PagedConfig,
                  tokens: jnp.ndarray, lengths: jnp.ndarray,
                  cache: dict, table: jnp.ndarray, mesh=None,
                  backend=None):
    """Process padded ragged prompts, writing K/V into the pool.

    tokens (B, S) right-padded; lengths (B,) true prompt lengths (0 =
    inactive slot); table (B, maxb) block ids (-1 pad). Returns
    (last-real-token logits (B, V), new cache).

    CUR-KV pools resolve the registry's ``paged_prefill`` variant.
    ``rank_fold`` (the default) compresses K/V to ``(B, S, K, r)`` once,
    attends in rank space, and scatters those same compressed arrays to
    the pool — one pass, zero full-head-dim KV bytes (see
    ``reconstructed_bytes_per_prefill``), and no last-position splice:
    every prompt position attends exactly the compressed cache decode
    will read, so the sampled stream agrees with the pool by
    construction. ``backend`` pins "fold"/"reconstruct" (the Server
    resolves ``REPRO_PREFILL_BACKEND`` ONCE and threads it here, same
    jit-cache-key contract as the decode ``kernel`` pin); None re-reads
    the env at trace time. Dense pools bypass the variant: the raw K/V
    IS the payload."""
    check_supported(cfg)
    x = _embed(params, cfg, {"tokens": tokens})
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                 (B, S))
    scale = cfg.resolved_head_dim ** -0.5
    last = jnp.clip(lengths - 1, 0, S - 1)
    be = resolve_prefill(backend)
    new_k, new_v = cache["k"], cache["v"]
    for li, spec, p in iter_blocks(params, cfg):
        win = cfg.window if spec.mixer == ATTN_LOCAL else 0
        h = norm(x, p.get("norm1"), cfg)
        q, k, v = attn.qkv_project(h, p, cfg, positions)
        qg = attn._group_q(q, cfg.n_kv_heads)
        qk, uk, qv, uv = _layer_proj(cache, li)
        if qk is None:                            # dense pool
            o = attn._mix(qg, k, v, positions, win, scale, cfg)
            kc, vc = k, v
        else:                                     # CUR-KV pool
            o, kc, vc = be.fn(qg, k, v, positions, win, scale, cfg,
                              (qk, uk, qv, uv))
        o = o.reshape(B, S, -1)
        pool_k = pcache.write_prompt(new_k[li], kc, table, lengths,
                                     pc.block_size)
        pool_v = pcache.write_prompt(new_v[li], vc, table, lengths,
                                     pc.block_size)
        new_k = new_k.at[li].set(pool_k)
        new_v = new_v.at[li].set(pool_v)
        x = x + apply_w(o, p["wo"])
        x = _channel_mix(x, p, spec, cfg, mesh)
    x = norm(x, params.get("final_norm"), cfg)
    x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)
    logits = _unembed(params, cfg, x_last)[:, 0, :]
    new_cache = dict(cache)
    new_cache["k"], new_cache["v"] = new_k, new_v
    return logits, new_cache


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def paged_decode(params, cfg: ModelConfig, pc: pcache.PagedConfig,
                 tokens: jnp.ndarray, cache: dict, table: jnp.ndarray,
                 ctx_len: jnp.ndarray, active: jnp.ndarray, mesh=None,
                 kernel=None):
    """One decode step for every active slot.

    tokens (B, 1) last sampled token per slot; ctx_len (B,) tokens already
    in cache (the new token is written at that position); active (B,)
    bool. Returns (logits (B, V), new cache)."""
    check_supported(cfg)
    x = _embed(params, cfg, {"tokens": tokens})
    B = x.shape[0]
    pos = ctx_len[:, None].astype(jnp.int32)              # (B, 1)
    scale = cfg.resolved_head_dim ** -0.5
    new_k, new_v = cache["k"], cache["v"]
    for li, spec, p in iter_blocks(params, cfg):
        win = cfg.window if spec.mixer == ATTN_LOCAL else 0
        h = norm(x, p.get("norm1"), cfg)
        q, k, v = attn.qkv_project(h, p, cfg, pos)        # (B, 1, ., hd)
        qk, uk, qv, uv = _layer_proj(cache, li)
        pool_k = pcache.write_token(
            new_k[li], pcache.compress_kv(k[:, 0], qk), table,
            ctx_len, active, pc.block_size)
        pool_v = pcache.write_token(
            new_v[li], pcache.compress_kv(v[:, 0], qv), table,
            ctx_len, active, pc.block_size)
        new_k = new_k.at[li].set(pool_k)
        new_v = new_v.at[li].set(pool_v)
        qg = attn._group_q(q, cfg.n_kv_heads)[:, 0]       # (B, K, G, hd)
        o = _paged_attn(qg, pool_k, pool_v, table, ctx_len, uk, uv,
                        scale, win, kernel)
        o = o.reshape(B, 1, -1)
        x = x + apply_w(o, p["wo"])
        x = _channel_mix(x, p, spec, cfg, mesh)
    x = norm(x, params.get("final_norm"), cfg)
    logits = _unembed(params, cfg, x)[:, 0, :]
    new_cache = dict(cache)
    new_cache["k"], new_cache["v"] = new_k, new_v
    return logits, new_cache


# ---------------------------------------------------------------------------
# multi-position verify (speculative decoding)
# ---------------------------------------------------------------------------

def paged_verify(params, cfg: ModelConfig, pc: pcache.PagedConfig,
                 tokens: jnp.ndarray, cache: dict, table: jnp.ndarray,
                 ctx_len: jnp.ndarray, active: jnp.ndarray, mesh=None,
                 kernel=None):
    """One forward over ``S`` consecutive positions per slot — the
    speculative verify step.

    tokens (B, S): token ``j`` is the input at position ``ctx + j``
    (j = 0 is the slot's pending ``next_token``, the rest are draft
    proposals). Per layer, all S positions' roped K/V are written to the
    (forked) pool FIRST, then every query attends through the pool with
    its own causal mask ``idx <= ctx + j`` — per-row math identical to S
    sequential :func:`paged_decode` calls, which is what makes the
    greedy accept path bit-identical to non-speculative decoding, while
    the pool is read once per (slot, layer) instead of S times. Returns
    (logits (B, S, V), new cache); ``logits[:, j]`` is the target
    distribution for the token AFTER position ``ctx + j``."""
    check_supported(cfg)
    x = _embed(params, cfg, {"tokens": tokens})
    B, S, _ = x.shape
    pos = ctx_len[:, None] + jnp.arange(S, dtype=jnp.int32)[None]
    scale = cfg.resolved_head_dim ** -0.5
    K = cfg.n_kv_heads
    new_k, new_v = cache["k"], cache["v"]
    for li, spec, p in iter_blocks(params, cfg):
        win = cfg.window if spec.mixer == ATTN_LOCAL else 0
        h = norm(x, p.get("norm1"), cfg)
        q, k, v = attn.qkv_project(h, p, cfg, pos)        # (B, S, ., hd)
        qk, uk, qv, uv = _layer_proj(cache, li)
        pool_k = pcache.write_span(
            new_k[li], pcache.compress_kv(k, qk), table, ctx_len, active,
            pc.block_size)
        pool_v = pcache.write_span(
            new_v[li], pcache.compress_kv(v, qv), table, ctx_len, active,
            pc.block_size)
        new_k = new_k.at[li].set(pool_k)
        new_v = new_v.at[li].set(pool_v)
        qg = attn._group_q(q, K)                          # (B, S, K, G, hd)
        G = qg.shape[3]
        qflat = jnp.transpose(qg, (0, 2, 1, 3, 4)).reshape(B, K, S * G, -1)
        o = _paged_attn(qflat, pool_k, pool_v, table, ctx_len, uk, uv,
                        scale, win, kernel, q_span=S)
        o = o.reshape(B, K, S, G, -1).transpose(0, 2, 1, 3, 4)
        o = o.reshape(B, S, -1)
        x = x + apply_w(o, p["wo"])
        x = _channel_mix(x, p, spec, cfg, mesh)
    x = norm(x, params.get("final_norm"), cfg)
    logits = _unembed(params, cfg, x)                     # (B, S, V)
    new_cache = dict(cache)
    new_cache["k"], new_cache["v"] = new_k, new_v
    return logits, new_cache


# ---------------------------------------------------------------------------
# multi-step decode (host-sync amortization)
# ---------------------------------------------------------------------------

def paged_decode_scan(params, cfg: ModelConfig, pc: pcache.PagedConfig,
                      tokens, cache, table, ctx, active, budgets,
                      base_keys, gen_starts, temps, top_ks, top_ps,
                      n_steps: int, mesh=None, greedy: bool = False,
                      kernel=None):
    """``n_steps`` decode+sample iterations in one compiled scan.

    Sampled tokens feed the next step on-device, so the host syncs once
    per window instead of once per token — the throughput edge the
    static seed path gets from free-running its whole decode loop. Rows
    whose generation budget fills mid-window freeze in place: their pool
    writes are masked off (the scheduler reserved blocks only for each
    row's real remainder) and the host discards their surplus tokens.
    Stop-token retirement needs a per-token host check, so the scheduler
    only opens windows when no live request carries one.

    budgets (B,): per-slot ``max_new_tokens``; base_keys (B, 2):
    fold_in(PRNGKey(seed), rid) per request — folding in the per-slot
    generated-token index reproduces ``request_key`` exactly, so
    multi-step and single-step sampling streams are identical.
    ``greedy`` (static) compiles an argmax-only sampler — the nucleus
    machinery is all sorts, pure overhead when no live request needs it."""
    from repro.serving.sampling import _sample_one

    def body(carry, i):
        toks, c, cx = carry
        live = active & (gen_starts + i < budgets)
        logits, c = paged_decode(params, cfg, pc, toks, c, table, cx,
                                 live, mesh, kernel)
        lg32 = logits.astype(jnp.float32)
        if greedy:
            logp = jax.nn.log_softmax(lg32)
            s_toks = jnp.argmax(lg32, axis=-1).astype(jnp.int32)
            s_lps = jnp.take_along_axis(logp, s_toks[:, None],
                                        axis=-1)[:, 0]
        else:
            keys = jax.vmap(jax.random.fold_in)(base_keys, gen_starts + i)
            s_toks, s_lps = jax.vmap(_sample_one)(
                lg32, temps, top_ks, top_ps, keys)
        return (s_toks[:, None], c, cx + 1), (s_toks, s_lps)

    (_, cache, _), (toks_seq, lps_seq) = jax.lax.scan(
        body, (tokens, cache, ctx), jnp.arange(n_steps))
    return toks_seq, lps_seq, cache


# ---------------------------------------------------------------------------
# CUR-KV calibration
# ---------------------------------------------------------------------------

def collect_kv(params, cfg: ModelConfig, tokens: jnp.ndarray
               ) -> Tuple[List[jnp.ndarray], List[jnp.ndarray]]:
    """Dense forward over a calibration batch collecting every attention
    layer's roped K/V (B, S, K, hd) — input to the DEIM column selection."""
    check_supported(cfg)
    x = _embed(params, cfg, {"tokens": tokens})
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                 (B, S))
    scale = cfg.resolved_head_dim ** -0.5
    ks, vs = [], []
    for li, spec, p in iter_blocks(params, cfg):
        win = cfg.window if spec.mixer == ATTN_LOCAL else 0
        h = norm(x, p.get("norm1"), cfg)
        q, k, v = attn.qkv_project(h, p, cfg, positions)
        ks.append(k)
        vs.append(v)
        qg = attn._group_q(q, cfg.n_kv_heads)
        o = attn._mix(qg, k, v, positions, win, scale, cfg)
        x = x + apply_w(o.reshape(B, S, -1), p["wo"])
        x = _channel_mix(x, p, spec, cfg, None)
    return ks, vs


def calibrate_kv(params, cfg: ModelConfig, pc: pcache.PagedConfig,
                 cache: dict, tokens: jnp.ndarray) -> dict:
    """Fill ``cache['proj']`` from a calibration prompt batch."""
    if not pc.cur_kv:
        return cache
    r = pc.rank(cfg.resolved_head_dim)
    ks, vs = collect_kv(params, cfg, tokens)
    new = dict(cache)
    new["proj"] = pcache.projections_from_kv(ks, vs, r)
    return new
