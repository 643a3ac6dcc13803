"""Paged-attention decode Pallas TPU kernel: block-table KV reads, online
softmax, rank-space CUR-KV.

One query token per slot attends to its paged KV history *in place*: the
grid is (B, maxb) with the per-sequence block index innermost, and a
scalar-prefetched block table drives the K/V BlockSpec index maps — each
grid step DMAs exactly one ``(K, block_size, r)`` pool block (every
kv-head of one block) into VMEM, so the full ``(B, maxb*bs, K, r)``
gather (and, in CUR-KV mode, the fp32 ``(.., head_dim)`` reconstruction)
that the XLA path materializes in HBM never exists. Pools are laid out
``(n_blocks, K, bs, r)`` so a block's last two dims ``(bs, r)`` meet the
TPU tiling rule (multiples of (8, 128), or the full dims) for any rank.
Per-(slot, kv-head) running (max, sum, acc) f32 scratch implements the
online softmax across blocks, exactly like ``flash_attention``'s KV-tile
loop; all kv-heads of a block are scored in one batched matmul.

CUR-KV attention happens natively in rank space: the caller folds the key
link matrix into the query (``q̃ = scale * q @ Ukᵀ``, see ``ref.fold_q``)
so scores are taken directly against the stored r-dim keys, and applies
the value link matrix to the r-dim output afterwards
(``o = (p @ v_r) @ Uv``) — algebra identical to reconstructing
``k̂ = k_r @ Uk`` / ``v̂ = v_r @ Uv``, with no full-head-dim intermediate
on any path. Dense pools are the ``r == head_dim`` special case (no
folds), so one kernel serves both modes.

Masking is in-kernel: token index ``t`` is live iff ``t <= ctx_len[b]``
(the newest token was just written at ``ctx_len[b]``), inside the local
window when ``window > 0``, and its table entry is assigned (>= 0).
Entirely-dead blocks — unassigned table entries, blocks past the context,
blocks before the window — are skipped with ``pl.when`` so their DMA'd
tile never touches the MXU. Slots with no live position (inactive rows
with an all-``-1`` table row) produce exact zeros.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(tbl_ref, ctx_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *, bs, nb, window, span=1):
    b = pl.program_id(0)
    j = pl.program_id(1)          # per-sequence block index (innermost)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ctx = ctx_ref[b]
    start = j * bs
    # block is live unless unassigned, entirely past the last query
    # position (ctx + span - 1), or entirely before the sliding window
    live = jnp.logical_and(tbl_ref[b, j] >= 0, start <= ctx + span - 1)
    if window > 0:
        live = jnp.logical_and(live, start + bs - 1 > ctx - window)

    @pl.when(live)
    def _update():
        q = q_ref[0]                             # (K, G, r), pre-scaled
        k = k_ref[0]                             # (K, bs, r)
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)  # (K, G, bs)
        idx = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        if span > 1:
            # speculative-verify layout: G = span * group, row g is query
            # position ctx + g // group (same per-row mask as span
            # sequential decode steps; one DMA'd KV tile serves them all)
            goff = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
                    // (q.shape[1] // span))
            qpos = ctx + goff
        else:
            qpos = ctx
        mask = idx <= qpos
        if window > 0:
            mask = jnp.logical_and(mask, idx > qpos - window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == nb - 1)
    def _finish():
        # l == 0 (no live block anywhere, e.g. an inactive slot with an
        # all-unassigned table row): acc is zero -> exact zero output
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def paged_attention(q, k_pool, v_pool, table, ctx_len, *, window: int = 0,
                    q_span: int = 1, interpret: bool = False):
    """q (B, K, G, r) folded/pre-scaled queries; k/v_pool
    (n_blocks, K, bs, r); table (B, maxb) int32 (-1 = unassigned);
    ctx_len (B,) newest-token index. Returns (B, K, G, r) rank-space
    attention outputs (apply ``Uv`` outside for CUR-KV pools).

    ``q_span = S > 1``: multi-position verify — ``G`` must be
    ``S * group`` with row ``g`` the query at position ``ctx + g //
    group`` (see ``ref.paged_attention_ref``); each pool block is still
    DMA'd exactly once per slot."""
    B, K, G, r = q.shape
    nb_pool, Kp, bs, rp = k_pool.shape
    if (Kp, rp) != (K, r) or v_pool.shape != k_pool.shape:
        raise ValueError(
            f"pool/query mismatch: q {q.shape}, k_pool {k_pool.shape}, "
            f"v_pool {v_pool.shape}")
    if q_span > 1 and G % q_span != 0:
        raise ValueError(f"q_span {q_span} must divide query rows {G}")
    maxb = table.shape[1]
    kernel = functools.partial(_kernel, bs=bs, nb=maxb, window=window,
                               span=q_span)
    # the block table IS the index map: unassigned entries clamp to
    # block 0 (their tile is DMA'd but pl.when-skipped)
    kv_spec = pl.BlockSpec(
        (1, K, bs, r),
        lambda b, j, tbl, ctx: (jnp.maximum(tbl[b, j], 0), 0, 0, 0))
    q_spec = pl.BlockSpec((1, K, G, r), lambda b, j, tbl, ctx: (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, maxb),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((K, G, 1), jnp.float32),
            pltpu.VMEM((K, G, 1), jnp.float32),
            pltpu.VMEM((K, G, r), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, r), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(table.astype(jnp.int32), ctx_len.astype(jnp.int32), q,
      k_pool, v_pool)
