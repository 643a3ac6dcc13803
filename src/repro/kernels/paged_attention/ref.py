"""Pure-jnp oracle for the paged-attention decode kernel, plus the
rank-space CUR-KV query fold.

``paged_attention_ref`` is also the serving runtime's non-kernel decode
path: it gathers the paged pool through the block table (pure XLA — the
gather the Pallas kernel eliminates) but computes attention in **rank
space**, so the CUR-KV fp32 full-head-dim reconstruction is gone on every
backend. Masking semantics match the kernel exactly, including zero
output for slots with no live position.
"""
import jax
import jax.numpy as jnp

NEG_INF = -1e30


def fold_q(q: jnp.ndarray, uk, scale: float) -> jnp.ndarray:
    """Fold the key link matrix and softmax scale into the query.

    q (..., hd); uk (r, hd) or None (dense pool). Returns (..., r) with
    ``q̃ = scale * q @ Ukᵀ``, so ``q̃ · k_r == scale * q · (k_r @ Uk)`` —
    scores against the stored r-dim keys equal scores against the
    reconstructed full-head-dim keys, without reconstructing them."""
    qf = q.astype(jnp.float32) * scale
    if uk is not None:
        qf = qf @ uk.astype(jnp.float32).T
    return qf.astype(q.dtype)


def unfold_o(o: jnp.ndarray, uv) -> jnp.ndarray:
    """Post-softmax value fold: (..., r) rank-space attention output ->
    (..., hd) via the value link matrix (identity when ``uv`` is None).
    ``(p @ v_r) @ Uv == p @ (v_r @ Uv)`` — same algebra as reconstructing
    v̂ first, one (G, r) @ (r, hd) matmul instead of an (L, r) @ (r, hd)
    cache materialization."""
    if uv is None:
        return o
    return (o.astype(jnp.float32) @ uv.astype(jnp.float32)).astype(o.dtype)


def paged_attention_ref(q, k_pool, v_pool, table, ctx_len, *,
                        window: int = 0, q_span: int = 1):
    """Gather-based oracle. q (B, K, G', r) folded/pre-scaled; pools
    (n_blocks, K, bs, r); table (B, maxb); ctx_len (B,). -> (B, K, G', r).

    ``q_span = S > 1`` is the speculative-verify layout: ``G' = S * G``
    rows per kv-head, row ``g`` holding query position ``ctx + g // G``
    of group member ``g % G`` (the caller flattens (B, S, K, G, r) to
    (B, K, S*G, r)). Each row is masked to its own position — per-row
    math identical to S sequential single-token calls — while the pool
    gather is shared across all S positions, which is the whole point:
    verifying k+1 draft positions costs ONE table-width gather instead
    of k+1."""
    B, maxb = table.shape
    K, bs, r = k_pool.shape[1:]
    L = maxb * bs
    Gq = q.shape[2]

    def gather(pool):                       # (B, L, K, r) table view
        g = jnp.swapaxes(pool[jnp.maximum(table, 0)], 2, 3)
        return g.reshape(B, L, K, r)

    ck, cv = gather(k_pool), gather(v_pool)
    s = jnp.einsum("bkgr,btkr->bkgt", q.astype(jnp.float32),
                   ck.astype(jnp.float32))
    idx = jnp.arange(L, dtype=jnp.int32)
    blk = jnp.repeat(table, bs, axis=1)               # (B, L) owning block
    if q_span > 1:
        off = jnp.arange(Gq, dtype=jnp.int32) // (Gq // q_span)
        qpos = ctx_len[:, None] + off[None, :]        # (B, G') row position
        valid = ((idx[None, None, :] <= qpos[:, :, None])
                 & (blk >= 0)[:, None, :])
        if window > 0:
            valid &= idx[None, None, :] > (qpos[:, :, None] - window)
        s = jnp.where(valid[:, None, :, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        p = p * valid.any(axis=-1)[:, None, :, None]
    else:
        valid = (idx[None, :] <= ctx_len[:, None]) & (blk >= 0)
        if window > 0:
            valid &= idx[None, :] > (ctx_len[:, None] - window)
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        # no live position (inactive slot): all-masked softmax is uniform
        # garbage — zero it to match the kernel's empty-accumulator output
        p = p * valid.any(axis=-1)[:, None, None, None]
    o = jnp.einsum("bkgt,btkr->bkgr", p, cv.astype(jnp.float32))
    return o.astype(q.dtype)
