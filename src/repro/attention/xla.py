"""XLA attention implementations shared by every registry variant.

These are the dense / chunked-flash / banded paths that used to live in
``repro.models.attention``; the registry registers them as ``mix``
backends. All three are **dimension-agnostic** in the feature axis: the
rank-space prefill path feeds them folded queries and ``(S, r)``
compressed K/V with ``scale=1.0`` and they compute the exact CUR-KV
algebra without ever materializing full-head-dim keys or values.

Layout contract (the registry's ``mix`` variant):
  q  (B, Sq, K, G, d)  GQA-grouped queries
  k,v (B, Skv, K, d)
  q_pos / kv_pos (B, Sq) / (B, Skv) absolute positions (causal masking is
  positional, so ragged right-padded batches are handled by the caller
  simply ignoring the garbage rows past each sequence's length).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

DENSE_MAX = 2048     # use dense masked softmax at or below this seq len
CHUNK = 512          # flash chunk (query and kv)

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# dense oracle
# ---------------------------------------------------------------------------

def dense_attn(q, k, v, q_pos, kv_pos, window: int, scale: float):
    """q (B,Sq,K,G,d); k,v (B,Skv,K,d); positions (B,Sq)/(B,Skv)."""
    s = jnp.einsum("bqkgd,btkd->bkgqt", q, k).astype(jnp.float32) * scale
    mask = kv_pos[:, None, :] <= q_pos[:, :, None]            # causal
    if window > 0:
        mask &= kv_pos[:, None, :] > (q_pos[:, :, None] - window)
    s = jnp.where(mask[:, None, None, :, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqt,btkd->bqkgd", p.astype(v.dtype), v)
    return o


# ---------------------------------------------------------------------------
# chunked flash path (full causal)
# ---------------------------------------------------------------------------

def _flash_chunk_update(carry, s, v_chunk):
    """Online softmax update. carry: (m, l, acc); s: (B,K,G,cq,ck) f32."""
    m, l, acc = carry
    m_new = jnp.maximum(m, s.max(axis=-1))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])
    l_new = l * alpha + p.sum(axis=-1)
    acc_new = acc * alpha[..., None] + jnp.einsum(
        "bkgqt,btkd->bkgqd", p.astype(v_chunk.dtype), v_chunk
    ).astype(jnp.float32)
    return m_new, l_new, acc_new


def flash_attn(q, k, v, q_pos, kv_pos, scale: float, chunk: int,
               static: bool = False):
    """Nested-chunk online softmax. q (B,Sq,K,G,d), k (B,Skv,K,d),
    v (B,Skv,K,dv).

    ``static=True`` unrolls both chunk loops in Python and *skips* causally
    dead (q, k) chunk pairs — the control flow the Pallas kernel executes
    on TPU (pl.when), used by the dry-run cost compiles so HLO FLOPs count
    loop trips and reflect causal tile skipping."""
    B, Sq, K, G, hd = q.shape
    Skv, dv = k.shape[1], v.shape[-1]
    cq = min(chunk, Sq)
    ck = min(chunk, Skv)
    nq, nk = Sq // cq, Skv // ck
    qc = q.reshape(B, nq, cq, K, G, hd)
    qp = q_pos.reshape(B, nq, cq)
    kc = k.reshape(B, nk, ck, K, hd)
    vc = v.reshape(B, nk, ck, K, dv)
    kp = kv_pos.reshape(B, nk, ck)

    def chunk_scores(qi, qpi, ki, kpi):
        s = jnp.einsum("bqkgd,btkd->bkgqt", qi, ki).astype(jnp.float32)
        s = s * scale
        mask = kpi[:, None, :] <= qpi[:, :, None]
        return jnp.where(mask[:, None, None, :, :], s, NEG_INF)

    def per_qchunk_scan(qi, qpi):
        m0 = jnp.full((B, K, G, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, K, G, cq), jnp.float32)
        a0 = jnp.zeros((B, K, G, cq, dv), jnp.float32)

        def body(carry, xs):
            ki, vi, kpi = xs
            s = chunk_scores(qi, qpi, ki, kpi)
            return _flash_chunk_update(carry, s, vi), None

        (m, l, acc), _ = jax.lax.scan(
            body, (m0, l0, a0),
            (kc.swapaxes(0, 1), vc.swapaxes(0, 1), kp.swapaxes(0, 1)))
        o = acc / jnp.maximum(l, 1e-30)[..., None]
        return o.transpose(0, 3, 1, 2, 4)     # -> (B,cq,K,G,hd)

    if static:
        outs = []
        for i in range(nq):
            qi, qpi = qc[:, i], qp[:, i]
            carry = (jnp.full((B, K, G, cq), NEG_INF, jnp.float32),
                     jnp.zeros((B, K, G, cq), jnp.float32),
                     jnp.zeros((B, K, G, cq, dv), jnp.float32))
            last_live = (i * cq + cq - 1) // ck     # causal skip beyond
            for j in range(last_live + 1):
                s = chunk_scores(qi, qpi, kc[:, j], kp[:, j])
                carry = _flash_chunk_update(carry, s, vc[:, j])
            m, l, acc = carry
            o = acc / jnp.maximum(l, 1e-30)[..., None]
            outs.append(o.transpose(0, 3, 1, 2, 4))
        o = jnp.concatenate(outs, axis=1)
        return o.reshape(B, Sq, K, G, dv).astype(q.dtype)

    o = jax.lax.map(lambda t: per_qchunk_scan(t[0], t[1]),
                    (qc.swapaxes(0, 1), qp.swapaxes(0, 1)))
    o = o.swapaxes(0, 1).reshape(B, Sq, K, G, dv)
    return o.astype(q.dtype)


# ---------------------------------------------------------------------------
# banded local path (sliding window)
# ---------------------------------------------------------------------------

def banded_attn(q, k, v, q_pos, kv_pos, window: int, scale: float,
                chunk: int, static: bool = False):
    """Sliding-window attention: query chunk i attends to the static KV
    slice [i*cq - band, i*cq + cq). band = ceil(window/cq)*cq.
    Structurally sub-quadratic: compute O(S * (window + chunk))."""
    B, Sq, K, G, hd = q.shape
    cq = min(chunk, Sq)
    nq = Sq // cq
    band = -(-window // cq) * cq                     # multiple of cq >= window
    width = band + cq
    # pad KV on the left by `band` so every slice is in-bounds & static-size
    kpad = jnp.pad(k, ((0, 0), (band, 0), (0, 0), (0, 0)))
    vpad = jnp.pad(v, ((0, 0), (band, 0), (0, 0), (0, 0)))
    # padded positions: left-pad with large negative so mask kills them
    ppad = jnp.pad(kv_pos, ((0, 0), (band, 0)), constant_values=-(10 ** 9))

    qc = q.reshape(B, nq, cq, K, G, hd)
    qp = q_pos.reshape(B, nq, cq)

    def per_qchunk(i, qi, qpi):
        start = i * cq                               # offset into padded kv
        ks = jax.lax.dynamic_slice_in_dim(kpad, start, width, axis=1)
        vs = jax.lax.dynamic_slice_in_dim(vpad, start, width, axis=1)
        ps = jax.lax.dynamic_slice_in_dim(ppad, start, width, axis=1)
        s = jnp.einsum("bqkgd,btkd->bkgqt", qi, ks).astype(jnp.float32)
        s = s * scale
        mask = (ps[:, None, :] <= qpi[:, :, None]) & (
            ps[:, None, :] > qpi[:, :, None] - window)
        s = jnp.where(mask[:, None, None, :, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgqt,btkd->bqkgd", p.astype(vs.dtype), vs)
        return o

    if static:
        outs = [per_qchunk(i, qc[:, i], qp[:, i]) for i in range(nq)]
        o = jnp.concatenate(outs, axis=1)
        return o.reshape(B, Sq, K, G, -1).astype(q.dtype)
    o = jax.lax.map(
        lambda t: per_qchunk(t[0], t[1], t[2]),
        (jnp.arange(nq), qc.swapaxes(0, 1), qp.swapaxes(0, 1)))
    return o.swapaxes(0, 1).reshape(B, Sq, K, G, -1).astype(q.dtype)
