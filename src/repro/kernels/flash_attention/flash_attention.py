"""Flash attention (GQA + causal + sliding window) Pallas TPU kernel.

Online-softmax over KV tiles with f32 running (max, sum, acc) in VMEM
scratch. Grid = (B, H, S/bq, S/bk) with the KV tile index innermost; the
GQA mapping (q head h reads kv head h // G) lives in the K/V BlockSpec
index maps, so no repeated-KV materialization. Causally dead (q, k) tile
pairs are skipped with ``pl.when`` — on TPU the MXU never sees them, which
is what recovers the ~2x causal FLOP saving over a masked dense scan.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, scale, bq, bk, nk, causal, window, kv_len):
    i = pl.program_id(2)          # q tile
    j = pl.program_id(3)          # kv tile

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = i * bq
    k_start = j * bk
    # tile is live unless it is entirely in the causal future or entirely
    # outside the sliding window
    live = True
    if causal:
        live = k_start <= q_start + bq - 1
    if window > 0:
        live = jnp.logical_and(live, k_start + bk - 1 > q_start - window)

    @pl.when(live)
    def _update():
        q = q_ref[0, 0]                          # (bq, d)
        k = k_ref[0, 0]                          # (bk, d)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        qi = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kj = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), bool)
        if causal:
            mask &= kj <= qi
        if window > 0:
            mask &= kj > qi - window
        if kv_len < nk * bk:       # ragged S: padded keys are dead
            mask &= kj < kv_len
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    bq: int = 128, bk: int = 128, scale=None,
                    interpret: bool = False):
    """q (B,H,S,d); k,v (B,K,S,d), H = K*G -> (B,H,S,d).

    ``scale=None`` uses 1/sqrt(d); the rank-space prefill path attends at
    feature dim r with the scale folded into q and passes 1.0 explicitly.

    Ragged S (not a multiple of the block sizes) pads q/k/v up to the
    block grid and slices the output back — the same pad-and-slice path
    ``cur_matmul`` uses. Padded keys are masked inside the kernel (the
    causal mask alone does not kill them when ``causal=False``); padded
    query rows produce garbage that the final slice discards."""
    B, H, S, d = q.shape
    K = k.shape[1]
    if H % K != 0:
        raise ValueError(
            f"GQA requires n_heads % n_kv_heads == 0; got H={H}, K={K}")
    G = H // K
    bq = min(bq, S)
    bk = min(bk, S)
    # q and kv pad independently to their own block multiple (never to
    # lcm(bq, bk), which explodes for divisor-unfriendly clamps)
    Sq = -(-S // bq) * bq
    Sk = -(-S // bk) * bk
    if Sq != S:
        q = jnp.pad(q, [(0, 0), (0, 0), (0, Sq - S), (0, 0)])
    if Sk != S:
        pad = [(0, 0), (0, 0), (0, Sk - S), (0, 0)]
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    nq, nk = Sq // bq, Sk // bk
    if scale is None:
        scale = d ** -0.5

    kernel = functools.partial(
        _kernel, scale=scale, bq=bq, bk=bk, nk=nk,
        causal=causal, window=window, kv_len=S)

    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
    return out[:, :, :S, :] if Sq != S else out
