"""Paged-attention decode kernel: interpret-mode parity vs the gather
reference over ragged ctx_len / GQA / sliding window / CUR rank / inactive
slots, the rank-space fold algebra, and scan-safety (tier-1, CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.paged_attention.ops import paged_attention_op
from repro.kernels.paged_attention.ref import (
    NEG_INF, fold_q, paged_attention_ref, unfold_o)


def _case(B, K, G, r, nb, bs, maxb, *, seed=0, dtype=jnp.float32,
          inactive_last=True):
    """Random pools + a ragged block-table layout: per-row random ctx_len,
    exactly enough blocks assigned (rest -1), optionally one fully
    inactive slot (ctx 0, no blocks)."""
    rng = np.random.RandomState(seed)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, K, G, r), jnp.float32).astype(dtype)
    kp = jax.random.normal(ks[1], (nb, K, bs, r), jnp.float32).astype(dtype)
    vp = jax.random.normal(ks[2], (nb, K, bs, r), jnp.float32).astype(dtype)
    ctx = np.array([rng.randint(0, maxb * bs) for _ in range(B)], np.int32)
    table = np.full((B, maxb), -1, np.int32)
    free = list(rng.permutation(nb))
    for b in range(B):
        if inactive_last and b == B - 1:
            ctx[b] = 0
            continue
        for j in range(ctx[b] // bs + 1):
            table[b, j] = free.pop()
    return q, kp, vp, jnp.asarray(table), jnp.asarray(ctx)


def _assert_close(y, yr, dtype=jnp.float32):
    y = np.asarray(y, np.float32)
    yr = np.asarray(yr, np.float32)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    scale = np.abs(yr).max() + 1e-9
    assert np.abs(y - yr).max() / scale < tol


@pytest.mark.parametrize("B,K,G,r,nb,bs,maxb,win", [
    (3, 2, 2, 16, 12, 4, 5, 0),     # GQA, ragged ctx
    (4, 4, 1, 8, 16, 8, 3, 0),      # MHA
    (2, 1, 4, 32, 8, 16, 2, 0),     # MQA
    (3, 2, 3, 16, 12, 4, 5, 7),     # sliding window
    (3, 2, 2, 16, 12, 4, 5, 3),     # window < block_size
])
def test_kernel_matches_reference(B, K, G, r, nb, bs, maxb, win):
    q, kp, vp, table, ctx = _case(B, K, G, r, nb, bs, maxb)
    y = paged_attention_op(q, kp, vp, table, ctx, window=win)
    yr = paged_attention_ref(q, kp, vp, table, ctx, window=win)
    _assert_close(y, yr)
    # inactive slot (all -1 table row): exact zeros on both paths
    assert (np.asarray(y)[-1] == 0).all()
    assert (np.asarray(yr)[-1] == 0).all()


def test_kernel_bf16():
    q, kp, vp, table, ctx = _case(2, 2, 2, 16, 8, 4, 4,
                                  dtype=jnp.bfloat16)
    y = paged_attention_op(q, kp, vp, table, ctx)
    yr = paged_attention_ref(q, kp, vp, table, ctx)
    assert y.dtype == jnp.bfloat16
    _assert_close(y, yr, jnp.bfloat16)


def test_kernel_matches_dense_oracle():
    """Blocks laid out contiguously == plain masked softmax attention
    over the true context (positions 0..ctx inclusive)."""
    B, K, G, r, bs, maxb = 2, 2, 2, 16, 4, 4
    q, kp, vp, _, _ = _case(B, K, G, r, maxb * B, bs, maxb,
                            inactive_last=False)
    table = jnp.arange(B * maxb, dtype=jnp.int32).reshape(B, maxb)
    ctx = jnp.asarray([5, 13], jnp.int32)
    y = paged_attention_op(q, kp, vp, table, ctx)
    # dense oracle over the gathered-contiguous layout
    L = maxb * bs
    kd = kp[table].swapaxes(2, 3).reshape(B, L, K, r)
    vd = vp[table].swapaxes(2, 3).reshape(B, L, K, r)
    s = jnp.einsum("bkgr,btkr->bkgt", q, kd).astype(jnp.float32)
    mask = jnp.arange(L)[None] <= np.asarray(ctx)[:, None]
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    o = jnp.einsum("bkgt,btkr->bkgr", jax.nn.softmax(s, -1),
                   vd.astype(jnp.float32))
    _assert_close(y, o)


@pytest.mark.parametrize("r", [16, 8])     # r == hd (exact), r < hd
def test_rank_space_fold_equals_reconstruct(r):
    """Uk/Uv folds == reconstruct-then-attend: scale*q·(k_r Uk) ==
    (scale*q Ukᵀ)·k_r and (p v_r) Uv == p (v_r Uv), at full and reduced
    rank — the algebra the decode hot path rides on."""
    hd, B, K, G, bs, maxb, nb = 16, 2, 2, 2, 4, 3, 8
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    q = jax.random.normal(ks[0], (B, K, G, hd))
    kp = jax.random.normal(ks[1], (nb, K, bs, r))
    vp = jax.random.normal(ks[2], (nb, K, bs, r))
    uk = jax.random.normal(ks[3], (r, hd))
    uv = jax.random.normal(ks[4], (r, hd))
    table = jnp.arange(B * maxb, dtype=jnp.int32).reshape(B, maxb)
    ctx = jnp.asarray([7, 10], jnp.int32)
    scale = hd ** -0.5
    # rank space (what runtime/kernel do)
    o = unfold_o(paged_attention_ref(fold_q(q, uk, scale), kp, vp,
                                     table, ctx), uv)
    # reconstruct-then-attend oracle (the old decode formulation)
    L = maxb * bs
    kh = (kp[table].swapaxes(2, 3).reshape(B, L, K, r) @ uk)          # (B, L, K, hd)
    vh = (vp[table].swapaxes(2, 3).reshape(B, L, K, r) @ uv)
    s = jnp.einsum("bkgd,btkd->bkgt", q, kh).astype(jnp.float32) * scale
    mask = jnp.arange(L)[None] <= np.asarray(ctx)[:, None]
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    oh = jnp.einsum("bkgt,btkd->bkgd", jax.nn.softmax(s, -1),
                    vh.astype(jnp.float32))
    _assert_close(o, oh)


def test_kernel_scan_safe():
    """The op composes under lax.scan with a carried ctx (the
    paged_decode_scan contract: no host syncs, re-traceable)."""
    q, kp, vp, table, _ = _case(2, 2, 2, 8, 8, 4, 3, inactive_last=False)

    def body(ctx, _):
        return ctx + 1, paged_attention_op(q, kp, vp, table, ctx)

    ctx0 = jnp.asarray([0, 1], jnp.int32)
    _, ys = jax.jit(lambda c: jax.lax.scan(body, c, jnp.arange(3)))(ctx0)
    refs = [paged_attention_ref(q, kp, vp, table, ctx0 + t)
            for t in range(3)]
    for t in range(3):
        _assert_close(ys[t], refs[t])


@pytest.mark.parametrize("B,K,G,r,nb,bs,maxb,win,span", [
    (3, 2, 2, 16, 16, 4, 7, 0, 4),    # GQA, ragged ctx
    (2, 1, 4, 8, 12, 8, 4, 0, 2),     # MQA
    (3, 2, 3, 16, 16, 4, 7, 5, 3),    # sliding window
])
def test_q_span_matches_sequential(B, K, G, r, nb, bs, maxb, win, span):
    """The multi-position verify layout — (span*G) query rows sharing one
    pool gather, row g' masked to position ctx + g'//G — must be
    BIT-identical per position to span sequential single-position calls
    (each row's attended set and reduction order are unchanged). This is
    what makes speculative verify exact vs step-by-step decode."""
    q, kp, vp, table, ctx = _case(B, K, span * G, r, nb, bs, maxb,
                                  inactive_last=False)
    # leave room for span positions past ctx inside the assigned blocks
    ctx = jnp.minimum(ctx, (table >= 0).sum(1) * bs - span)
    ctx = jnp.maximum(ctx, 0)
    y = paged_attention_ref(q, kp, vp, table, ctx, window=win,
                            q_span=span)
    yk = paged_attention_op(q, kp, vp, table, ctx, window=win,
                            q_span=span)
    for s in range(span):
        qs = q[:, :, s * G:(s + 1) * G]
        ys = paged_attention_ref(qs, kp, vp, table, ctx + s, window=win)
        np.testing.assert_array_equal(
            np.asarray(y[:, :, s * G:(s + 1) * G]), np.asarray(ys))
        _assert_close(yk[:, :, s * G:(s + 1) * G], ys)


def test_q_span_one_is_plain_path():
    """q_span=1 must be the unchanged single-position code path."""
    q, kp, vp, table, ctx = _case(3, 2, 2, 16, 12, 4, 5)
    y0 = paged_attention_ref(q, kp, vp, table, ctx)
    y1 = paged_attention_ref(q, kp, vp, table, ctx, q_span=1)
    np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))


def test_q_span_must_divide_groups():
    q, kp, vp, table, ctx = _case(2, 2, 6, 8, 8, 4, 4)
    with pytest.raises(ValueError, match="q_span"):
        paged_attention_op(q, kp, vp, table, ctx, q_span=4)


def test_kernel_shape_mismatch_raises():
    q = jnp.zeros((2, 2, 2, 8))
    kp = jnp.zeros((4, 2, 4, 8))
    vp_bad = jnp.zeros((4, 2, 4, 4))
    table = jnp.zeros((2, 2), jnp.int32)
    ctx = jnp.zeros((2,), jnp.int32)
    with pytest.raises(ValueError, match="mismatch"):
        paged_attention_op(q, kp, vp_bad, table, ctx)
