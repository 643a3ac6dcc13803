"""Attention-backend registry: parity matrix of every (variant, backend)
pair vs the dense oracle over ragged/GQA/window/bf16 fixtures, gate and
caps resolution, and rank-space prefill fold-vs-reconstruct closeness
(tier-1, CPU; Pallas backends run in interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.attention import registry, xla
from repro.attention import prefill as pf
from repro.attention.registry import resolve, resolve_paged, resolve_prefill
from repro.serving.paged_cache import PagedConfig


def _assert_close(y, yr, dtype=jnp.float32, tol=None):
    y = np.asarray(y, np.float32)
    yr = np.asarray(yr, np.float32)
    if tol is None:
        tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    scale = np.abs(yr).max() + 1e-9
    assert np.abs(y - yr).max() / scale < tol


def _mix_case(B, S, K, G, d, *, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    qg = jax.random.normal(ks[0], (B, S, K, G, d), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, K, d), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, K, d), jnp.float32).astype(dtype)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    return qg, k, v, pos


# ---------------------------------------------------------------------------
# mix: every registered backend vs the dense oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,K,G,d,window,dtype", [
    (2, 16, 2, 2, 16, 0, jnp.float32),    # GQA
    (2, 48, 2, 1, 16, 0, jnp.float32),    # MHA, multi-chunk
    (1, 40, 1, 4, 8, 0, jnp.float32),     # MQA, ragged S (not chunk-mult)
    (2, 48, 2, 2, 16, 8, jnp.float32),    # sliding window
    (2, 24, 2, 2, 16, 8, jnp.bfloat16),   # bf16 + window
])
def test_mix_backend_parity_matrix(B, S, K, G, d, window, dtype,
                                   monkeypatch):
    """Every mix backend (flash_pallas in interpret mode included) must
    match the dense masked-softmax oracle on the same inputs."""
    monkeypatch.setenv("REPRO_FLASH_KERNEL", "1")
    qg, k, v, pos = _mix_case(B, S, K, G, d, dtype=dtype)
    scale = d ** -0.5
    oracle = xla.dense_attn(qg, k, v, pos, pos, window, scale)
    ctx = dict(seq_len=S, window=window, static=False,
               dense_max=xla.DENSE_MAX)
    ran = []
    for be in registry.backends("mix"):
        # the same caps + availability filter resolve() applies: banded
        # is only defined for window > 0, flash_xla cannot window
        if window > 0 and not be.caps.window:
            continue
        if not be.available(ctx):
            continue
        # chunked XLA refs require S % chunk == 0 (call sites bucket)
        y = be.fn(qg, k, v, pos, pos, window, scale,
                  chunk=16 if S % 16 == 0 else S, static=False)
        assert y.dtype == qg.dtype
        _assert_close(y, oracle, dtype)
        ran.append(be.name)
    assert "flash_pallas" in ran and "dense_xla" in ran
    if window == 0:
        assert "flash_xla" in ran
    else:
        assert "banded_xla" in ran


# ---------------------------------------------------------------------------
# paged_decode: both backends vs a dense oracle over contiguous blocks
# ---------------------------------------------------------------------------

def test_paged_decode_backend_parity():
    B, K, G, r, bs, maxb = 2, 2, 2, 16, 4, 4
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, K, G, r))
    kp = jax.random.normal(ks[1], (B * maxb, K, bs, r))
    vp = jax.random.normal(ks[2], (B * maxb, K, bs, r))
    table = jnp.arange(B * maxb, dtype=jnp.int32).reshape(B, maxb)
    ctx = jnp.asarray([5, 13], jnp.int32)
    # dense oracle over the gathered-contiguous layout
    L = maxb * bs
    kd = kp.reshape(B, maxb, K, bs, r).swapaxes(2, 3).reshape(B, L, K, r)
    vd = vp.reshape(B, maxb, K, bs, r).swapaxes(2, 3).reshape(B, L, K, r)
    # no scale: paged backends take pre-scaled (folded) queries
    logits = jnp.einsum("bkgr,blkr->bkgl", q, kd)
    mask = jnp.arange(L)[None, :] <= ctx[:, None]
    logits = jnp.where(mask[:, None, None, :], logits, -1e30)
    oracle = jnp.einsum("bkgl,blkr->bkgr", jax.nn.softmax(logits, -1), vd)
    for be in registry.backends("paged_decode"):
        y = be.fn(q, kp, vp, table, ctx, window=0, q_span=1)
        _assert_close(y, oracle)


# ---------------------------------------------------------------------------
# paged_prefill: fold vs reconstruct vs raw dense oracle
# ---------------------------------------------------------------------------

def _proj(hd, r, seed=0):
    """Calibration-style CUR link: r feature columns + pinv link matrix
    (exact inverse permutation at r == hd)."""
    rng = np.random.RandomState(seed)
    M = rng.randn(64, hd).astype(np.float32)
    out = []
    for s in (0, 1):
        perm = rng.permutation(hd)[:r]
        U = np.linalg.pinv(M[:, perm]) @ M
        out += [jnp.asarray(perm, jnp.int32), jnp.asarray(U)]
    return tuple(out)  # (qk, uk, qv, uv)


@pytest.mark.parametrize("r_frac,window", [
    (1, 0), (1, 8), (2, 0), (2, 8),
])
def test_prefill_fold_matches_reconstruct(r_frac, window):
    """rank_fold is a reassociation of reconstruct's matrix products:
    bit-close at full rank AND at r = hd/2, with kc/vc bit-identical."""
    B, S, K, G, hd = 2, 24, 2, 2, 16
    r = hd // r_frac
    qg, k, v, pos = _mix_case(B, S, K, G, hd, seed=7)
    proj = _proj(hd, r, seed=r_frac)
    scale = hd ** -0.5
    o_f, kc_f, vc_f = pf.fold_prefill(qg, k, v, pos, window, scale,
                                      None, proj)
    o_r, kc_r, vc_r = pf.reconstruct_prefill(qg, k, v, pos, window,
                                             scale, None, proj)
    _assert_close(o_f, o_r, tol=1e-4)
    assert (np.asarray(kc_f) == np.asarray(kc_r)).all()
    assert (np.asarray(vc_f) == np.asarray(vc_r)).all()
    if r == hd:
        # full rank: the link is an (pinv-computed) inverse permutation,
        # so both backends must match raw full-head-dim attention
        oracle = xla.dense_attn(qg, k, v, pos, pos, window, scale)
        _assert_close(o_f, oracle, tol=1e-4)
        _assert_close(o_r, oracle, tol=1e-4)


def test_prefill_fold_exact_at_full_rank_permutation():
    """With an exact permutation link (no pinv noise) the fold equals the
    raw dense oracle to fp32 tolerance."""
    B, S, K, G, hd = 2, 16, 2, 2, 16
    qg, k, v, pos = _mix_case(B, S, K, G, hd, seed=11)
    rng = np.random.RandomState(2)
    qk = rng.permutation(hd)
    qv = rng.permutation(hd)
    # U[i] maps kept column qk[i] back to its original slot, so
    # k_c @ U == k exactly (no pinv noise)
    perm_uk = np.zeros((hd, hd), np.float32)
    perm_uk[np.arange(hd), qk] = 1.0
    perm_uv = np.zeros((hd, hd), np.float32)
    perm_uv[np.arange(hd), qv] = 1.0
    proj = (jnp.asarray(qk, jnp.int32), jnp.asarray(perm_uk),
            jnp.asarray(qv, jnp.int32), jnp.asarray(perm_uv))
    scale = hd ** -0.5
    o_f, _, _ = pf.fold_prefill(qg, k, v, pos, 0, scale, None, proj)
    oracle = xla.dense_attn(qg, k, v, pos, pos, 0, scale)
    _assert_close(o_f, oracle)


# ---------------------------------------------------------------------------
# resolution: gates, caps filters, pins
# ---------------------------------------------------------------------------

def test_resolve_mix_order(monkeypatch):
    monkeypatch.setenv("REPRO_FLASH_KERNEL", "0")
    assert resolve("mix", seq_len=16, window=0).name == "dense_xla"
    assert resolve("mix", seq_len=9999, window=0).name == "flash_xla"
    assert resolve("mix", seq_len=9999, window=8).name == "banded_xla"
    # static traces (dry-run cost model) never take the oracle/Pallas path
    assert resolve("mix", seq_len=16, window=8,
                   static=True).name == "banded_xla"
    assert resolve("mix", seq_len=16, window=0,
                   static=True).name == "flash_xla"
    monkeypatch.setenv("REPRO_FLASH_KERNEL", "1")
    assert resolve("mix", seq_len=16, window=0).name == "flash_pallas"
    assert resolve("mix", seq_len=16, window=8).name == "flash_pallas"
    assert resolve("mix", seq_len=16, window=0,
                   static=True).name != "flash_pallas"


def test_resolve_caps_filter():
    # flash_xla cannot window: a huge windowed request must skip it
    be = resolve("mix", seq_len=10 ** 6, window=4)
    assert be.caps.window and be.name == "banded_xla"
    with pytest.raises(KeyError):
        resolve("no_such_variant")


def test_resolve_paged_pin(monkeypatch):
    assert resolve_paged(True).name == "paged_pallas"
    assert resolve_paged(False).name == "paged_xla"
    monkeypatch.setenv("REPRO_PAGED_KERNEL", "0")
    assert resolve_paged(None).name == "paged_xla"
    monkeypatch.setenv("REPRO_PAGED_KERNEL", "1")
    assert resolve_paged(None).name == "paged_pallas"


def test_resolve_prefill(monkeypatch):
    monkeypatch.delenv("REPRO_PREFILL_BACKEND", raising=False)
    assert resolve_prefill().name == "rank_fold"
    monkeypatch.setenv("REPRO_PREFILL_BACKEND", "reconstruct")
    assert resolve_prefill().name == "reconstruct"
    # explicit pins override the env (the Server's jit-cache contract)
    assert resolve_prefill("fold").name == "rank_fold"
    assert resolve_prefill("rank_fold").name == "rank_fold"
    monkeypatch.setenv("REPRO_PREFILL_BACKEND", "fold")
    assert resolve_prefill("reconstruct").name == "reconstruct"
    monkeypatch.setenv("REPRO_PREFILL_BACKEND", "bogus")
    with pytest.raises(ValueError):
        resolve_prefill()


def test_describe_covers_registry():
    rows = registry.describe()
    pairs = {(row["variant"], row["backend"]) for row in rows}
    assert {("mix", "flash_pallas"), ("mix", "dense_xla"),
            ("mix", "banded_xla"), ("mix", "flash_xla"),
            ("paged_decode", "paged_pallas"),
            ("paged_decode", "paged_xla"),
            ("paged_prefill", "rank_fold"),
            ("paged_prefill", "reconstruct")} <= pairs
    assert registry.variants() == ["mix", "paged_decode", "paged_prefill"]
    for row in rows:
        assert row["kind"] in ("pallas", "xla", "oracle")


# ---------------------------------------------------------------------------
# reconstructed-bytes accounting (the zero-materialization acceptance)
# ---------------------------------------------------------------------------

def test_reconstructed_bytes_accounting():
    from repro.configs import get_smoke
    cfg = get_smoke("olmo-1b")
    cur = PagedConfig(block_size=8, n_blocks=64, max_blocks_per_seq=8,
                      cur_kv=True, kv_rank=cfg.resolved_head_dim // 2)
    dense = PagedConfig(block_size=8, n_blocks=64, max_blocks_per_seq=8)
    # the fold path (and any dense pool) materializes zero full-head-dim
    # KV during prefill; only the reconstruct oracle pays for it
    assert pf.reconstructed_bytes_per_prefill(cfg, cur, 4, 64) == 0
    assert pf.reconstructed_bytes_per_prefill(
        cfg, cur, 4, 64, backend="rank_fold") == 0
    assert pf.reconstructed_bytes_per_prefill(
        cfg, dense, 4, 64, backend="reconstruct") == 0
    got = pf.reconstructed_bytes_per_prefill(
        cfg, cur, 4, 64, backend="reconstruct")
    from repro.serving.paged_cache import _attn_layers
    L = _attn_layers(cfg)
    want = (2 * L * 4 * 64 * cfg.n_kv_heads * cfg.resolved_head_dim
            * jnp.dtype(cfg.dtype).itemsize)
    assert got == want > 0
