"""repro.serving: block-allocator invariants, continuous-batching
correctness vs the seed engine, preemption round-trips, CUR-KV parity,
and per-request sampling."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.models import init_params
from repro.serve.engine import generate
from repro.serving import (
    BlockAllocator, PagedConfig, SamplingParams, Server)
from repro.serving import paged_cache as pcache
from repro.serving import sampling as smp


@pytest.fixture(scope="module")
def olmo():
    cfg = get_smoke("olmo-1b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def prompts(olmo):
    cfg, _ = olmo
    rng = np.random.RandomState(0)
    return [rng.randint(0, cfg.vocab_size, size=n).tolist()
            for n in (5, 9, 13, 7, 11)]


def _run(params, cfg, pc, prompts, n_new=6, C=4, **submit_kw):
    srv = Server(params, cfg, pc, max_concurrency=C)
    for p in prompts:
        srv.submit(p, max_new_tokens=n_new, **submit_kw)
    res = srv.drain()
    return {r: res[r].out_tokens for r in res}, srv


# ---------------------------------------------------------------------------
# allocator invariants
# ---------------------------------------------------------------------------

def test_allocator_alloc_free_roundtrip():
    a = BlockAllocator(8)
    b1 = a.alloc(3)
    b2 = a.alloc(5)
    assert a.n_free == 0 and a.alloc(1) is None
    # no double allocation: every live block id is unique
    assert len(set(b1) | set(b2)) == 8
    a.free(b1)
    assert a.n_free == 3
    b3 = a.alloc(3)
    assert set(b3) == set(b1)
    a.free(b2)
    a.free(b3)
    assert a.n_free == 8


def test_allocator_double_free_raises():
    a = BlockAllocator(4)
    b = a.alloc(2)
    a.free(b)
    with pytest.raises(ValueError):
        a.free(b)


def test_allocator_fork_refcounts():
    a = BlockAllocator(4)
    b = a.alloc(2)
    shared = a.fork(b)
    assert shared == b and a.ref(b[0]) == 2
    a.free(b)                      # one reference down, still live
    assert a.n_free == 2 and a.ref(b[0]) == 1
    a.free(shared)
    assert a.n_free == 4


def test_allocator_copy_on_write():
    a = BlockAllocator(4)
    b = a.alloc(1)
    assert a.copy_on_write(b[0]) == b[0]        # exclusive: in place
    a.fork(b)
    fresh = a.copy_on_write(b[0])
    assert fresh != b[0] and a.ref(b[0]) == 1 and a.ref(fresh) == 1
    a.free([fresh])
    a.free(b)
    assert a.n_free == 4


def test_request_over_capacity_rejected(olmo):
    cfg, params = olmo
    pc = PagedConfig(block_size=4, n_blocks=4, max_blocks_per_seq=4)
    srv = Server(params, cfg, pc, max_concurrency=2)
    with pytest.raises(ValueError):
        srv.submit(list(range(30)), max_new_tokens=8)


# ---------------------------------------------------------------------------
# continuous batching correctness
# ---------------------------------------------------------------------------

def test_ragged_batch_matches_seed_engine(olmo, prompts):
    """Greedy continuous batching over ragged prompts reproduces the seed
    static-batch engine per request (same prefill math, paged decode)."""
    cfg, params = olmo
    pc = PagedConfig(block_size=8, n_blocks=64, max_blocks_per_seq=8)
    out, srv = _run(params, cfg, pc, prompts)
    for i, p in enumerate(prompts):
        ref = np.asarray(
            generate(params, cfg, jnp.asarray([p]), 6).tokens)[0].tolist()
        assert out[i] == ref, f"request {i} diverged"
    # all blocks returned to the pool after drain
    assert srv.scheduler.alloc.n_free == pc.n_blocks
    assert srv.stats()["completed"] == len(prompts)


def test_preemption_restore_roundtrip(olmo, prompts):
    """A pool too small for the workload forces eviction; the preempted
    request must resume bit-exactly after its re-prefill."""
    cfg, params = olmo
    big = PagedConfig(block_size=4, n_blocks=64, max_blocks_per_seq=8)
    tiny = PagedConfig(block_size=4, n_blocks=7, max_blocks_per_seq=8)
    ref, _ = _run(params, cfg, big, prompts[:4], C=3)
    out, srv = _run(params, cfg, tiny, prompts[:4], C=3)
    assert srv.scheduler.n_preemptions > 0, "pool sized to force eviction"
    assert out == ref
    assert any(r.n_preempted > 0 for r in srv.finished.values())
    assert srv.scheduler.alloc.n_free == tiny.n_blocks


def test_eos_retirement(olmo, prompts):
    cfg, params = olmo
    pc = PagedConfig(block_size=8, n_blocks=64, max_blocks_per_seq=8)
    # greedy reference: pick the first token value that differs from the
    # first emission, so retirement happens mid-stream at a known index
    ref = np.asarray(
        generate(params, cfg, jnp.asarray([prompts[0]]), 6).tokens)[0]
    idx = int(np.argmax(ref != ref[0]))
    assert idx > 0, "fixture emits a constant stream; pick another seed"
    eos = int(ref[idx])
    out, srv = _run(params, cfg, pc, prompts[:1], n_new=6, C=2, eos_id=eos)
    req = srv.finished[0]
    assert req.finish_reason == "eos"
    assert req.out_tokens[-1] == eos and len(req.out_tokens) == idx + 1


def test_arrival_staggering_and_stats(olmo, prompts):
    cfg, params = olmo
    pc = PagedConfig(block_size=8, n_blocks=64, max_blocks_per_seq=8)
    srv = Server(params, cfg, pc, max_concurrency=2)
    for p in prompts:
        srv.submit(p, max_new_tokens=4)
    res = srv.drain()
    st = srv.stats()
    assert st["completed"] == len(prompts)
    assert st["tokens_generated"] == 4 * len(prompts)
    assert st["queue_depth_max"] >= len(prompts) - 2  # admission capped
    assert all(r.ttft is not None and r.ttft >= 0 for r in res.values())


# ---------------------------------------------------------------------------
# CUR-compressed KV cache
# ---------------------------------------------------------------------------

def test_cur_kv_full_rank_exact(olmo, prompts):
    """r == head_dim: the DEIM selection is a permutation and the link
    matrix its inverse — CUR-KV must match the dense pool exactly."""
    cfg, params = olmo
    hd = cfg.resolved_head_dim
    dense = PagedConfig(block_size=8, n_blocks=64, max_blocks_per_seq=8)
    curkv = PagedConfig(block_size=8, n_blocks=64, max_blocks_per_seq=8,
                        cur_kv=True, kv_rank=hd)
    ref, _ = _run(params, cfg, dense, prompts)
    out, _ = _run(params, cfg, curkv, prompts)
    assert out == ref


def test_cur_kv_compressed_bytes_and_finite(olmo, prompts):
    """r == head_dim // 2: half the cache bytes; decode stays finite.
    Prompt attention runs in rank space (the rank_fold prefill backend),
    so every position — the first sampled token included — sees the same
    compressed KV decode reads, and may legitimately differ from the
    dense run."""
    cfg, params = olmo
    hd = cfg.resolved_head_dim
    dense = PagedConfig(block_size=8, n_blocks=64, max_blocks_per_seq=8)
    half = PagedConfig(block_size=8, n_blocks=64, max_blocks_per_seq=8,
                       cur_kv=True, kv_rank=hd // 2)
    ref, s0 = _run(params, cfg, dense, prompts)
    out, s1 = _run(params, cfg, half, prompts)
    assert s1.cache_bytes() * 2 == s0.cache_bytes()
    for i in ref:
        assert all(0 <= t < cfg.vocab_size for t in out[i])
    lps = [lp for r in s1.finished.values() for lp in r.out_logprobs]
    assert np.isfinite(lps).all()


@pytest.mark.parametrize("rank_div", [1, 2])     # r == hd, r == hd/2
def test_decode_fold_matches_old_reconstruct_path(olmo, rank_div):
    """The rank-space decode (q̃ = scale·q·Ukᵀ, post-softmax ·Uv) is
    bit-close to the pre-fold formulation that gathered the pool and
    reconstructed full-head-dim K/V before a dense einsum."""
    from repro.serving import runtime

    cfg, params = olmo
    hd = cfg.resolved_head_dim
    r = hd // rank_div
    key = jax.random.PRNGKey(2)
    k1, k2, k3 = jax.random.split(key, 3)
    B, K, G, nb, bs, maxb = 3, cfg.n_kv_heads, 1, 12, 4, 3
    pool_k = jax.random.normal(k1, (nb, K, bs, r))
    pool_v = jax.random.normal(k2, (nb, K, bs, r))
    qg = jax.random.normal(k3, (B, K, G, hd))
    # calibrated-style link matrices (r, hd); identity-ish at full rank
    uk = pcache.kv_projection(jax.random.normal(k1, (64, hd)), r)[1]
    uv = pcache.kv_projection(jax.random.normal(k2, (64, hd)), r)[1]
    table = jnp.asarray(np.arange(B * maxb).reshape(B, maxb), jnp.int32)
    ctx = jnp.asarray([2, 7, 11], jnp.int32)
    scale = hd ** -0.5
    o_new = runtime._paged_attn(qg, pool_k, pool_v, table, ctx,
                                uk, uv, scale, 0)
    # old formulation: gather -> reconstruct to full hd -> dense einsum
    ck = pcache.reconstruct_kv(pcache.gather_kv(pool_k, table), uk)
    cv = pcache.reconstruct_kv(pcache.gather_kv(pool_v, table), uv)
    s = jnp.einsum("bkgd,btkd->bkgt", qg, ck).astype(jnp.float32) * scale
    L = maxb * bs
    valid = jnp.arange(L)[None, :] <= ctx[:, None]
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    pr = jax.nn.softmax(s, axis=-1)
    o_old = jnp.einsum("bkgt,btkd->bkgd", pr.astype(cv.dtype), cv)
    np.testing.assert_allclose(np.asarray(o_new), np.asarray(o_old),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("cur_kv", [False, True])
def test_decode_scan_kernel_on_off_identical(olmo, prompts, monkeypatch,
                                             cur_kv):
    """End-to-end greedy serving (prefill + multi-step decode windows)
    emits identical tokens with the paged Pallas kernel forced on
    (interpret mode on CPU) and forced off (rank-space XLA path) — for
    dense AND CUR-KV pools: the gate may only change dispatch, never the
    sampled stream (the rank-fold prefill keys on cur_kv, not the
    gate)."""
    cfg, params = olmo
    kw = dict(cur_kv=True, kv_rank=cfg.resolved_head_dim // 2) \
        if cur_kv else {}
    pc = PagedConfig(block_size=4, n_blocks=16, max_blocks_per_seq=4, **kw)

    def go(mode):
        monkeypatch.setenv("REPRO_PAGED_KERNEL", mode)
        out, srv = _run(params, cfg, pc, prompts[:2], n_new=5, C=2)
        assert srv.stats()["n_decode_steps"] > 1   # scan windows ran
        return out, srv.stats()["gathered_bytes_per_step"]

    out_off, bytes_off = go("0")
    out_on, bytes_on = go("1")
    assert out_on == out_off
    # the kernel path reads blocks in place: nothing is gathered
    assert bytes_on == 0 and bytes_off > 0


def test_kv_projection_reconstruction():
    """Low-rank rows reconstruct near-exactly through (q, U)."""
    key = jax.random.PRNGKey(3)
    k1, k2 = jax.random.split(key)
    M = jax.random.normal(k1, (128, 4)) @ jax.random.normal(k2, (4, 16))
    q, U = pcache.kv_projection(M, 8)
    assert len(set(np.asarray(q).tolist())) == 8
    err = float(jnp.linalg.norm(M[:, q] @ U - M) / jnp.linalg.norm(M))
    assert err < 1e-4


# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------

def test_sampling_greedy_and_determinism():
    key = jax.random.PRNGKey(0)
    logits = jax.random.normal(key, (4, 32))
    temps = jnp.asarray([0.0, 0.0, 1.0, 1.0])
    top_ks = jnp.asarray([0, 0, 0, 5], jnp.int32)
    top_ps = jnp.asarray([1.0, 1.0, 0.9, 1.0])
    keys = jnp.stack([jnp.asarray(smp.request_key(0, i, 0), jnp.uint32)
                      for i in range(4)])
    t1, lp1 = smp.sample_tokens(logits, temps, top_ks, top_ps, keys)
    t2, _ = smp.sample_tokens(logits, temps, top_ks, top_ps, keys)
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))
    # greedy rows equal argmax; logprobs from untempered distribution
    np.testing.assert_array_equal(
        np.asarray(t1[:2]), np.asarray(jnp.argmax(logits[:2], axis=-1)))
    ref_lp = jax.nn.log_softmax(logits)[jnp.arange(4), t1]
    np.testing.assert_allclose(np.asarray(lp1), np.asarray(ref_lp),
                               rtol=1e-5)


def test_sampling_top_k_one_is_greedy():
    logits = jax.random.normal(jax.random.PRNGKey(5), (3, 64))
    B = logits.shape[0]
    temps = jnp.ones((B,))
    top_ks = jnp.full((B,), 1, jnp.int32)
    top_ps = jnp.ones((B,))
    keys = jnp.stack([jnp.asarray(smp.request_key(9, i, 0), jnp.uint32)
                      for i in range(B)])
    toks, _ = smp.sample_tokens(logits, temps, top_ks, top_ps, keys)
    np.testing.assert_array_equal(
        np.asarray(toks), np.asarray(jnp.argmax(logits, axis=-1)))


def test_per_request_temperature_server(olmo, prompts):
    """Per-request sampling params coexist in one decode batch and are
    reproducible for a fixed seed."""
    cfg, params = olmo
    pc = PagedConfig(block_size=8, n_blocks=64, max_blocks_per_seq=8)

    def go():
        srv = Server(params, cfg, pc, max_concurrency=4)
        srv.submit(prompts[0], 5)                       # greedy
        srv.submit(prompts[1], 5,
                   sampling=SamplingParams(temperature=1.0, seed=11))
        srv.submit(prompts[2], 5,
                   sampling=SamplingParams(temperature=0.8, top_k=8,
                                           seed=12))
        res = srv.drain()
        return {r: res[r].out_tokens for r in res}

    a, b = go(), go()
    assert a == b
    ref = np.asarray(
        generate(params, cfg, jnp.asarray([prompts[0]]), 5).tokens)[0]
    assert a[0] == ref.tolist()


# ---------------------------------------------------------------------------
# seed engine EOS satellite
# ---------------------------------------------------------------------------

def test_generate_eos_freezes_and_early_exits(olmo, prompts):
    cfg, params = olmo
    p = jnp.asarray([prompts[0], prompts[0]])
    ref = np.asarray(generate(params, cfg, p, 8).tokens)
    eos = int(ref[0, 2])                     # hit at step 2
    out = generate(params, cfg, p, 8, eos_id=eos)
    toks = np.asarray(out.tokens)
    lps = np.asarray(out.logprobs)
    i = int(np.argmax(toks[0] == eos))
    # frozen after eos: token stays eos, logprob 0, both rows identical
    assert (toks[:, i + 1:] == eos).all()
    assert (lps[:, i + 1:] == 0.0).all()
    # early exit: loop stopped once all rows were done
    assert toks.shape[1] <= 8


def test_generate_without_eos_unchanged(olmo, prompts):
    cfg, params = olmo
    p = jnp.asarray([prompts[0]])
    out = generate(params, cfg, p, 6)
    assert out.tokens.shape == (1, 6)
    assert np.isfinite(np.asarray(out.logprobs)).all()


# ---------------------------------------------------------------------------
# prefill backend (rank_fold vs reconstruct) and sliding-window eviction
# ---------------------------------------------------------------------------

def test_prefill_backend_fold_vs_reconstruct_identity(olmo, prompts,
                                                      monkeypatch):
    """End-to-end greedy decode with the rank-space prefill on (rank_fold)
    vs off (reconstruct oracle): identical token streams, and only the
    oracle materializes full-head-dim KV during prefill."""
    cfg, params = olmo
    hd = cfg.resolved_head_dim
    pc = PagedConfig(block_size=8, n_blocks=64, max_blocks_per_seq=8,
                     cur_kv=True, kv_rank=hd // 2)
    monkeypatch.setenv("REPRO_PREFILL_BACKEND", "reconstruct")
    ref, s0 = _run(params, cfg, pc, prompts)
    monkeypatch.setenv("REPRO_PREFILL_BACKEND", "fold")
    out, s1 = _run(params, cfg, pc, prompts)
    assert out == ref
    st0, st1 = s0.stats(), s1.stats()
    assert st0["prefill_backend"] == "reconstruct"
    assert st1["prefill_backend"] == "rank_fold"
    assert st1["attn_backends"]["paged_prefill"] == "rank_fold"
    # acceptance: the fold path materializes ZERO full-head-dim KV
    assert st1["reconstructed_bytes_per_prefill"] == 0
    assert st0["reconstructed_bytes_per_prefill"] > 0


def _all_local_cfg():
    """gemma3 smoke with every layer sliding-window (the mixed stack's
    single global layer pins the whole context, window=0 for serving)."""
    from repro.configs.base import ATTN_LOCAL, MLP, BlockSpec
    cfg = get_smoke("gemma3-1b")
    loc = BlockSpec(ATTN_LOCAL, MLP)
    return cfg.replace(name="gemma3-smoke-all-local",
                       groups=(((loc,) * cfg.n_layers, 1),))


def test_serving_window_requires_fully_local_stack():
    mixed = get_smoke("gemma3-1b")
    assert pcache.serving_window(mixed) == 0        # one global layer
    local = _all_local_cfg()
    assert pcache.serving_window(local) == local.window > 0


def test_window_eviction_pool_drain(prompts, monkeypatch):
    """Sliding-window serving under scheduler churn: out-of-window blocks
    are freed as decode advances, occupancy returns to zero on drain, and
    tokens are identical to the no-eviction run (the window mask already
    kills evicted positions — eviction only reclaims dead pool space)."""
    cfg = _all_local_cfg()
    params = init_params(jax.random.PRNGKey(1), cfg)
    pc = PagedConfig(block_size=4, n_blocks=32, max_blocks_per_seq=8)
    out, srv = _run(params, cfg, pc, prompts, n_new=12, C=2)
    assert srv.window == cfg.window
    alloc = srv.scheduler.alloc
    assert alloc.blocks_freed_window > 0
    # pool-drain invariant: every block (evicted or retired) came back
    alloc.assert_used(exactly=0)
    assert alloc.n_free == pc.n_blocks
    st = srv.stats()
    assert st["window"] == cfg.window
    assert st["window_blocks_freed"] == alloc.blocks_freed_window
    # eviction must not change a single sampled token
    monkeypatch.setattr(pcache, "serving_window", lambda _cfg: 0)
    ref, srv0 = _run(params, cfg, pc, prompts, n_new=12, C=2)
    assert srv0.window == 0
    assert srv0.scheduler.alloc.blocks_freed_window == 0
    assert out == ref
