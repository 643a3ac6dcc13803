"""Operation and byte counts per kernel call and per model token, against
hand counts and against ``compiled.cost_analysis()`` of plain XLA
programs that do the same work at a small shape on the CPU.

Cost analysis cannot see into a Pallas call (interpret mode lowers the
kernel to a loop whose body it counts once), so each count is compared
with the plain computation the kernel replaces. Where the two differ by
design the test says by how much: XLA's bytes include intermediates the
kernels keep on chip, and its FLOPs include elementwise work (softmax,
norms) and the masked half of causal attention, which the counts leave
out."""
import jax
import jax.numpy as jnp
import pytest

import chipbench_tiny
from benchmarks.chip import counts, harness


def _cost(fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes]
    ca = jax.jit(fn).lower(*args).compile().cost_analysis()
    ca = ca[0] if isinstance(ca, (list, tuple)) else ca
    return ca["flops"], ca["bytes accessed"]


def test_paged_attention_hand_count():
    # 2 slots with 3 and 5 live positions, 2 kv-heads, group 4, r 8
    fl, nb = counts.paged_attention([3, 5], 2, 4, 8, itemsize=2)
    assert fl == 4 * 2 * 4 * 8 * 8
    assert nb == (2 * 2 * 8 * 8 + 2 * 2 * 2 * 4 * 8) * 2


def test_flash_attention_hand_count():
    fl, nb = counts.flash_attention([4], 2, 1, 8, itemsize=2)
    assert fl == 4 * 2 * 8 * 10            # 10 = 4 * 5 / 2 pairs
    assert nb == (2 * 2 + 2 * 1) * 8 * 4 * 2


def test_cur_matmul_hand_count():
    assert counts.cur_matmul(4, 16, 2, 8, itemsize=2) == (
        2 * 4 * 2 * (16 + 8), (4 * 16 + 16 * 2 + 2 * 8 + 4 * 8) * 2)


def test_cur_matmul_against_cost_analysis():
    M, m, r, n = 64, 256, 32, 512
    fl, nb = counts.cur_matmul(M, m, r, n, itemsize=4)
    ca_fl, ca_nb = _cost(lambda x, cu, rr: (x @ cu) @ rr,
                         (M, m), (m, r), (r, n))
    assert ca_fl == pytest.approx(fl, rel=1e-6)
    # XLA writes and reads back the (M, r) intermediate; the kernel does not
    assert ca_nb == pytest.approx(nb + 2 * M * r * 4, rel=1e-6)


def test_paged_attention_against_cost_analysis():
    K, G, r, c = 2, 4, 128, 256

    def attn(q, k, v):                      # one slot, c live positions
        s = jnp.einsum("kgr,ckr->kgc", q, k)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgc,ckr->kgr", p, v)
    fl, nb = counts.paged_attention([c], K, G, r, itemsize=4)
    ca_fl, ca_nb = _cost(attn, (K, G, r), (c, K, r), (c, K, r))
    # softmax adds a few FLOPs per score: K*G*c scores against 4*K*G*c*r
    assert fl <= ca_fl <= fl * 1.03
    # XLA moves more (scores, probabilities and relayouts go through
    # memory); the count is what any implementation has to move
    assert nb <= ca_nb


def test_flash_attention_against_cost_analysis():
    H, hd, S = 2, 64, 128

    def attn(q, k, v):                      # causal, masked square
        s = jnp.einsum("shd,thd->hst", q, k)
        mask = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
        p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
        return jnp.einsum("hst,thd->shd", p, v)
    fl, _ = counts.flash_attention([S], H, H, hd, itemsize=4)
    ca_fl, _ = _cost(attn, (S, H, hd), (S, H, hd), (S, H, hd))
    # XLA computes the whole S x S square, the count only the triangle
    full = fl * 2 * S / (S + 1)
    assert full <= ca_fl <= full * 1.05


def test_prompt_flops_against_the_program_forward():
    """The program's own full forward at a tiny size: it computes the
    vocabulary projection at every position and the full square of
    attention scores; the count takes one projection and the triangle."""
    from repro.models.model import forward
    cfg = harness.model_config(dict(
        chipbench_tiny._load(chipbench_tiny.ROOT,
                             "benchmarks/chip/configs/olmo-1b.json"),
        **chipbench_tiny.TINY, dtype="float32")).replace(scan_layers=False)
    S = 64
    params = jax.eval_shape(lambda: __import__(
        "repro.models", fromlist=["init_params"]).init_params(
            jax.random.PRNGKey(0), cfg))
    tokens = jax.ShapeDtypeStruct((1, S), jnp.int32)
    ca = jax.jit(lambda p, t: forward(p, cfg, {"tokens": t})).lower(
        params, tokens).compile().cost_analysis()
    ca = ca[0] if isinstance(ca, (list, tuple)) else ca
    hd = cfg.resolved_head_dim
    unembed = 2 * cfg.d_model * cfg.vocab_size
    want = (counts.prompt_flops(cfg, S) + (S - 1) * unembed
            + cfg.n_layers * 4 * cfg.n_heads * hd * S * (S - 1) // 2)
    # elementwise work (norms, rope, softmax, silu) adds a few percent
    assert want <= ca["flops"] <= want * 1.10


def test_decode_flops_is_the_sum_of_token_flops():
    cfg = harness.model_config(dict(
        chipbench_tiny._load(chipbench_tiny.ROOT,
                             "benchmarks/chip/configs/olmo-1b.json"),
        **chipbench_tiny.TINY))
    assert counts.decode_flops(cfg, 10, 3) == sum(
        counts.token_flops(cfg, 10 + j + 1, True) for j in range(3))


def test_roofline_share_names_its_bound():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert counts.roofline_share(100.0, 5.0, 2.0, peaks) == (50.0, "compute")
    assert counts.roofline_share(10.0, 20.0, 4.0, peaks) == (50.0, "memory")
