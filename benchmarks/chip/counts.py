"""Operations and bytes the algorithm needs, per kernel call and per model
token, computed from shapes. Rooflines and MFU divide these by device
time; they count what the computation requires, not what a kernel
happens to do (padding, dead blocks and recomputation are not counted).
"""
from __future__ import annotations

from typing import Sequence


def paged_attention(ctx_lens: Sequence[int], n_kv: int, group: int, r: int,
                    itemsize: int = 2):
    """One decode query per slot against its paged K/V at feature dim r.

    ``ctx_lens``: live positions per slot (the newest token included).
    FLOPs: QK and PV, 2 each per (query head, position, feature).
    Bytes: every live K and V entry once, plus the queries and outputs.
    Returns (flops, bytes)."""
    pos = sum(int(c) for c in ctx_lens)
    b = len(ctx_lens)
    flops = 4 * n_kv * group * r * pos
    nbytes = (2 * n_kv * r * pos + 2 * b * n_kv * group * r) * itemsize
    return flops, nbytes


def flash_attention(lengths: Sequence[int], n_heads: int, n_kv: int,
                    head_dim: int, itemsize: int = 2):
    """Causal prefill attention of ragged prompts (real tokens only).

    FLOPs: QK and PV over the causal triangle, 2 each per (head, query,
    key <= query, feature). Bytes: Q and O of every head, K and V of
    every kv-head, each read or written once. Returns (flops, bytes)."""
    tri = sum(int(n) * (int(n) + 1) // 2 for n in lengths)
    toks = sum(int(n) for n in lengths)
    flops = 4 * n_heads * head_dim * tri
    nbytes = (2 * n_heads + 2 * n_kv) * head_dim * toks * itemsize
    return flops, nbytes


def cur_matmul(M: int, m: int, r: int, n: int, itemsize: int = 2):
    """``(x @ CU) @ R`` with x (M, m), CU (m, r), R (r, n): the (M, r)
    intermediate stays on chip. Returns (flops, bytes)."""
    flops = 2 * M * r * (m + n)
    nbytes = (M * m + m * r + r * n + M * n) * itemsize
    return flops, nbytes


def layer_matmul_params(cfg) -> int:
    """Weights one token multiplies through in one decoder layer."""
    D, H, K = cfg.d_model, cfg.n_heads, cfg.n_kv_heads
    hd, F = cfg.resolved_head_dim, cfg.d_ff
    n_mlp = 3 if cfg.gated_mlp else 2
    return D * H * hd + 2 * D * K * hd + H * hd * D + n_mlp * D * F


def token_flops(cfg, context: int, unembed: bool) -> int:
    """Forward FLOPs of one token that attends ``context`` positions
    (itself included): every layer's matmuls, attention over the
    context, and the vocabulary projection when its logits are used."""
    hd = cfg.resolved_head_dim
    f = cfg.n_layers * (2 * layer_matmul_params(cfg)
                        + 4 * cfg.n_heads * hd * context)
    if unembed:
        f += 2 * cfg.d_model * cfg.vocab_size
    return f


def prompt_flops(cfg, length: int) -> int:
    """A prompt of ``length`` tokens: each attends its causal prefix;
    only the last one's logits are used (they give the first token)."""
    hd = cfg.resolved_head_dim
    per_layer = (2 * layer_matmul_params(cfg) * length
                 + 4 * cfg.n_heads * hd * length * (length + 1) // 2)
    return cfg.n_layers * per_layer + 2 * cfg.d_model * cfg.vocab_size


def decode_flops(cfg, context: int, tokens: int) -> int:
    """``tokens`` generated tokens from a cache of ``context`` tokens: the
    j-th attends ``context + j + 1`` positions and needs its logits."""
    hd = cfg.resolved_head_dim
    attended = tokens * context + tokens * (tokens + 1) // 2
    return (tokens * token_flops(cfg, 0, True)
            + cfg.n_layers * 4 * cfg.n_heads * hd * attended)


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peaks: dict):
    """(share in %, the bound that applies): the least time the chip
    could take, the larger of FLOPs over peak FLOP/s and bytes over peak
    bandwidth, over the measured time."""
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    bound = "compute" if t_flops >= t_bytes else "memory"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
