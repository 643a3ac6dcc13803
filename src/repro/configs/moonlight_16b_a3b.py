"""moonlight-16b-a3b [moe, mla] — DeepSeek-V3 block at 16B
[hf:moonshotai/Moonlight-16B-A3B config.json; arXiv:2412.19437].

27L d_model=2048 16H vocab=163840, untied, RMSNorm eps 1e-5. Latent
attention without query LoRA: kv_lora_rank 512, q/k heads of 128 (no
RoPE) + 64 (RoPE, one key shared by all heads), values of 128, RoPE
theta 5e4. Layer 0 dense SwiGLU (d_ff 11264); layers 1-26 DeepSeekMoE:
64 routed experts of width 1408, top-6 by sigmoid score + selection
bias (noaux_tc, one group), renormalised, gates scaled by 2.446, and 2
shared experts run as one SwiGLU of width 2816.

Layout choices a checkpoint loader has to undo: HF's ``kv_b_proj`` holds
W_UK and W_UV interleaved per head; here they are ``wk_b`` (512, H*128)
and ``wv_b`` (512, H*128). RoPE rotates the two halves of the rope part
(HF de-interleaves the pairs first).
"""
from repro.configs.base import MLA, MLP, MOE, BlockSpec, ModelConfig

_DENSE = BlockSpec(MLA, MLP)
_MOE = BlockSpec(MLA, MOE)

CONFIG = ModelConfig(
    name="moonlight-16b-a3b",
    d_model=2048,
    n_layers=27,
    n_heads=16,
    n_kv_heads=16,
    head_dim=128,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_theta=50_000.0,
    d_ff=11264,
    moe_d_ff=1408,
    n_experts=64,
    n_experts_per_tok=6,
    n_shared_experts=2,
    moe_scoring="sigmoid",
    moe_router_bias=True,
    moe_routed_scale=2.446,
    vocab_size=163_840,
    norm_eps=1e-5,
    groups=(((_DENSE,), 1), ((_MOE,), 26)),
    fsdp=True,
    moe_impl="a2a",
    cur_targets=("wq", "wk_b", "w_gate", "shared.w_gate"),
)

SMOKE = CONFIG.replace(
    name="moonlight-16b-a3b-smoke",
    d_model=64, n_layers=4, n_heads=4, n_kv_heads=4, head_dim=16,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, d_ff=128, moe_d_ff=32, n_experts=8,
    n_experts_per_tok=3, n_shared_experts=2, vocab_size=256,
    groups=(((_DENSE,), 1), ((_MOE,), 3)),
    scan_layers=False, fsdp=False, moe_impl="dense", dtype="float32",
)
