"""Multi-head latent attention (DeepSeek-V2 §2.1; the DeepSeek-V3 block,
without query LoRA): projections, the latent cache and decode.

With h the normed input, per position:

    q = h W_q                        (H, dn + dr): q_C | RoPE(q_R)
    [c ; k_R] = h W_kv_a             c <- RMSNorm_kv(c), k_R <- RoPE(k_R)
    k_C = c W_UK,  v = c W_UV        (H, dn), (H, dv)
    o = softmax((q_C k_C + q_R k_R) / sqrt(dn + dr)) v,  out = o W_o

k_R is one key shared by every head. The parameters are ``wq``,
``wkv_a``, ``kv_norm``, ``wk_b`` (W_UK), ``wv_b`` (W_UV) and ``wo``.
Full-sequence attention goes through the registry's ``mix`` variant
(queries and keys are dn + dr wide, values dv), so the backends that
allow a value width other than the key width serve it.

The decode cache holds what the layer needs to rebuild keys and values:
the normed latent ``c`` (kv_lora_rank) and the roped ``kr`` (dr) per
position, beside each slot's absolute position.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.attention.xla import NEG_INF
from repro.models import attention as attn
from repro.models.layers import apply_rope, apply_w, rms_norm


def _scale(cfg) -> float:
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5


def mla_project(h, p, cfg, positions):
    """h (B,S,D) -> q (B,S,H,dn+dr) roped on its last dr, the normed
    latent c (B,S,r) and the roped shared key kr (B,S,dr)."""
    B, S, _ = h.shape
    H, dn, r = cfg.n_heads, cfg.qk_nope_head_dim, cfg.kv_lora_rank
    q = apply_w(h, p["wq"]).reshape(B, S, H, -1)
    q = jnp.concatenate(
        [q[..., :dn], apply_rope(q[..., dn:], positions, cfg.rope_theta)],
        axis=-1)
    ckr = apply_w(h, p["wkv_a"])
    c = rms_norm(ckr[..., :r], p["kv_norm"]["scale"], cfg.norm_eps)
    kr = apply_rope(ckr[..., None, r:], positions, cfg.rope_theta)[:, :, 0]
    return q, c, kr


def mla_keys_values(c, kr, p, cfg):
    """Per-head keys (B,T,H,dn+dr) and values (B,T,H,dv) from the latent
    and the shared rope key."""
    B, T, _ = c.shape
    H = cfg.n_heads
    kc = apply_w(c, p["wk_b"]).reshape(B, T, H, cfg.qk_nope_head_dim)
    v = apply_w(c, p["wv_b"]).reshape(B, T, H, cfg.v_head_dim)
    kr = jnp.broadcast_to(kr[:, :, None, :],
                          (B, T, H, cfg.qk_rope_head_dim)).astype(kc.dtype)
    return jnp.concatenate([kc, kr], axis=-1), v


def mla_attend(h, p, cfg, positions):
    """(out (B,S,D), normed latent c, roped kr) of one layer."""
    q, c, kr = mla_project(h, p, cfg, positions)
    k, v = mla_keys_values(c, kr, p, cfg)
    B, S = h.shape[:2]
    o = attn._mix(q[:, :, :, None], k, v, positions, 0, _scale(cfg), cfg)
    out = apply_w(o.reshape(B, S, cfg.n_heads * cfg.v_head_dim), p["wo"])
    return out, c, kr


def mla_forward(x, p, cfg, positions):
    """Full-sequence latent attention. x (B,S,D) normed -> (B,S,D)."""
    with jax.named_scope("mla"):
        return mla_attend(x, p, cfg, positions)[0]


def init_mla_cache(cfg, batch: int, max_len: int, dtype):
    return {
        "c": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
        "kr": jnp.zeros((batch, max_len, cfg.qk_rope_head_dim), dtype),
        # absolute position of each slot (-1 = empty)
        "pos": jnp.full((batch, max_len), -1, jnp.int32),
    }


def mla_prefill(x, p, cfg, positions, cache):
    """Forward + latent-cache fill. Returns (out, new_cache)."""
    with jax.named_scope("mla"):
        out, c, kr = mla_attend(x, p, cfg, positions)
    cache = dict(cache)
    for name, val in (("c", c), ("kr", kr), ("pos", positions)):
        cache[name] = jax.lax.dynamic_update_slice_in_dim(
            cache[name], val.astype(cache[name].dtype), 0, 1)
    return out, cache


def mla_decode(x, p, cfg, cache, pos):
    """Single-token decode. x (B,1,D) normed; pos (B,1) absolute."""
    with jax.named_scope("mla"):
        B = x.shape[0]
        q, c, kr = mla_project(x, p, cfg, pos)
        b_idx = jnp.arange(B)
        slot = pos[:, 0]
        cc = cache["c"].at[b_idx, slot].set(c[:, 0].astype(cache["c"].dtype))
        ckr = cache["kr"].at[b_idx, slot].set(
            kr[:, 0].astype(cache["kr"].dtype))
        cp = cache["pos"].at[b_idx, slot].set(slot)
        k, v = mla_keys_values(cc, ckr, p, cfg)            # (B,L,H,·)
        s = jnp.einsum("bqhd,bthd->bhqt", q, k).astype(jnp.float32)
        s = s * _scale(cfg)
        valid = (cp >= 0) & (cp <= pos[:, :1])
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
        pr = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqt,bthd->bqhd", pr.astype(v.dtype), v)
        out = apply_w(o.reshape(B, 1, -1), p["wo"])
    return out, {"c": cc, "kr": ckr, "pos": cp}
