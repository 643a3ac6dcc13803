"""Seeded weights for a latent-attention mixture-of-experts configuration
(the DeepSeek-V3 block), made on the device in one jitted call, in the
type they are served in.

The tree has the layout the program's ``init_params`` gives the
configuration with one group per layer: each layer is a group of one
block whose leaves carry a leading axis of 1, so CURe hands the layers
over without slicing a copy. An MoE layer holds the router over all
``n_experts`` and the stacks of the experts held here, the contiguous
share ``[0, n_experts_held)``; each expert's values come from its own
index in the router, so a share holds the same experts whatever its
size. Weights are normal with std 1/sqrt(fan-in), the embedding 0.02,
norm scales 1, the router float32 and the selection bias normal with
std ``ROUTER_BIAS_STD`` (assumed: a trained bias balances the experts'
loads and is not published as a distribution).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.chip.weights import seed_key

ROUTER_BIAS_STD = 0.02

SIZE_KEYS = ("d_model", "n_layers", "n_heads", "kv_lora_rank",
             "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "d_ff",
             "moe_d_ff", "n_experts", "n_experts_held", "n_shared_experts",
             "first_k_dense_replace", "vocab_size", "dtype")


def _normal(key, shape, fan_in, dtype):
    std = 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _experts(key, n_held, shape, fan_in, dtype):
    """(n_held,) + shape, expert e from fold_in(key, e)."""
    keys = jax.vmap(lambda e: jax.random.fold_in(key, e))(jnp.arange(n_held))
    return jax.vmap(lambda k: _normal(k, shape, fan_in, dtype))(keys)


def _block(cfg, li, key, dtype):
    D, H, r = cfg["d_model"], cfg["n_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    k = [jax.random.fold_in(key, i) for i in range(16)]
    ones = functools.partial(jnp.ones, dtype=dtype)
    b = {"norm1": {"scale": ones((D,))}, "norm2": {"scale": ones((D,))},
         "wq": _normal(k[0], (D, H * (dn + dr)), D, dtype),
         "wkv_a": _normal(k[1], (D, r + dr), D, dtype),
         "kv_norm": {"scale": ones((r,))},
         "wk_b": _normal(k[2], (r, H * dn), r, dtype),
         "wv_b": _normal(k[3], (r, H * dv), r, dtype),
         "wo": _normal(k[4], (H * dv, D), H * dv, dtype)}
    if li < cfg["first_k_dense_replace"]:
        F = cfg["d_ff"]
        b.update(w_gate=_normal(k[5], (D, F), D, dtype),
                 w_up=_normal(k[6], (D, F), D, dtype),
                 w_down=_normal(k[7], (F, D), F, dtype))
        return b
    E, Eh, F = cfg["n_experts"], cfg["n_experts_held"], cfg["moe_d_ff"]
    Fs = cfg["n_shared_experts"] * F
    b.update(
        router=_normal(k[8], (D, E), D, jnp.float32),
        router_bias=ROUTER_BIAS_STD * jax.random.normal(k[9], (E,)),
        w_gate=_experts(k[10], Eh, (D, F), D, dtype),
        w_up=_experts(k[11], Eh, (D, F), D, dtype),
        w_down=_experts(k[12], Eh, (F, D), F, dtype),
        shared={"w_gate": _normal(k[13], (D, Fs), D, dtype),
                "w_up": _normal(k[14], (D, Fs), D, dtype),
                "w_down": _normal(k[15], (Fs, D), Fs, dtype)})
    return b


def _make(items, key):
    cfg = dict(items)
    dtype = jnp.dtype(cfg["dtype"])
    D, V = cfg["d_model"], cfg["vocab_size"]
    k_embed, k_head, k_layers = jax.random.split(key, 3)
    params = {"embed": (jax.random.normal(k_embed, (V, D), jnp.float32)
                        * 0.02).astype(dtype),
              "out_head": _normal(k_head, (D, V), D, dtype),
              "final_norm": {"scale": jnp.ones((D,), dtype)}}
    params["groups"] = [
        [jax.tree.map(lambda a: a[None],
                      _block(cfg, li, jax.random.fold_in(k_layers, li),
                             dtype))]
        for li in range(cfg["n_layers"])]
    return params


@functools.lru_cache(maxsize=None)
def _jitted(items):
    return jax.jit(functools.partial(_make, items))


def make(cfg: dict, seed: int):
    """The whole weight tree of the configuration file ``cfg`` for
    ``seed``, on the default device, made by one jitted call."""
    items = tuple((k, cfg[k]) for k in SIZE_KEYS)
    return jax.block_until_ready(_jitted(items)(seed_key(seed)))
