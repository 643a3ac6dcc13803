"""Seeded weights for a decoder configuration, made on the device in one
jitted call, in the type they are served in.

The tree has the layout the program's ``init_params`` gives (one scan
group of attention + MLP blocks) so the program takes it as is, while the
values come from here: the references regenerate the same weights from
the same seed and never read anything the program made.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (more than 32 bits included)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    for shift in (31, 62):
        key = jax.random.fold_in(key, (seed >> shift) & 0x7FFFFFFF)
    return key


def block_shapes(cfg: dict) -> dict:
    """name -> (shape without the layer axis, fan-in or None for ones)."""
    D, H, K = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"]
    hd, F = cfg["head_dim"], cfg["d_ff"]
    out = {
        "wq": ((D, H * hd), D), "wk": ((D, K * hd), D),
        "wv": ((D, K * hd), D), "wo": ((H * hd, D), H * hd),
        "w_gate": ((D, F), D), "w_up": ((D, F), D), "w_down": ((F, D), F),
    }
    if cfg["parametric_norm"]:
        out["norm1"] = ((D,), None)
        out["norm2"] = ((D,), None)
    return out


def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _make(items, key):
    cfg = dict(items)
    dtype = jnp.dtype(cfg["dtype"])
    L, D, V = cfg["n_layers"], cfg["d_model"], cfg["vocab_size"]
    k_embed, k_head, k_layers = jax.random.split(key, 3)
    params = {"embed": _normal(k_embed, (V, D), 0.02, dtype)}
    if not cfg["tie_embeddings"]:
        params["out_head"] = _normal(k_head, (D, V), 1 / math.sqrt(D), dtype)
    if cfg["parametric_norm"]:
        params["final_norm"] = {"scale": jnp.ones((D,), dtype)}
    block = {}
    for i, (name, (shape, fan_in)) in enumerate(
            sorted(block_shapes(cfg).items())):
        if fan_in is None:
            block[name] = {"scale": jnp.ones((L,) + shape, dtype)}
        else:
            block[name] = _normal(jax.random.fold_in(k_layers, i),
                                  (L,) + shape, 1 / math.sqrt(fan_in), dtype)
    params["groups"] = [[block]]
    return params


SIZE_KEYS = ("d_model", "n_layers", "n_heads", "n_kv_heads", "head_dim",
             "d_ff", "vocab_size", "tie_embeddings", "parametric_norm",
             "dtype")


@functools.lru_cache(maxsize=None)
def _jitted(items):
    return jax.jit(functools.partial(_make, items))


def make(cfg: dict, seed: int):
    """The whole weight tree of the configuration file ``cfg`` for
    ``seed``, on the default device, made by one jitted call."""
    items = tuple((k, cfg[k]) for k in SIZE_KEYS)
    return jax.block_until_ready(_jitted(items)(seed_key(seed)))


def _make_cur(items, cur_items, key):
    """The weights of a CUR-compressed model as it is served: one group
    per layer (the program's unrolled layout), the chosen weights in the
    folded form ``{"CU": (m, r), "R": (r, n)}``, and per-layer CUR-KV
    column indices and link matrices when ``kv_rank`` is set."""
    cfg, cur = dict(items), dict(cur_items)
    dtype = jnp.dtype(cfg["dtype"])
    dense = _make(items, key)
    stacked = dense.pop("groups")[0][0]
    k_cur = jax.random.fold_in(key, 1 << 20)
    groups = []
    for li in range(cfg["n_layers"]):
        block = jax.tree.map(lambda a: a[li][None], stacked)
        if li in cur["layers"]:
            for ti, name in enumerate(cur["targets"]):
                m, n = block[name].shape[1:]
                r = cur["rank"]
                kk = jax.random.fold_in(k_cur, li * 64 + ti)
                block[name] = {
                    "CU": _normal(jax.random.fold_in(kk, 0), (1, m, r),
                                  1 / math.sqrt(m), dtype),
                    "R": _normal(jax.random.fold_in(kk, 1), (1, r, n),
                                 1 / math.sqrt(r), dtype)}
        groups.append([block])
    dense["groups"] = groups
    proj = None
    if cur.get("kv_rank"):
        L, hd, r = cfg["n_layers"], cfg["head_dim"], cur["kv_rank"]
        k_kv = jax.random.fold_in(key, 1 << 21)

        def cols(k):
            return jnp.sort(jax.random.permutation(k, hd)[:r]).astype(
                jnp.int32)
        ks = jax.random.split(k_kv, 4 * L).reshape(L, 4, -1)
        proj = {"qk": jax.vmap(cols)(ks[:, 0]),
                "uk": jax.vmap(lambda k: _normal(k, (r, hd), 1 / math.sqrt(r),
                                                 jnp.float32))(ks[:, 1]),
                "qv": jax.vmap(cols)(ks[:, 2]),
                "uv": jax.vmap(lambda k: _normal(k, (r, hd), 1 / math.sqrt(r),
                                                 jnp.float32))(ks[:, 3])}
    return dense, proj


@functools.lru_cache(maxsize=None)
def _jitted_cur(items, cur_items):
    return jax.jit(functools.partial(_make_cur, items, cur_items))


def make_cur(cfg: dict, cur: dict, seed: int):
    """(weights, CUR-KV projections or None) of a CUR-compressed model
    for ``seed``: ``cur`` names the compressed ``layers``, the
    ``targets`` and their ``rank``, and the ``kv_rank`` of the pool."""
    items = tuple((k, cfg[k]) for k in SIZE_KEYS)
    cur_items = tuple((k, tuple(v) if isinstance(v, list) else v)
                      for k, v in sorted(cur.items()))
    return jax.block_until_ready(
        _jitted_cur(items, cur_items)(seed_key(seed)))
