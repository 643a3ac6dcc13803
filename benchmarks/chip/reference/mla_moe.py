"""Plain reference of the DeepSeek-V3 block that Moonlight-16B-A3B uses
(latent attention without query LoRA; DeepSeekMoE with sigmoid routing),
as one chip holds it under expert parallelism: float32 ``jax.numpy`` at
the highest matmul precision, layer by layer, with no kernel, cache or
batching inside a sequence.

Per layer, with ``n`` RMSNorm with a scale and h = n(x):

- attention: ``q = h Wq`` (H, dn + dr), split into ``q_C | q_R``;
  ``[c ; k_R] = h Wkv_a``, ``c <- n_kv(c)``; RoPE on ``q_R`` and on
  ``k_R`` (one key for all heads); ``k_C = c Wk_b``, ``v = c Wv_b``;
  causal ``softmax((q_C k_C + q_R k_R) / sqrt(dn + dr)) v``, then ``Wo``;
- the first ``first_k_dense_replace`` layers: ``x += Wd (silu(Wg h) *
  Wu h)``;
- the others: scores ``s = sigmoid(h Wr)`` over all experts, the top-k of
  ``s + b`` picked, gates ``scale * s_i / sum_picked s``, and ``x +=
  sum over picked and held experts of gate_i E_i(h) + Shared(h)``, where
  the held experts are the contiguous share ``[first, stop)`` of the
  router's. The absent experts' part is left out, as the chip that runs
  the share leaves it out.

RoPE rotates the two halves of the rope part (``[x1 cos - x2 sin, x1 sin
+ x2 cos]``); the weights are those of ``weights_moe.py``.

Calibration sums, in the program's form: per layer ``wq`` the sum of
``h^2`` over every token, ``wk_b`` of ``c^2``, a dense layer's ``w_gate``
of ``h2^2``; an MoE layer's ``w_gate`` one row per held expert, over the
tokens that picked it, and ``shared.w_gate`` over every token.

``precision="fp8"`` is the control: every matmul operand rounded to
float8 e4m3 (scaled per row of the activations and per column of the
weights), one step below the bfloat16 the configuration states.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class Dims:
    d_model: int
    n_layers: int
    n_heads: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    d_ff: int
    moe_d_ff: int
    n_experts: int
    n_experts_per_tok: int
    n_shared_experts: int
    moe_routed_scale: float
    first_k_dense_replace: int
    vocab_size: int
    rope_theta: float
    norm_eps: float
    held: tuple               # (first, stop) of the router's experts

    @classmethod
    def of(cls, cfg_file: dict, held=None) -> "Dims":
        kw = {f.name: cfg_file[f.name] for f in dataclasses.fields(cls)
              if f.name != "held"}
        if held is None:
            held = (0, cfg_file["n_experts_held"])
        return cls(held=tuple(held), **kw)

    def is_moe(self, layer: int) -> bool:
        return layer >= self.first_k_dense_replace


def _fp8(x, axis):
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(F32)
    return q * scale


def _mm(a, b, precision):
    """a (..., k) @ b (k, n) in float32; operands rounded for the
    control; a folded CUR weight ``{"CU", "R"}`` multiplies as two."""
    if isinstance(b, dict):
        return _mm(_mm(a, b["CU"], precision), b["R"], precision)
    a, b = a.astype(F32), b.astype(F32)
    if precision == "fp8":
        a, b = _fp8(a, -1), _fp8(b, 0)
    return jnp.matmul(a, b, precision=HIGHEST)


def _rms(x, scale, d: Dims):
    y = x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + d.norm_eps)
    return y * scale.astype(F32)


def _rope(x, d: Dims):
    """x (S, ..., hd) at positions 0..S-1."""
    S, hd = x.shape[0], x.shape[-1]
    inv = 1.0 / (d.rope_theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv
    shape = (S,) + (1,) * (x.ndim - 2) + (hd // 2,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(h, w, d: Dims, precision="f32"):
    """Latent attention of one sequence h (S, D), normed. Returns
    (output (S, D), the normed latent c (S, r))."""
    S = h.shape[0]
    H, dn, r = d.n_heads, d.qk_nope_head_dim, d.kv_lora_rank
    q = _mm(h, w["wq"], precision).reshape(S, H, -1)
    qc, qr = q[..., :dn], _rope(q[..., dn:], d)
    ckr = _mm(h, w["wkv_a"], precision)
    c = _rms(ckr[:, :r], w["kv_norm"]["scale"], d)
    kr = _rope(ckr[:, r:], d)                                  # (S, dr)
    kc = _mm(c, w["wk_b"], precision).reshape(S, H, dn)
    v = _mm(c, w["wv_b"], precision).reshape(S, H, d.v_head_dim)
    if precision == "fp8":
        qc, qr, kc, kr, v = (_fp8(qc, -1), _fp8(qr, -1), _fp8(kc, -1),
                             _fp8(kr, -1), _fp8(v, 0))
    s = (jnp.einsum("shd,thd->hst", qc, kc, precision=HIGHEST)
         + jnp.einsum("shd,td->hst", qr, kr, precision=HIGHEST))
    s = s * (dn + d.qk_rope_head_dim) ** -0.5
    mask = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    if precision == "fp8":
        p = _fp8(p, -1)
    o = jnp.einsum("hst,thd->shd", p, v, precision=HIGHEST)
    return _mm(o.reshape(S, -1), w["wo"], precision), c


def _swiglu(h, wg, wu, wd, precision):
    g = _mm(h, wg, precision)
    return _mm(jax.nn.silu(g) * _mm(h, wu, precision), wd, precision)


def route(h, w, d: Dims, precision="f32"):
    """(gates (S, E) of every expert, 0 where not picked; picked (S, E))."""
    s = jax.nn.sigmoid(_mm(h, w["router"], precision))
    _, top = jax.lax.top_k(s + w["router_bias"].astype(F32),
                           d.n_experts_per_tok)
    picked = jnp.zeros(s.shape, bool).at[
        jnp.arange(s.shape[0])[:, None], top].set(True)
    sp = jnp.where(picked, s, 0.0)
    return d.moe_routed_scale * sp / sp.sum(-1, keepdims=True), picked


def moe(h, w, d: Dims, precision="f32"):
    """The held share of an MoE channel mixer, shared experts included.
    Returns (output (S, D), picked (S, E_held))."""
    gates, picked = route(h, w, d, precision)
    first, stop = d.held
    out = _swiglu(h, w["shared"]["w_gate"], w["shared"]["w_up"],
                  w["shared"]["w_down"], precision)
    for j, e in enumerate(range(first, stop)):
        y = _swiglu(h, w["w_gate"][j], w["w_up"][j], w["w_down"][j],
                    precision)
        out = out + gates[:, e:e + 1] * y
    return out, picked[:, first:stop]


def layer(x, w, d: Dims, is_moe: bool, precision="f32"):
    """One layer of one sequence x (S, D), an MoE layer or a dense one.
    Returns (output, calibration sums of this sequence in the program's
    form)."""
    h1 = _rms(x, w["norm1"]["scale"], d)
    a, c = attention(h1, w, d, precision)
    x = x + a
    h2 = _rms(x, w["norm2"]["scale"], d)
    sums = {"wq": (h1 ** 2).sum(0), "wk_b": (c ** 2).sum(0)}
    if is_moe:
        y, picked = moe(h2, w, d, precision)
        sums["w_gate"] = jnp.matmul(picked.T.astype(F32), h2 ** 2,
                                    precision=HIGHEST)
        sums["shared.w_gate"] = (h2 ** 2).sum(0)
    else:
        y = _swiglu(h2, w["w_gate"], w["w_up"], w["w_down"], precision)
        sums["w_gate"] = (h2 ** 2).sum(0)
    return x + y, sums


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


@functools.partial(jax.jit, static_argnames=("d", "is_moe", "precision"))
def _layer_batch(x, group, *, d, is_moe, precision):
    """x (B, S, D) through the layer a one-layer group holds; sums over
    the batch; last tokens."""
    w = _f32(jax.tree.map(lambda a: a[0], group[0]))
    y, sums = jax.vmap(lambda xi: layer(xi, w, d, is_moe, precision))(x)
    return y, jax.tree.map(lambda s: s.sum(0), sums), y[:, -1]


def calibrate(weights, tokens: np.ndarray, d: Dims, batch: int,
              precision: str = "f32"):
    """Sums of every layer over all calibration sequences, and the
    last-token states. Returns (sums: list per layer of name -> float64
    array, hidden (L + 1, N, D) float64)."""
    prec = "fp8" if precision == "control" else "f32"
    sums = [dict() for _ in range(d.n_layers)]
    hidden = []
    for i in range(0, tokens.shape[0], batch):
        x = weights["embed"][jnp.asarray(tokens[i:i + batch])].astype(F32)
        last = [x[:, -1]]
        for li in range(d.n_layers):
            x, s, l_ = _layer_batch(x, weights["groups"][li], d=d,
                                    is_moe=d.is_moe(li), precision=prec)
            last.append(l_)
            for name, v in s.items():
                sums[li][name] = sums[li].get(name, 0.0) + v
        hidden.append(jnp.stack(last))
    sums, hidden = jax.device_get((sums, hidden))
    return ([{k: np.asarray(v, np.float64) for k, v in s.items()}
             for s in sums],
            np.concatenate([np.asarray(h, np.float64) for h in hidden], 1))


def logits(weights, tokens, d: Dims, precision="f32"):
    """(S, V) float32 logits of one sequence."""
    x = weights["embed"][tokens].astype(F32)
    for li in range(d.n_layers):
        w = _f32(jax.tree.map(lambda a: a[0], weights["groups"][li][0]))
        x = layer(x, w, d, d.is_moe(li), precision)[0]
    x = _rms(x, weights["final_norm"]["scale"], d)
    return _mm(x, weights["out_head"], precision)
