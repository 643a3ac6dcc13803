"""Plain reference of the decoder the configurations describe: a float32
forward pass over one whole sequence, in ``jax.numpy`` at the highest
matmul precision, layer by layer, with no kernel, cache or batching.

Per layer: ``x += Wo attn(rope(Wq n(x)), rope(Wk n(x)), Wv n(x))`` with
causal softmax over grouped KV heads, then ``x += Wd (silu(Wg n(x)) *
Wu n(x))``; ``n`` is LayerNorm without affine parameters (OLMo) or
RMSNorm with a scale, as the config's ``norm_type`` and
``parametric_norm`` say. RoPE rotates the two halves of each head
(``[x1 cos - x2 sin, x1 sin + x2 cos]``). Logits are ``n(x) @ embed.T``
for tied embeddings, else ``n(x) @ out_head``.

A CUR-compressed weight ``{"CU", "R"}`` multiplies as ``(x @ CU) @ R``.
With CUR-KV projections ``(qk, uk, qv, uv)`` a layer's keys and values
are ``k[..., qk] @ uk`` and ``v[..., qv] @ uv`` (keys after RoPE).

``precision="fp8"`` is the control: the same pass with every matmul
operand rounded to float8 e4m3 (scaled per row of the activations and per
column of the weights), one step below the bfloat16 the configurations
state.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class Dims:
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    rope_theta: float
    norm_eps: float
    norm_type: str
    parametric_norm: bool
    tie_embeddings: bool

    @classmethod
    def of(cls, cfg_file: dict) -> "Dims":
        return cls(**{f.name: cfg_file[f.name]
                      for f in dataclasses.fields(cls)})


def _fp8(x, axis):
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(F32)
    return q * scale


def _mm(a, b, precision):
    """a (..., k) @ b (k, n) in float32, operands rounded for the control."""
    if isinstance(b, dict):
        return _mm(_mm(a, b["CU"], precision), b["R"], precision)
    a, b = a.astype(F32), b.astype(F32)
    if precision == "fp8":
        a, b = _fp8(a, -1), _fp8(b, 0)
    return jnp.matmul(a, b, precision=HIGHEST)


def _norm(x, scale, d: Dims):
    if d.norm_type == "layernorm":
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        y = (x - mu) * jax.lax.rsqrt(var + d.norm_eps)
    else:
        y = x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + d.norm_eps)
    if scale is not None:
        y = y * scale.astype(F32)
    return y


def _rope(x, d: Dims):
    """x (S, H, hd) at positions 0..S-1."""
    S, hd = x.shape[0], x.shape[-1]
    inv = 1.0 / (d.rope_theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, d: Dims, precision):
    """q (S, H, hd), k/v (S, K, hd): causal, grouped KV heads."""
    S = q.shape[0]
    G = d.n_heads // d.n_kv_heads
    qg = q.reshape(S, d.n_kv_heads, G, d.head_dim)
    if precision == "fp8":
        qg, k, v = _fp8(qg, -1), _fp8(k, -1), _fp8(v, 0)
    s = jnp.einsum("skgd,tkd->kgst", qg, k, precision=HIGHEST)
    s = s * d.head_dim ** -0.5
    mask = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    if precision == "fp8":
        p = _fp8(p, -1)
    o = jnp.einsum("kgst,tkd->skgd", p, v, precision=HIGHEST)
    return o.reshape(S, d.n_heads * d.head_dim)


def block(x, w, d: Dims, precision="f32", proj=None):
    """One decoder layer. ``w`` holds the layer's weights by the names
    the configurations use (wq, wk, wv, wo, w_gate, w_up, w_down and,
    with parametric norms, norm1 / norm2 scales)."""
    return block_inputs(x, w, d, precision, proj)[0]


def block_inputs(x, w, d: Dims, precision="f32", proj=None):
    """One decoder layer; also returns the normed inputs of the
    attention projections and of the MLP (what calibration reads)."""
    S = x.shape[0]
    n1 = w["norm1"]["scale"] if d.parametric_norm else None
    n2 = w["norm2"]["scale"] if d.parametric_norm else None
    h1 = _norm(x, n1, d)
    q = _mm(h1, w["wq"], precision).reshape(S, d.n_heads, d.head_dim)
    k = _mm(h1, w["wk"], precision).reshape(S, d.n_kv_heads, d.head_dim)
    v = _mm(h1, w["wv"], precision).reshape(S, d.n_kv_heads, d.head_dim)
    k = _rope(k, d)
    if proj is not None:
        k = jnp.matmul(k[..., proj["qk"]], proj["uk"], precision=HIGHEST)
        v = jnp.matmul(v[..., proj["qv"]], proj["uv"], precision=HIGHEST)
    o = _attention(_rope(q, d), k, v, d, precision)
    x = x + _mm(o, w["wo"], precision)
    h = _norm(x, n2, d)
    g = _mm(h, w["w_gate"], precision)
    u = _mm(h, w["w_up"], precision)
    return x + _mm(jax.nn.silu(g) * u, w["w_down"], precision), h1, h


def embed(weights, tokens):
    return weights["embed"][tokens].astype(F32)


def unembed(weights, x, d: Dims, precision="f32"):
    fn = weights.get("final_norm")
    x = _norm(x, fn["scale"] if fn is not None else None, d)
    head = (weights["embed"].T if d.tie_embeddings
            else weights["out_head"])
    return _mm(x, head, precision)


@functools.partial(jax.jit, static_argnames=("d", "precision"))
def logits(weights, tokens, proj=None, *, d: Dims, precision="f32"):
    """(S, V) float32 logits of one sequence. Weights are stacked per
    layer in one group (scanned) or held one group per layer; ``proj``
    holds CUR-KV projections stacked per layer, or None."""
    x = embed(weights, tokens)
    groups = weights["groups"]
    if len(groups) == 1:
        def step(x, wp):
            return block(x, wp[0], d, precision, wp[1]), None
        x, _ = jax.lax.scan(step, x, (groups[0][0], proj))
    else:
        for li, g in enumerate(groups):
            w = jax.tree.map(lambda a: a[0], g[0])
            p = None if proj is None else jax.tree.map(lambda a: a[li], proj)
            x = block(x, w, d, precision, p)
    return unembed(weights, x, d, precision)


@functools.partial(jax.jit, static_argnames=("d", "control"))
def served_gaps(weights, tokens, first, served, n_served, proj=None, *,
                d: Dims, control: bool = False):
    """How far below the reference's best logit each served token lies.

    ``tokens`` (S,) is prompt + served tokens, padded; ``served`` (S,)
    the served tokens, padded, the j-th predicted at position
    ``first + j``. Returns (gaps, control gaps): the second reads, at the
    same positions, the gap of the token float8 logits put first (zeros
    when ``control`` is off). Padded entries are 0."""
    ref = logits(weights, tokens, proj, d=d)
    S = tokens.shape[0]
    pos = jnp.clip(first + jnp.arange(S), 0, S - 1)
    live = jnp.arange(S) < n_served
    lg = ref[pos]
    best = lg.max(-1)
    gap = best - jnp.take_along_axis(lg, served[:, None], -1)[:, 0]
    gap = jnp.where(live, gap, 0.0)
    if not control:
        return gap, jnp.zeros_like(gap)
    ctl = logits(weights, tokens, proj, d=d, precision="fp8")[pos]
    pick = jnp.argmax(ctl, -1)
    cgap = best - jnp.take_along_axis(lg, pick[:, None], -1)[:, 0]
    return gap, jnp.where(live, cgap, 0.0)
