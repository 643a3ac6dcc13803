"""Mixture-of-Experts channel mixer.

Three implementations, selected by ``cfg.moe_impl`` and mesh availability:

  - ``dense`` (also every call without a mesh, and every config that
    holds a share of the experts, ``cfg.experts_held``): the held-experts
    layer. It routes over all ``n_experts``, applies every held expert to
    every token, and keeps, per token, the gated outputs of the held
    experts among its top-k, gates normalised over all k picked as in the
    deployment: one chip's part of an expert-parallel layer, with no
    exchange and no dropped token. O(T·E_held·D·F) — the smoke path, the
    oracle the distributed paths are verified against, and one chip's
    share of a layer split over chips. Expert weights may be dense
    ``(E, D, F)`` stacks or CUR-factorized stacks (``_expert_mm``).

  - ``a2a`` with E % model_axis == 0 (kimi 384e, jamba 16e): production
    expert parallelism. Tokens are sequence-sharded over the 'model' axis,
    sorted by destination expert, packed into fixed-capacity per-device
    buffers, exchanged with ``lax.all_to_all``, processed by the local
    expert slice as batched GEMMs, and returned by a second all-to-all.
    Capacity overflow tokens are dropped (GShard semantics); the residual
    connection carries them.

  - ``a2a`` with E < model_axis (mixtral 8e over 16): megatron-style
    expert-TP. Every device holds all experts with the intermediate dim
    F sharded over 'model'; dispatch is local (sort + capacity buffer),
    outputs are combined locally then psum-reduced over 'model'.

Routing: softmax-then-top-k with renormalized gates (Mixtral convention)
by default; ``cfg.moe_scoring="sigmoid"`` is DeepSeek-V3's noaux_tc with
one group: sigmoid scores, top-k of score + a selection-only bias
(``router_bias``), renormalised over the top-k, scaled by
``cfg.moe_routed_scale``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import act_fn
from repro.models.mlp import mlp_forward

def shard_map(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# ---------------------------------------------------------------------------
# routing helpers
# ---------------------------------------------------------------------------

def route(xt, router, k, cfg=None, bias=None):
    """xt (T,D) -> (gates (T,k) f32, experts (T,k) i32)."""
    if cfg is not None and cfg.moe_scoring == "sigmoid":
        # scored in float32 at full precision, as the published router
        logits = jnp.matmul(xt.astype(jnp.float32),
                            router.astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        scores = jax.nn.sigmoid(logits)
        pick = scores if bias is None else scores + bias.astype(jnp.float32)
        _, topi = jax.lax.top_k(pick, k)
        topv = jnp.take_along_axis(scores, topi, axis=-1)
        topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-20)
        return topv * cfg.moe_routed_scale, topi
    logits = (xt @ router.astype(xt.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, k)
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    return topv, topi


def route_held(xt, p, cfg):
    """xt (T,D) -> (gates (T,E_held) f32, picked (T,E_held) bool): each
    held expert's gate for every token (0 where it is not among the
    token's top-k) and whether it was picked."""
    gates, experts = route(xt, p["router"], cfg.n_experts_per_tok, cfg,
                           p.get("router_bias"))
    first, stop = cfg.held_experts
    hit = experts[:, :, None] == jnp.arange(first, stop)   # (T,k,E_held)
    return (jnp.where(hit, gates[:, :, None], 0.0).sum(axis=1),
            hit.any(axis=1))


def _rank_within_expert(fe):
    """For each assignment (sorted arbitrary order), its occurrence rank
    within its expert id. O(A log A) — no (A, E) one-hot materialized."""
    A = fe.shape[0]
    order = jnp.argsort(fe, stable=True)
    fe_s = fe[order]
    idx = jnp.arange(A)
    is_start = jnp.concatenate(
        [jnp.ones((1,), bool), fe_s[1:] != fe_s[:-1]])
    start_pos = jax.lax.associative_scan(
        jnp.maximum, jnp.where(is_start, idx, -1))
    rank_s = idx - start_pos
    rank = jnp.zeros((A,), jnp.int32).at[order].set(rank_s.astype(jnp.int32))
    return rank


def _expert_mm(h, w):
    """Batched expert matmul supporting CUR-factorized expert weights.
    h (E,C,D); w dense (E,D,F) or {"C","U0","dU","R"}/{"CU","R"} stacks."""
    if isinstance(w, dict) and ("C" in w or "CU" in w):
        if "CU" in w:
            t = jnp.einsum("ecd,edr->ecr", h, w["CU"].astype(h.dtype))
        else:
            u = (w["U0"] + w["dU"]).astype(h.dtype)
            t = jnp.einsum("ecd,edr->ecr", h, w["C"].astype(h.dtype))
            t = jnp.einsum("ecr,erk->eck", t, u)
        return jnp.einsum("ecr,erf->ecf", t, w["R"].astype(h.dtype))
    return jnp.einsum("ecd,edf->ecf", h, w)


def _expert_ffn(h, wg, wu, wd, act):
    """h (E,C,D) x weights (E,D,F)/(E,F,D) -> (E,C,D)."""
    g = act(_expert_mm(h, wg))
    u = _expert_mm(h, wu)
    return _expert_mm(g * u, wd)


# ---------------------------------------------------------------------------
# dense path (oracle / smoke)
# ---------------------------------------------------------------------------

def moe_dense(x, p, cfg):
    """The held-experts layer (module docstring). x (B,S,D) -> (B,S,D)."""
    B, S, D = x.shape
    T = B * S
    act = act_fn(cfg.mlp_act)
    xt = x.reshape(T, D)
    with jax.named_scope("moe_route"):
        gates, _ = route_held(xt, p, cfg)
    with jax.named_scope("moe_experts"):
        h = jnp.broadcast_to(xt, (cfg.n_held_experts, T, D))
        y = _expert_ffn(h, p["w_gate"], p["w_up"], p["w_down"], act)
        out = jnp.einsum("etd,te->td", y, gates.astype(y.dtype))
    if cfg.n_shared_experts:
        out = out + mlp_forward(xt, p["shared"], cfg)
    return out.reshape(B, S, D)


# ---------------------------------------------------------------------------
# distributed paths (shard_map over the mesh)
# ---------------------------------------------------------------------------

def _dp_axes(mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def _moe_body_a2a(xs, rp, wg, wu, wd, *, cfg, n):
    """Expert-parallel body. xs (B,S_loc,D); wg/wu/wd (E_loc,D,F)."""
    k = cfg.n_experts_per_tok
    E = cfg.n_experts
    E_loc = E // n
    act = act_fn(cfg.mlp_act)
    B, S, D = xs.shape
    T = B * S
    xt = xs.reshape(T, D)
    gates, experts = route(xt, rp["router"], k, cfg, rp.get("router_bias"))
    A = T * k
    fe = experts.reshape(-1)
    fg = gates.reshape(-1)
    ft = jnp.repeat(jnp.arange(T), k)
    rank = _rank_within_expert(fe)
    capE = max(1, math.ceil(A * cfg.capacity_factor / E))
    capB = E_loc * capE
    dst = fe // E_loc
    slot = (fe % E_loc) * capE + rank
    keep = rank < capE
    slot_eff = jnp.where(keep, slot, capB)               # capB = drop
    send = jnp.zeros((n, capB, D), xs.dtype).at[dst, slot_eff].set(
        xt[ft], mode="drop")
    recv = jax.lax.all_to_all(send, "model", 0, 0, tiled=True)
    # slot layout per source: (E_loc, capE); regroup by local expert
    h = recv.reshape(n, E_loc, capE, D).transpose(1, 0, 2, 3)
    h = h.reshape(E_loc, n * capE, D)
    y = _expert_ffn(h, wg, wu, wd, act)
    back = y.reshape(E_loc, n, capE, D).transpose(1, 0, 2, 3)
    back = back.reshape(n, capB, D)
    ret = jax.lax.all_to_all(back, "model", 0, 0, tiled=True)
    y_a = ret[dst, jnp.clip(slot_eff, 0, capB - 1)]
    y_a = jnp.where(keep[:, None], y_a, 0)
    y_a = y_a * fg[:, None].astype(y_a.dtype)
    out = jax.ops.segment_sum(y_a, ft, num_segments=T)
    return out.reshape(B, S, D)


def _moe_body_tp(xs, rp, wg, wu, wd, *, cfg):
    """Expert-TP body (E < model axis). xs (B,S,D) replicated over 'model';
    wg/wu (E,D,F_loc), wd (E,F_loc,D). Output psum over 'model'."""
    k = cfg.n_experts_per_tok
    E = cfg.n_experts
    act = act_fn(cfg.mlp_act)
    B, S, D = xs.shape
    T = B * S
    xt = xs.reshape(T, D)
    gates, experts = route(xt, rp["router"], k, cfg, rp.get("router_bias"))
    A = T * k
    fe = experts.reshape(-1)
    fg = gates.reshape(-1)
    ft = jnp.repeat(jnp.arange(T), k)
    rank = _rank_within_expert(fe)
    capE = max(1, math.ceil(A * cfg.capacity_factor / E))
    keep = rank < capE
    slot_eff = jnp.where(keep, rank, capE)
    buf = jnp.zeros((E, capE + 1, D), xs.dtype).at[fe, slot_eff].set(
        xt[ft], mode="drop")[:, :capE]
    y = _expert_ffn(buf, wg, wu, wd, act)               # partial over F_loc
    y_a = y[fe, jnp.clip(slot_eff, 0, capE - 1)]
    y_a = jnp.where(keep[:, None], y_a, 0) * fg[:, None].astype(xs.dtype)
    out = jax.ops.segment_sum(y_a, ft, num_segments=T)
    out = jax.lax.psum(out, "model")
    return out.reshape(B, S, D)


def moe_forward(x, p, cfg, mesh=None):
    """Dispatch on impl + mesh. x (B,S,D) -> (B,S,D)."""
    if cfg.moe_impl == "dense" or mesh is None or cfg.experts_held:
        return moe_dense(x, p, cfg)
    n = mesh.shape["model"]
    dp = _dp_axes(mesh)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    E = cfg.n_experts
    B = x.shape[0]
    # small/indivisible batches (long-context B=1) replicate over 'data'
    b_ax = dp if (B % dp_size == 0 and B >= dp_size) else None
    # a2a needs the sequence dim divisible by the model axis (it shards
    # tokens over 'model'); decode steps (S == 1) use the TP body instead.
    fsdp_layout = getattr(cfg, "layout", "tp") == "fsdp"
    if E % n == 0 and (x.shape[1] % n == 0 or fsdp_layout):
        body = functools.partial(_moe_body_a2a, cfg=cfg, n=n)
        if fsdp_layout and b_ax is not None and \
                B % (dp_size * n) == 0 and B >= dp_size * n:
            # batch already spans (data, model): tokens arrive fully split
            x_spec = P(dp + ("model",), None, None)
        else:
            x_spec = P(b_ax, "model", None)
        fn = shard_map(
            body, mesh,
            in_specs=(x_spec,                        # tokens 256-way split
                      P(),                           # router replicated
                      P("model", None, None),        # experts EP-sharded
                      P("model", None, None),
                      P("model", None, None)),
            out_specs=x_spec)
    else:
        body = functools.partial(_moe_body_tp, cfg=cfg)
        fn = shard_map(
            body, mesh,
            in_specs=(P(b_ax, None, None),           # x replicated on model
                      P(),                           # router replicated
                      P(None, None, "model"),        # F sharded (TP)
                      P(None, None, "model"),
                      P(None, "model", None),
                      ),
            out_specs=P(b_ax, None, None))
    rp = {k: p[k] for k in ("router", "router_bias") if k in p}
    out = fn(x, rp, p["w_gate"], p["w_up"], p["w_down"])
    if cfg.n_shared_experts:
        out = out + mlp_forward(x, p["shared"], cfg)
    return out
