"""Attention-backend registry: one resolution point from model to pool.

Every attention variant the stack serves — full causal, sliding-window,
GQA/MQA, CUR-KV rank-space, paged decode, paged prefill — is a registered
:class:`Backend` with a capability descriptor (:class:`Caps`) and an
availability gate, grouped under a *variant* name:

  ``mix``            full-sequence attention over in-flight K/V (training
                     forward, prefill, calibration). Backends in
                     resolution order: ``flash_pallas`` (TPU kernel,
                     ``REPRO_FLASH_KERNEL``) -> ``dense_xla`` (the oracle,
                     which doubles as the small-S fast path) ->
                     ``banded_xla`` / ``flash_xla`` (chunked XLA refs).
  ``paged_decode``   single/multi-position queries against the paged pool
                     (rank space): ``paged_pallas``
                     (``REPRO_PAGED_KERNEL``) -> ``paged_xla``.
  ``paged_prefill``  prompt attention + pool write for CUR-KV pools:
                     ``rank_fold`` (fold Uk/Uv, attend at dim r, scatter
                     the compressed K/V in the same pass) ->
                     ``reconstruct`` (materialize k̂ = k_c @ Uk — the
                     algebraically equal full-head-dim oracle, kept for
                     calibration/tests; ``REPRO_PREFILL_BACKEND``).

This replaces the per-module ``REPRO_*_KERNEL`` if/else ladders that used
to live in ``models/attention.py``, ``serving/runtime.py`` and the two
kernel ``ops.py`` wrappers: adding the next variant (block-sparse
prefill, per-block-rank online compression) means registering one backend
here, not threading a new env var through four layers.

Env gates (all resolve at **trace time** — the serving jit cache keys on
their resolved values, see ``serving.server``):

  REPRO_PAGED_KERNEL   "auto" (TPU only) | "1" force | "0" off
  REPRO_FLASH_KERNEL   "auto" (TPU only) | "1" force (interpret off-TPU)
                       | "0" off
  REPRO_PREFILL_BACKEND  "auto" (= fold) | "fold" | "reconstruct"
"""
from __future__ import annotations

import dataclasses
import os
from typing import Callable, Dict, List, Optional

import jax

from repro.kernels.paged_attention.ref import (     # noqa: F401 (re-export)
    fold_q, unfold_o)

from repro.attention import xla

_PAGED_KERNEL_ENV = "REPRO_PAGED_KERNEL"
_FLASH_KERNEL_ENV = "REPRO_FLASH_KERNEL"
_PREFILL_BACKEND_ENV = "REPRO_PREFILL_BACKEND"


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def paged_kernel_mode() -> str:
    return os.environ.get(_PAGED_KERNEL_ENV, "auto")


def use_paged_kernel() -> bool:
    """Trace-time gate for the block-table Pallas decode kernel."""
    mode = paged_kernel_mode()
    if mode == "0":
        return False
    if mode == "1":
        return True
    return _on_tpu()


def flash_kernel_mode() -> str:
    return os.environ.get(_FLASH_KERNEL_ENV, "auto")


def use_flash_kernel() -> bool:
    """Trace-time gate for the Pallas flash-attention prefill kernel
    ("1" forces interpret mode off-TPU — the parity tests)."""
    mode = flash_kernel_mode()
    if mode == "0":
        return False
    if mode == "1":
        return True
    return _on_tpu()


def prefill_backend_mode() -> str:
    return os.environ.get(_PREFILL_BACKEND_ENV, "auto")


# ---------------------------------------------------------------------------
# descriptors
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Caps:
    """What a backend can express (resolution filters on these)."""
    causal: bool = True
    window: bool = False       # sliding-window masking
    gqa: bool = True           # grouped queries (G > 1)
    rank_space: bool = False   # correct at feature dim r != head_dim
    paged: bool = False        # reads KV through a block table
    q_span: bool = False       # multi-position verify layout
    value_width: bool = False  # values narrower or wider than q/k (MLA)


@dataclasses.dataclass(frozen=True)
class Backend:
    name: str
    kind: str                  # "pallas" | "xla" | "oracle"
    caps: Caps
    fn: Callable
    # availability gate over a resolution context dict (seq_len, window,
    # static, force, ...); first available backend in registration order
    # wins, so gates encode the Pallas -> XLA -> oracle preference
    available: Callable[[dict], bool] = lambda ctx: True
    gate: str = ""             # env var / heuristic shown in tables


_REGISTRY: Dict[str, List[Backend]] = {}


def register(variant: str, backend: Backend) -> Backend:
    _REGISTRY.setdefault(variant, []).append(backend)
    return backend


def variants() -> List[str]:
    return sorted(_REGISTRY)


def backends(variant: str) -> List[Backend]:
    return list(_REGISTRY.get(variant, []))


def describe() -> List[dict]:
    """Flat (variant, backend, kind, caps, gate) rows — the stats/README
    registry table."""
    rows = []
    for variant in variants():
        for be in _REGISTRY[variant]:
            rows.append({
                "variant": variant, "backend": be.name, "kind": be.kind,
                "caps": dataclasses.asdict(be.caps), "gate": be.gate})
    return rows


def resolve(variant: str, **ctx) -> Backend:
    """First registered backend whose caps cover the request and whose
    availability gate passes. ``ctx`` keys: seq_len, window, q_span,
    rank_space, value_width, static, force (variant-specific pin)."""
    cands = _REGISTRY.get(variant)
    if not cands:
        raise KeyError(f"unknown attention variant {variant!r}; "
                       f"registered: {variants()}")
    for be in cands:
        if ctx.get("window", 0) > 0 and not be.caps.window:
            continue
        if ctx.get("q_span", 1) > 1 and not be.caps.q_span:
            continue
        if ctx.get("rank_space", False) and not be.caps.rank_space:
            continue
        if ctx.get("value_width", False) and not be.caps.value_width:
            continue
        if be.available(ctx):
            return be
    raise LookupError(f"no available backend for {variant!r} with {ctx}")


# ---------------------------------------------------------------------------
# mix variant: full-sequence attention over in-flight K/V
# ---------------------------------------------------------------------------

def _mix_flash_pallas(q, k, v, q_pos, kv_pos, window, scale, *,
                      chunk, static):
    from repro.kernels.flash_attention.ops import flash_attention_op
    B, S, K, G, d = q.shape
    qh = q.transpose(0, 2, 3, 1, 4).reshape(B, K * G, S, d)
    o = flash_attention_op(qh, k.transpose(0, 2, 1, 3),
                           v.transpose(0, 2, 1, 3), causal=True,
                           window=window, scale=scale)
    return o.reshape(B, K, G, S, d).transpose(0, 3, 1, 2, 4)


def _mix_dense(q, k, v, q_pos, kv_pos, window, scale, *, chunk, static):
    return xla.dense_attn(q, k, v, q_pos, kv_pos, window, scale)


def _mix_banded(q, k, v, q_pos, kv_pos, window, scale, *, chunk, static):
    return xla.banded_attn(q, k, v, q_pos, kv_pos, window, scale,
                           chunk, static)


def _mix_flash_xla(q, k, v, q_pos, kv_pos, window, scale, *, chunk,
                   static):
    return xla.flash_attn(q, k, v, q_pos, kv_pos, scale, chunk, static)


# The Pallas flash kernel assumes contiguous-from-zero positions (every
# mix call site builds positions as broadcast arange) and cannot emit the
# static python-unrolled HLO the dry-run cost compiles count, so the
# ``static`` flag keeps it out of those traces.
register("mix", Backend(
    "flash_pallas", "pallas",
    Caps(window=True, rank_space=True),
    _mix_flash_pallas,
    available=lambda ctx: use_flash_kernel() and not ctx.get("static"),
    gate=f"{_FLASH_KERNEL_ENV}=auto|1|0 (auto: TPU)"))
register("mix", Backend(
    "dense_xla", "oracle",
    Caps(window=True, rank_space=True, value_width=True),
    _mix_dense,
    available=lambda ctx: (ctx.get("seq_len", 0)
                           <= ctx.get("dense_max", xla.DENSE_MAX)
                           and not ctx.get("static")),
    gate="seq_len <= DENSE_MAX"))
register("mix", Backend(
    "banded_xla", "xla",
    Caps(window=True, rank_space=True, value_width=True),
    _mix_banded,
    available=lambda ctx: ctx.get("window", 0) > 0,
    gate="window > 0"))
register("mix", Backend(
    "flash_xla", "xla",
    Caps(window=False, rank_space=True, value_width=True),
    _mix_flash_xla,
    gate="fallback"))


def mix(qg, k, v, positions, window: int, scale: float, cfg=None, *,
        dense_max: Optional[int] = None):
    """Resolve and run the ``mix`` variant.

    qg (B,S,K,G,d) grouped queries; k (B,S,K,d), v (B,S,K,dv);
    positions (B,S).
    ``dense_max`` overrides the small-S oracle threshold (the models
    layer threads its monkeypatchable module global through here)."""
    S = qg.shape[1]
    static = bool(cfg is not None and cfg.static_loops)
    chunk = cfg.attn_chunk if cfg is not None else xla.CHUNK
    be = resolve("mix", seq_len=S, window=window, static=static,
                 value_width=v.shape[-1] != qg.shape[-1],
                 dense_max=dense_max if dense_max is not None
                 else xla.DENSE_MAX)
    return be.fn(qg, k, v, positions, positions, window, scale,
                 chunk=chunk, static=static)


# ---------------------------------------------------------------------------
# paged_decode variant: queries against the block-table pool (rank space)
# ---------------------------------------------------------------------------

def _paged_pallas(qf, k_pool, v_pool, table, ctx_len, *, window, q_span):
    from repro.kernels.paged_attention.ops import paged_attention_op
    return paged_attention_op(qf, k_pool, v_pool, table, ctx_len,
                              window=window, q_span=q_span)


def _paged_xla(qf, k_pool, v_pool, table, ctx_len, *, window, q_span):
    from repro.kernels.paged_attention.ref import paged_attention_ref
    return paged_attention_ref(qf, k_pool, v_pool, table, ctx_len,
                               window=window, q_span=q_span)


register("paged_decode", Backend(
    "paged_pallas", "pallas",
    Caps(window=True, rank_space=True, paged=True, q_span=True),
    _paged_pallas,
    available=lambda ctx: (use_paged_kernel() if ctx.get("force") is None
                           else bool(ctx["force"])),
    gate=f"{_PAGED_KERNEL_ENV}=auto|1|0 (auto: TPU)"))
register("paged_decode", Backend(
    "paged_xla", "xla",
    Caps(window=True, rank_space=True, paged=True, q_span=True),
    _paged_xla,
    gate="fallback"))


def resolve_paged(force: Optional[bool] = None) -> Backend:
    """``force`` pins the dispatch (the Server resolves the env gate ONCE
    at construction and threads the pin through its compiled steps);
    None re-reads the env at trace time."""
    return resolve("paged_decode", force=force)


# ---------------------------------------------------------------------------
# paged_prefill variant (backends registered by repro.attention.__init__,
# which wires in repro.attention.prefill without an import cycle)
# ---------------------------------------------------------------------------

def resolve_prefill(force: Optional[str] = None) -> Backend:
    """CUR-KV prompt attention backend. ``force`` pins "fold" or
    "reconstruct" (same jit-cache-key contract as :func:`resolve_paged`);
    None resolves ``REPRO_PREFILL_BACKEND`` (auto = fold)."""
    mode = force if force is not None else prefill_backend_mode()
    if mode not in ("auto", "fold", "rank_fold", "reconstruct"):
        raise ValueError(
            f"REPRO_PREFILL_BACKEND must be auto|fold|reconstruct, "
            f"got {mode!r}")
    name = "reconstruct" if mode == "reconstruct" else "rank_fold"
    for be in _REGISTRY.get("paged_prefill", []):
        if be.name == name:
            return be
    raise LookupError(f"paged_prefill backend {name!r} not registered")
