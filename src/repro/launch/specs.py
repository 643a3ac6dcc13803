"""ShapeDtypeStruct stand-ins for every model input — weak-type-correct,
shardable, zero allocation. The dry-run lowers against these.

Also: structural CUR transformation of a parameter *shape* pytree — the
paper's compression applied at dry-run scale (every eligible weight in
every layer becomes C/U0/dU/R stand-ins with Eq.-2 ranks), so the
compressed model's distributed roofline is measurable without real weights.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import CURConfig, ModelConfig, ShapeConfig
from repro.core.cur import rank_for
from repro.models.model import init_cache, init_params

S = jax.ShapeDtypeStruct


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    """Model inputs for a train/prefill step: tokens or stub embeddings."""
    B, L = shape.global_batch, shape.seq_len
    batch = {"labels": S((B, L), jnp.int32)}
    if cfg.input_mode == "tokens":
        batch["tokens"] = S((B, L), jnp.int32)
    else:
        batch["embeds"] = S((B, L, cfg.d_model), jnp.dtype(cfg.dtype))
    return batch


def decode_input_specs(cfg: ModelConfig, shape: ShapeConfig):
    """(batch, pos) for one decode step with a seq_len-deep cache."""
    B = shape.global_batch
    if cfg.input_mode == "tokens":
        batch = {"tokens": S((B, 1), jnp.int32)}
    else:
        batch = {"embeds": S((B, 1, cfg.d_model), jnp.dtype(cfg.dtype))}
    pos = S((B, 1), jnp.int32)
    return batch, pos


def param_specs(cfg: ModelConfig):
    """Parameter ShapeDtypeStructs via eval_shape (no allocation)."""
    return jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg))


def cache_specs(cfg: ModelConfig, shape: ShapeConfig):
    return jax.eval_shape(
        lambda: init_cache(cfg, shape.global_batch, shape.seq_len))


# ---------------------------------------------------------------------------
# paged serving (repro.serving) decode-shape stand-ins
# ---------------------------------------------------------------------------

def paged_config_for(shape: ShapeConfig, block_size: int = 128):
    """PagedConfig sized so ``global_batch`` sequences of ``seq_len``
    tokens fit exactly (the dry-run's worst-case residency)."""
    from repro.serving.paged_cache import PagedConfig
    maxb = -(-shape.seq_len // block_size)
    return PagedConfig(block_size=block_size,
                       n_blocks=shape.global_batch * maxb,
                       max_blocks_per_seq=maxb)


def paged_cache_specs(cfg: ModelConfig, shape: ShapeConfig,
                      block_size: int = 128):
    """ShapeDtypeStructs for the paged k/v pool at a decode shape."""
    from repro.serving.paged_cache import init_paged_cache
    pc = paged_config_for(shape, block_size)
    return jax.eval_shape(lambda: init_paged_cache(cfg, pc)), pc


def paged_decode_input_specs(cfg: ModelConfig, shape: ShapeConfig,
                             pc=None, block_size: int = 128):
    """(tokens, table, ctx_len, active) for one paged decode step. Pass
    the PagedConfig returned by :func:`paged_cache_specs` so the table
    width always matches the pool layout."""
    if pc is None:
        pc = paged_config_for(shape, block_size)
    B = shape.global_batch
    return (S((B, 1), jnp.int32),
            S((B, pc.max_blocks_per_seq), jnp.int32),
            S((B,), jnp.int32), S((B,), jnp.bool_))


# ---------------------------------------------------------------------------
# structural CUR (dry-run compression)
# ---------------------------------------------------------------------------

def _cur_struct(leaf: S, r_max: int) -> dict:
    """Dense weight struct (..., m, n) -> CUR dict of structs."""
    *lead, m, n = leaf.shape
    r = rank_for(m, n, r_max)
    lead = tuple(lead)
    dt = leaf.dtype
    return {
        "C": S(lead + (m, r), dt),
        "U0": S(lead + (r, r), jnp.float32),
        "dU": S(lead + (r, r), jnp.float32),
        "R": S(lead + (r, n), dt),
    }


def structural_cur(params, cfg: ModelConfig, cur_cfg: CURConfig):
    """Replace every CUR-target weight (all layers) with CUR stand-ins.
    Group stacking is preserved (uniform ranks), so scanned HLO stays
    compact. Returns the new params pytree (structs or arrays untouched
    elsewhere)."""
    new = {k: v for k, v in params.items() if k != "groups"}
    new["groups"] = []
    for gi, (pattern, reps) in enumerate(cfg.groups):
        group = []
        for pi, spec in enumerate(pattern):
            block = dict(params["groups"][gi][pi])
            for t in cfg.cur_targets:
                # "shared.w_gate": the weight w_gate of block["shared"]
                outer, _, name = t.rpartition(".")
                holder = block
                if outer:
                    if outer not in block:
                        continue
                    holder = block[outer] = dict(block[outer])
                leaf = holder.get(name)
                if not hasattr(leaf, "shape"):
                    continue
                m, n = leaf.shape[-2], leaf.shape[-1]
                r = rank_for(m, n, cur_cfg.r_max)
                if m * r + r * r + r * n >= m * n:
                    continue  # Eq. 2: no saving, keep dense
                holder[name] = _cur_struct(leaf, cur_cfg.r_max)
            group.append(block)
        new["groups"].append(group)
    return new


def fold_cur_struct(params):
    """Struct analogue of ``core.compress.fold_cur``: every healing-form
    CUR dict {C, U0, dU, R} becomes the folded serving form {CU, R}
    (C @ (U0 + dU) collapses to one (m, r) factor), so the dry-run can
    lower the deployed inference layout."""
    def is_cur(node):
        return isinstance(node, dict) and set(node) == {"C", "U0", "dU", "R"}

    def fold(node):
        if not is_cur(node):
            return node
        C = node["C"]
        return {"CU": S(C.shape, C.dtype), "R": node["R"]}

    return jax.tree.map(fold, params, is_leaf=is_cur)


def count_struct_params(tree) -> int:
    return sum(int(np.prod(l.shape))
               for l in jax.tree.leaves(tree) if hasattr(l, "shape"))
