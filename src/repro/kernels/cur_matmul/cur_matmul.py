"""Fused CUR matmul Pallas TPU kernel: y = (x @ CU) @ R.

TPU adaptation of the paper's inference hot path (DESIGN.md §3): after
CURing, every compressed weight is applied as a low-rank chain. XLA would
materialize the (M, r) intermediate in HBM between two GEMM dispatches;
this kernel keeps it in VMEM:

  grid = (M/bm, N/bn), j (N tiles) iterating fastest.
  - CU (m, r) is small (r <= 512) and resident in VMEM for all tiles.
  - at j == 0 the kernel computes t = x_tile @ CU once per M-tile into a
    VMEM scratch accumulator (f32),
  - every j computes y_tile = t @ R_tile on the MXU.

Block sizes default to 128-aligned (MXU native). HBM traffic: x is read
once per M-tile (not once per (i, j) pair), R once, y written once —
bytes ~= M*m + m*r + r*N + M*N versus the unfused M*m + 2*M*r + r*N + M*N.

The whole-row x block and the resident CU block are the point of the
design, so the kernel asks for the scoped VMEM its double-buffered
blocks need (``_vmem_limit``) instead of tiling the contraction dim: at
olmo-1b's down projection (m = 8192, r = 256, bm = bn = 256, bf16) that
is ~16.8 MiB, just over the compiler's 16 MiB default and far below the
128 MiB a v5e core has.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_MiB = 1 << 20


def _vmem_limit(bm: int, bn: int, m: int, rk: int, itemsize: int) -> int:
    """Scoped VMEM for the double-buffered x/CU/R/y blocks plus the f32 t
    accumulator, with 25% headroom, never below the 16 MiB default."""
    blocks = 2 * (bm * m + m * rk + rk * bn + bm * bn) * itemsize
    return max((blocks + bm * rk * 4) * 5 // 4, 16 * _MiB)


def _kernel(x_ref, cu_ref, r_ref, o_ref, t_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        t_ref[...] = jnp.dot(
            x_ref[...], cu_ref[...],
            preferred_element_type=jnp.float32)

    o_ref[...] = jnp.dot(
        t_ref[...].astype(x_ref.dtype), r_ref[...],
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


def cur_matmul(x, cu, r, *, bm: int = 256, bn: int = 256,
               interpret: bool = False):
    """x (M, m) @ cu (m, rk) @ r (rk, n) -> (M, n).

    Ragged M / n (decode batches, odd vocab slices) are padded up to the
    block grid and sliced back after the call — XLA pads with zeros, the
    zero rows/cols fall out of the matmuls, and the kernel body keeps its
    aligned-tile fast path (no per-tile masking on the MXU)."""
    M, m = x.shape
    rk = cu.shape[1]
    n = r.shape[1]
    bm = min(bm, M)
    bn = min(bn, n)
    Mp = -(-M // bm) * bm
    np_ = -(-n // bn) * bn
    if Mp != M:
        x = jnp.pad(x, ((0, Mp - M), (0, 0)))
    if np_ != n:
        r = jnp.pad(r, ((0, 0), (0, np_ - n)))
    y = _cur_matmul_aligned(x, cu, r, bm=bm, bn=bn, interpret=interpret)
    if Mp != M or np_ != n:
        y = y[:M, :n]
    return y


def _cur_matmul_aligned(x, cu, r, *, bm: int, bn: int, interpret: bool):
    M, m = x.shape
    rk = cu.shape[1]
    n = r.shape[1]
    assert M % bm == 0 and n % bn == 0, (M, n, bm, bn)
    grid = (M // bm, n // bn)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, m), lambda i, j: (i, 0)),
            pl.BlockSpec((m, rk), lambda i, j: (0, 0)),
            pl.BlockSpec((rk, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, rk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_vmem_limit(
            bm, bn, m, rk, x.dtype.itemsize)),
        interpret=interpret,
        name="cur_matmul",
    )(x, cu, r)
