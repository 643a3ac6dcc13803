"""Plain reference of CURing (paper §4), from the benchmark's own weights
and calibration tokens:

1. calibration: a float32 forward (``decoder.block_inputs``) that sums,
   per layer, the squares of the normed inputs of the attention
   projections and of the MLP over every token, and keeps each layer's
   input and output at the last token of every sequence;
2. layers: the angular distance ``arccos(cos(h_in, h_out)) / pi`` of
   each layer, averaged over the sequences; the layers compressed are
   the ``n`` with the smallest, the first and last never (paper §4.1);
3. per weight W (m, n) at rank r: WANDA scores ``S = |W| * sqrt(act)``
   by row, the bases of S's leading r left and right singular subspaces,
   DEIM row indices p from the left and column indices q from the right
   one, ``C = W[:, q]``, ``R = W[p, :]``, ``U = C+ W R+``, folded to
   ``CU = C @ U``.

A selection is judged by its DEIM growth factor on the reference's
bases, ``||P[p, :]^-1||_2`` (Sorensen & Embree 2016, Lemma 3.2: the
factor by which the CUR error may exceed the best rank-r error). It does
not depend on the basis chosen inside a subspace, so it compares
selections made from slightly different SVDs, where the indices
themselves differ.

Step 1 runs on the device at the highest matmul precision; steps 2 and
3 run in float64 numpy on the host, step 3 for the weights a run
samples. ``precision="control"`` is the same pipeline one step below the
bfloat16 the configuration states: the calibration forward with float8
matmul operands, and step 3 with every operand rounded to float8 e4m3
(scaled per matrix).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import scipy.linalg

from benchmarks.chip.reference import decoder as dec

F32 = jnp.float32


@functools.partial(jax.jit, static_argnames=("d", "precision"))
def _calib_batch(weights, tokens, *, d, precision):
    """tokens (B, S) -> (attention-input sq sums (L, D), MLP-input sq sums
    (L, D), last-token states (L + 1, B, D): the embedding, then each
    layer's output)."""
    x = jax.vmap(lambda t: dec.embed(weights, t))(tokens)

    def step(x, w):
        y, h1, h2 = jax.vmap(
            lambda xi: dec.block_inputs(xi, w, d, precision))(x)
        return y, ((h1 ** 2).sum((0, 1)), (h2 ** 2).sum((0, 1)), y[:, -1])

    _, (sq1, sq2, last) = jax.lax.scan(step, x, weights["groups"][0][0])
    return sq1, sq2, jnp.concatenate([x[None, :, -1], last])


def calibrate(weights, tokens: np.ndarray, d, batch: int,
              precision: str = "f32"):
    """Per-layer sums of squared inputs over all calibration sequences,
    and the last-token states. Returns (sq_attn (L, D), sq_mlp (L, D),
    hidden (L + 1, N, D)) as float64 numpy."""
    prec = "fp8" if precision == "control" else "f32"
    sq1 = sq2 = 0.0
    hidden = []
    for i in range(0, tokens.shape[0], batch):
        a, b, h = jax.device_get(_calib_batch(
            weights, jnp.asarray(tokens[i:i + batch]), d=d, precision=prec))
        sq1 = sq1 + a.astype(np.float64)
        sq2 = sq2 + b.astype(np.float64)
        hidden.append(h.astype(np.float64))
    return sq1, sq2, np.concatenate(hidden, axis=1)


def distances(hidden: np.ndarray) -> np.ndarray:
    """(L,) mean angular distance between each layer's input and output."""
    a, b = hidden[:-1], hidden[1:]
    cos = (a * b).sum(-1) / (np.linalg.norm(a, axis=-1)
                             * np.linalg.norm(b, axis=-1))
    return (np.arccos(np.clip(cos, -1.0, 1.0)) / np.pi).mean(-1)


def select_layers(dist: np.ndarray, n: int) -> list:
    """The n layers of smallest distance, the first and last excluded."""
    inner = sorted(range(1, len(dist) - 1), key=lambda i: dist[i])
    return sorted(inner[:n])


def rank_for(m: int, n: int, r_max: int) -> int:
    """Largest power of two r with m r + r^2 + r n < m n, at most r_max."""
    r = 1
    while 2 * r <= r_max and m * 2 * r + 4 * r * r + 2 * r * n < m * n:
        r *= 2
    return r


def top_subspace(S: np.ndarray, r: int):
    """Orthonormal bases P (m, r) and Q (n, r) of S's leading r left and
    right singular subspaces, leading vector first, from the eigenvectors
    of the smaller Gram matrix."""
    m, n = S.shape
    if m > n:
        Q, P = top_subspace(S.T, r)
        return P, Q
    ev, P = scipy.linalg.eigh(S @ S.T, subset_by_index=[m - r, m - 1])
    P = P[:, ::-1]
    return P, (S.T @ P) / np.sqrt(ev[::-1])


def growth(V: np.ndarray, idx) -> float:
    """DEIM growth factor ``||V[idx, :]^-1||_2`` of r indices on an
    (m, r) orthonormal basis; infinite when they are not r distinct
    indices."""
    idx = np.asarray(idx).reshape(-1)
    r = V.shape[1]
    if len(idx) != r or len(np.unique(idx)) != r:
        return float("inf")
    smin = np.linalg.svd(V[idx], compute_uv=False)[-1]
    return float("inf") if smin == 0.0 else float(1.0 / smin)


def deim(V: np.ndarray) -> np.ndarray:
    """DEIM indices (Sorensen & Embree 2016, Alg. 1) of V's columns."""
    m, r = V.shape
    p = [int(np.argmax(np.abs(V[:, 0])))]
    for j in range(1, r):
        c = np.linalg.solve(V[p, :j], V[p, j])
        res = V[:, j] - V[:, :j] @ c
        res[p] = 0.0
        p.append(int(np.argmax(np.abs(res))))
    return np.array(p)


def _fp8(x: np.ndarray) -> np.ndarray:
    """Round to float8 e4m3, scaled so the largest entry is 448."""
    amax = float(np.max(np.abs(x))) or 1.0
    q = jnp.asarray(x / amax * 448.0, F32).astype(jnp.float8_e4m3fn)
    return np.asarray(q.astype(F32), np.float64) * amax / 448.0


def scores(W: np.ndarray, act_sq: np.ndarray) -> np.ndarray:
    """WANDA scores of a weight (m, n) whose rows see inputs with the
    summed squares ``act_sq`` (m,)."""
    return np.abs(W) * np.sqrt(np.maximum(act_sq, 0.0))[:, None]


def cur_weight(W: np.ndarray, act_sq: np.ndarray, r: int,
               precision: str = "f32"):
    """(p, q, CU, R) of one weight, in float64 (control: float8-rounded
    operands at every step)."""
    rnd = _fp8 if precision == "control" else (lambda x: x)
    W = np.asarray(W, np.float64)
    P, Q = top_subspace(rnd(scores(W, act_sq)), r)
    p = deim(rnd(P))
    q = deim(rnd(Q))
    C, R = W[:, q], W[p, :]
    U = rnd(np.linalg.pinv(rnd(C))) @ rnd(W) @ rnd(np.linalg.pinv(rnd(R)))
    return p, q, rnd(rnd(C) @ rnd(U)), R
