#!/usr/bin/env python3
"""Smoke run of CURing and paged serving of olmo-1b on one TPU chip.

    python3 chip_smoke.py

One process, five phases, each through the entry points a user calls,
at the full olmo-1b width (16 layers, d_model 2048, 16 x 128 heads,
d_ff 8192, vocab 50304, bf16) with random weights from a fixed seed:

  1. device   the first JAX device must be a TPU; anything else (a TPU
              that failed to initialise falls back to the CPU) exits
              non-zero naming the platform found.
  2. kernels  cur_matmul, paged_attention and flash_attention compiled
              for the chip at olmo-1b shapes, each against its ref.py.
  3. cure     ``repro.launch.cure.main``: calibrate -> compress 4 layers
              at r_max 256 -> fold -> save -> generate through Server.
  4. serve    ``repro.launch.serve.main`` twice, dense then CUR-KV at
              head_dim / 2, 8 open-loop poisson requests each.
  5. paths    the compiled serving steps ran the Pallas kernels: every
              decode step holds paged_attention, every prefill step
              flash_attention, and the CURed model's prefill cur_matmul
              (read from the StableHLO handed to the compiler).

Per-phase seconds (compilation included) are printed as they finish.
The last line is ``{"ok": true, "device": {...}}``, printed only when
every phase passed.
"""
from __future__ import annotations

import contextlib
import importlib.metadata
import json
import math
import os
import re
import shutil
import sys
import time

import jax
import jax.numpy as jnp
import jaxlib
import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

from repro.configs import get_config  # noqa: E402
from repro.core.cur import rank_for  # noqa: E402
from repro.kernels.cur_matmul.ops import cur_matmul_op  # noqa: E402
from repro.kernels.cur_matmul.ref import cur_matmul_ref  # noqa: E402
from repro.kernels.flash_attention.ops import (  # noqa: E402
    flash_attention_op)
from repro.kernels.flash_attention.ref import (  # noqa: E402
    flash_attention_ref)
from repro.kernels.paged_attention.ops import (  # noqa: E402
    paged_attention_op)
from repro.kernels.paged_attention.ref import (  # noqa: E402
    paged_attention_ref)
from repro.launch import compile_cache, cure, serve  # noqa: E402

WORK = os.path.join(ROOT, ".chip_smoke")      # git-ignored scratch
ARCH = "olmo-1b"
KERNEL_TOL = 2e-2                              # bf16, scale-relative


class SmokeFailure(AssertionError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


# ---------------------------------------------------------------------------
# phase 1: device
# ---------------------------------------------------------------------------

def phase_device() -> dict:
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU: JAX found platform {d.platform!r} "
            f"({len(devs)} device(s), kind {d.device_kind!r})")
    log(f"jax {jax.__version__} jaxlib {jaxlib.__version__} "
        f"libtpu {importlib.metadata.version('libtpu')} device_kind {d.device_kind} "
        f"count {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


# ---------------------------------------------------------------------------
# phase 2: kernels against their references
# ---------------------------------------------------------------------------

def _vs_ref(y, ref, *args) -> float:
    """Max scaled error of kernel output ``y`` against ``ref(*args)``,
    the reference computed at full f32 matmul precision (the TPU default
    rounds f32 matmul inputs to bf16)."""
    with jax.default_matmul_precision("highest"):
        yr = np.asarray(ref(*args), np.float32)
    y = np.asarray(y, np.float32)
    return float(np.abs(y - yr).max() / (np.abs(yr).max() + 1e-9))


def phase_kernels(cfg) -> dict:
    """Each kernel at the model's shapes vs its ref.py (bf16 inputs):
    the prefill GEMM chain of every weight shape at its Eq. 2 rank, the
    paged decode read at dense and CUR-KV (head_dim / 2) rank over a
    ragged 8-slot block table, and causal prefill attention at 512."""
    D, F = cfg.d_model, cfg.d_ff
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 32))

    def rand(shape, scale=1.0):
        return (scale * jax.random.normal(next(keys), shape)).astype(
            jnp.bfloat16)

    errs = {}
    for m, n in ((D, F), (F, D), (D, D)):
        r = rank_for(m, n, 256)
        x, cu, rr = rand((512, m)), rand((m, r), m ** -0.5), rand(
            (r, n), r ** -0.5)
        errs[f"cur_matmul x(512,{m}) cu({m},{r}) r({r},{n})"] = _vs_ref(
            cur_matmul_op(x, cu, rr), cur_matmul_ref, x, cu, rr)

    B, bs, maxb, nb = 8, 16, 32, 256
    rng = np.random.RandomState(0)
    ctx = rng.randint(0, maxb * bs, size=B).astype(np.int32)
    table = np.full((B, maxb), -1, np.int32)
    free = list(rng.permutation(nb))
    for b in range(B):
        for j in range(ctx[b] // bs + 1):
            table[b, j] = free.pop()
    table, ctx = jnp.asarray(table), jnp.asarray(ctx)
    for r in (hd, hd // 2):
        q = rand((B, K, H // K, r), r ** -0.5)
        kp, vp = rand((nb, K, bs, r)), rand((nb, K, bs, r))
        errs[f"paged_attention q({B},{K},{H // K},{r}) "
             f"pool({nb},{K},{bs},{r})"] = _vs_ref(
            paged_attention_op(q, kp, vp, table, ctx),
            paged_attention_ref, q, kp, vp, table, ctx)

    S = 512
    q, k, v = rand((1, H, S, hd)), rand((1, K, S, hd)), rand((1, K, S, hd))
    errs[f"flash_attention (1,{H},{S},{hd})"] = _vs_ref(
        flash_attention_op(q, k, v), flash_attention_ref, q, k, v)

    for name, e in errs.items():
        log(f"  {name}: max scaled error {e:.3e}")
    bad = {n: e for n, e in errs.items()
           if not (math.isfinite(e) and e < KERNEL_TOL)}
    check(not bad, f"kernels off their references (tol {KERNEL_TOL}): "
          f"{bad}")
    return errs


# ---------------------------------------------------------------------------
# phase 3: CURe
# ---------------------------------------------------------------------------

def phase_cure(arch_args, work: str, layers: int = 4) -> dict:
    n_req, new = 4, 8
    ckpt = os.path.join(work, "ckpt")
    rep = cure.main(arch_args + [
        "--layers", str(layers), "--r-max", "256", "--ckpt-dir", ckpt,
        "--n-requests", str(n_req), "--prompt-len", "16",
        "--new-tokens", str(new), "--max-concurrency", str(n_req)])
    errs = [w["rel_fro_err"] for w in rep["weights"]]
    check(errs and all(math.isfinite(e) and e < 1 for e in errs),
          f"rel_fro_err not finite and below 1: {errs}")
    check(rep["params"]["saved_deployed"] > 0,
          f"CURe saved no parameters: {rep['params']}")
    check(rep["generate"]["engine"] == "serving"
          and rep["generate"]["tokens"] == n_req * new,
          f"generate made {rep['generate']}, want {n_req * new} tokens "
          f"through the serving runtime")
    shutil.rmtree(ckpt)                        # model-sized, not kept
    return rep


# ---------------------------------------------------------------------------
# phase 4: serve
# ---------------------------------------------------------------------------

def phase_serve(arch_args, vocab_size: int) -> dict:
    n_req, new = 8, 4
    runs = {}
    for name, extra in (("dense", []), ("cur_kv", ["--cur-kv"])):
        stats, finished = serve.main(arch_args + [
            "--arrival", "poisson", "--rate", "50",
            "--n-requests", str(n_req), "--new-tokens", str(new),
            "--max-concurrency", str(n_req)] + extra)
        failed = {k: v for k, v in stats.get("failed", {}).items() if v}
        check(stats["completed"] == n_req and len(finished) == n_req
              and not failed,
              f"{name}: completed {stats['completed']}/{n_req}, "
              f"failed {failed}")
        toks = [t for r in finished.values() for t in r.out_tokens]
        check(all(len(r.out_tokens) == new for r in finished.values()),
              f"{name}: token counts "
              f"{[len(r.out_tokens) for r in finished.values()]}")
        check(all(0 <= t < vocab_size for t in toks),
              f"{name}: tokens outside the vocabulary")
        runs[name] = stats
    return runs


# ---------------------------------------------------------------------------
# phase 5: which paths the compiled steps took
# ---------------------------------------------------------------------------

_STEP_RE = re.compile(r"jax_ir\d+_jit__(prefill|decode|decode_scan)"
                      r"_compile\.mlir$")


@contextlib.contextmanager
def dump_ir(path: str):
    """Write every module handed to the compiler under ``path``."""
    shutil.rmtree(path, ignore_errors=True)
    new = {"jax_dump_ir_to": path, "jax_include_debug_info_in_dumps": False}
    old = {k: jax.config.values[k] for k in new}
    for k, v in new.items():
        jax.config.update(k, v)
    try:
        yield path
    finally:
        for k, v in old.items():
            jax.config.update(k, v)


def step_kernels(ir_dir: str) -> dict:
    """Serving step name -> one set of Pallas TPU kernel names per
    compiled module of that step."""
    out = {}
    for fn in sorted(os.listdir(ir_dir)):
        m = _STEP_RE.match(fn)
        if m:
            with open(os.path.join(ir_dir, fn)) as f:
                text = f.read()
            out.setdefault(m.group(1), []).append(
                set(re.findall(r'kernel_name = "(\w+)"', text)))
    return out


def phase_paths(ir_dir: str, serve_runs: dict) -> dict:
    for name, st in serve_runs.items():
        be = st["attn_backends"]
        check(be["paged_decode"] == "paged_pallas",
              f"{name}: decode resolved {be['paged_decode']}")
    check(serve_runs["cur_kv"]["attn_backends"]["paged_prefill"]
          == "rank_fold", "CUR-KV prefill did not resolve rank_fold")
    steps = step_kernels(ir_dir)
    summary = {k: [sorted(s) for s in v] for k, v in steps.items()}
    log(f"  kernels per compiled step: {summary}")
    decode = steps.get("decode", []) + steps.get("decode_scan", [])
    prefill = steps.get("prefill", [])
    check(decode and all("paged_attention" in s for s in decode),
          f"a decode step ran without the paged_attention kernel: "
          f"{summary}")
    check(prefill and all("flash_attention" in s for s in prefill),
          f"a prefill step ran without the flash_attention kernel: "
          f"{summary}")
    check(any("cur_matmul" in s for s in prefill),
          f"no prefill step ran the fused cur_matmul kernel: {summary}")
    return summary


# ---------------------------------------------------------------------------

def main() -> int:
    compile_cache.enable()
    timings = {}

    def timed(name, fn, *args):
        t = time.perf_counter()
        out = fn(*args)
        timings[name] = time.perf_counter() - t
        log(f"phase {name}: {timings[name]:.1f} s")
        return out

    device = timed("device", phase_device)
    cfg = get_config(ARCH)
    arch_args = ["--arch", ARCH]
    timed("kernels", phase_kernels, cfg)
    with dump_ir(os.path.join(WORK, "ir")) as ir_dir:
        timed("cure", phase_cure, arch_args, WORK)
        runs = timed("serve", phase_serve, arch_args, cfg.vocab_size)
    timed("paths", phase_paths, ir_dir, runs)
    log("phase seconds " + json.dumps(
        {k: round(v, 1) for k, v in timings.items()}))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
