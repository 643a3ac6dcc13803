"""A tiny copy of the benchmark's latent-attention MoE cell (same files,
small sizes) for runs on the CPU, and a way to run it in-process with the
chip check left out."""
import os
import shutil

import chipbench_tiny

CELL = "moonlight-16b-a3b-ep8.cure"
CONFIG = "moonlight-16b-a3b-ep8"
# widths cut to CPU size; four layers: the dense one, two that CURe may
# pick and the last. In float32: with some 50 tokens routed to an expert
# here, one token that bfloat16 rounding moves across the top-k boundary
# shifts that expert's sums by 2%, which would drown the comparison at
# this size (on the chip an expert sees some 6,000 tokens)
TINY = {"d_model": 64, "n_layers": 4, "n_heads": 4, "kv_lora_rank": 32,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "d_ff": 128, "moe_d_ff": 32, "n_experts": 16, "n_experts_per_tok": 3,
        "n_experts_held": 4, "vocab_size": 256, "dtype": "float32"}


def build(dst: str) -> str:
    """Write a benchmark root under ``dst`` that holds the cell at small
    sizes; returns it."""
    shutil.rmtree(dst, ignore_errors=True)
    d = os.path.join(dst, "benchmarks", "chip")
    for sub in ("configs", "workloads", "traffic"):
        os.makedirs(os.path.join(d, sub))
    bench = chipbench_tiny._load(chipbench_tiny.ROOT, "BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    cfg = chipbench_tiny._load(chipbench_tiny.ROOT, entry["file"])
    cfg.update(TINY)
    chipbench_tiny._dump(cfg, d, "configs", CONFIG + ".json")
    bench["configs"] = [dict(entry,
                             file=f"benchmarks/chip/configs/{CONFIG}.json")]
    bench["workloads"] = [cell]
    chipbench_tiny._dump(bench, dst, "BENCHMARK.json")
    wl = chipbench_tiny._load(chipbench_tiny.CHIP, "workloads",
                              CELL + ".json")
    wl.update(r_max=8, layers=1)
    chipbench_tiny._dump(wl, d, "workloads", CELL + ".json")
    chipbench_tiny._dump({"sequences": 8, "length": 32, "batch": 4},
                         d, "traffic", cell["traffic"] + ".json")
    return dst


def context(root: str, seed: int, seconds: float, fault=None,
            control=False):
    return chipbench_tiny.context(root, CELL, seed, seconds, fault, control)


def run(root: str, seed: int, seconds: float, fault=None):
    """The result line of one run of the tiny cell."""
    from benchmarks.chip import harness
    ctx = context(root, seed, seconds, fault)
    return harness.run_cell(ctx, harness.load_benchmark(root))
