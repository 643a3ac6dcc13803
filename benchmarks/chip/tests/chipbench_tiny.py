"""A tiny copy of the benchmark's data (same cells, same files, small
sizes) for runs on the CPU, and a way to run a cell of it in-process
with the chip check left out."""
import argparse
import json
import os
import shutil
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

CHIP = os.path.join(ROOT, "benchmarks", "chip")

TINY = {"d_model": 64, "n_layers": 4, "n_heads": 4, "n_kv_heads": 2,
        "head_dim": 16, "d_ff": 160, "vocab_size": 256}


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _dump(obj, *parts):
    with open(os.path.join(*parts), "w") as f:
        json.dump(obj, f)


def workload_files():
    return sorted(f[:-5] for f in os.listdir(os.path.join(CHIP, "workloads"))
                  if f.endswith(".json"))


def build(dst: str) -> str:
    """Write a benchmark root under ``dst`` whose cells are the real ones
    at small sizes; returns it."""
    shutil.rmtree(dst, ignore_errors=True)
    d = os.path.join(dst, "benchmarks", "chip")
    for sub in ("configs", "workloads", "traffic"):
        os.makedirs(os.path.join(d, sub))
    bench = _load(ROOT, "BENCHMARK.json")
    configs = []
    for c in bench["configs"]:
        cfg = _load(ROOT, c["file"])
        cfg.update(TINY)
        _dump(cfg, d, "configs", c["name"] + ".json")
        configs.append(dict(c, file=f"benchmarks/chip/configs/{c['name']}.json"))
    bench["configs"] = configs
    # workload files that no cell names yet (ready for a later PR) are
    # run as cells too, on the chat mix
    listed = {w["name"] for w in bench["workloads"]}
    for name in workload_files():
        if name not in listed:
            bench["workloads"].append(dict(
                bench["workloads"][-1], name=name, traffic="chat"))
    _dump(bench, dst, "BENCHMARK.json")
    for w in bench["workloads"]:
        wl = _load(CHIP, "workloads", w["name"] + ".json")
        if wl["mode"] == "cure":
            # one of the two inner layers, so the choice is seen
            wl.update(r_max=8, layers=1)
            _dump({"sequences": 8, "length": 32, "batch": 4},
                  d, "traffic", w["traffic"] + ".json")
        else:
            wl.update(slots=4, max_len=128, block_size=16, rate_rps=8.0,
                      check_tokens=40, drain_s=30, trace_s=1)
            if "cur" in wl:
                wl["cur"].update(layers=[1, 2], rank=8, kv_rank=8)
            _dump({"arrival": "poisson",
                   "prompt": {"dist": "lognormal", "median": 16,
                              "sigma": 0.8, "lo": 8, "hi": 64},
                   "output": {"dist": "lognormal", "median": 8,
                              "sigma": 0.8, "lo": 4, "hi": 24}}, d, "traffic", w["traffic"] + ".json")
        _dump(wl, d, "workloads", w["name"] + ".json")
    return dst


def context(root: str, cell: str, seed: int, seconds: float,
            fault=None, control=False):
    """A run's context on the CPU: the device check is left out and the
    chip's peaks stand in for the CPU's, which the table lacks."""
    import jax
    from benchmarks.chip import harness
    args = argparse.Namespace(workload=cell, seed=seed, seconds=seconds,
                              trace=0)
    peaks = _load(CHIP, "peaks.json")["TPU v5 lite"]
    ctx = harness.build_context(args, jax.devices()[:1], time.perf_counter(),
                                root=root, peaks=peaks)
    ctx.fault, ctx.control = fault, control
    return ctx


def run(root: str, cell: str, seed: int, seconds: float, fault=None):
    """The result line of one run of a tiny cell."""
    from benchmarks.chip import harness
    ctx = context(root, cell, seed, seconds, fault)
    return harness.run_cell(ctx, harness.load_benchmark(root))
