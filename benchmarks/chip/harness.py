"""The data-driven core of the benchmark: find a cell by name, check the
device, run the cell's mode, read its metrics, print the result line.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric lives in a file of its own and is found by the name
``BENCHMARK.json`` gives it:

- ``configs/<config>.json``      model sizes (the file ``configs[].file`` names)
- ``traffic/<traffic>.json``     parameters of the one traffic generator
- ``workloads/<cell>.json``      mode, rate, slots and sizes of one cell
- ``metrics/<metric>.py``        ``read(ctx) -> float | None`` per metric
- ``modes/<mode>.py``            ``run(ctx) -> Outcome`` per kind of cell
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib
import importlib.util
import json
import math
import os
import sys
import time
from typing import Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
# the persistent compile cache: one fixed directory inside the checkout
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
# traces and other scratch of a run (git-ignored, removed after use)
SCRATCH_DIR = os.path.join(ROOT, ".chipbench")


class DeviceError(RuntimeError):
    """The machine does not hold the chips the cell asks for."""


# ---------------------------------------------------------------------------
# loading by name
# ---------------------------------------------------------------------------

def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def find_cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def find_config(bench: dict, name: str) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return c
    raise SystemExit(f"no config {name!r} in BENCHMARK.json")


def load_module(path: str, name: str):
    """Import a reader or mode file by path (names may hold dots)."""
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def model_config(cfg_file: dict, unrolled: bool = False):
    """Build the program's ``ModelConfig`` from a config file: every key
    the dataclass knows is taken as it stands; the layer stack is
    ``n_layers`` attention + MLP blocks, in one scanned group or (as a
    CUR-compressed model is served) one group per layer."""
    from repro.configs.base import ATTN, MLP, BlockSpec, ModelConfig
    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    kw = {k: v for k, v in cfg_file.items() if k in fields}
    spec = BlockSpec(ATTN, MLP)
    L = int(kw["n_layers"])
    if unrolled:
        kw["groups"] = (((spec,), 1),) * L
        kw["scan_layers"] = False
    else:
        kw["groups"] = (((spec,), L),)
    return ModelConfig(**kw)


def per_layer_for(bench: dict, cell: str) -> List[dict]:
    """The per-layer metrics a cell reports: those that list it, and
    those without a list whose ``moves`` metric the cell reports."""
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    reported = {n for n, m in e2e.items()
                if "workloads" not in m or cell in m["workloads"]}
    out = []
    for m in bench["per_layer"]:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif m["moves"] in reported:
            out.append(m)
    return out


def end_to_end_for(bench: dict, cell: str) -> List[dict]:
    return [m for m in bench["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]]


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

def check_devices(devices, chips: int, platform: str = "tpu"):
    """The first ``chips`` devices, or DeviceError when the first device
    is not a ``platform`` device or there are fewer than ``chips``."""
    if not devices or devices[0].platform != platform:
        got = devices[0].platform if devices else "none"
        raise DeviceError(f"first device is {got!r}, not {platform!r}")
    if len(devices) < chips:
        raise DeviceError(f"{len(devices)} {platform} device(s), the cell "
                          f"asks for {chips}")
    return list(devices[:chips])


def load_peaks(kind: str) -> dict:
    table = load_json(os.path.join(HERE, "peaks.json"))
    if kind not in table:
        raise DeviceError(f"device kind {kind!r} is not in peaks.json")
    return table[kind]


def memory_peak(devices) -> Optional[int]:
    peaks = []
    for d in devices:
        try:
            st = d.memory_stats()
        except Exception:
            st = None
        if st and "peak_bytes_in_use" in st:
            peaks.append(int(st["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


# ---------------------------------------------------------------------------
# a run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Check:
    """One number compared with the reference, beside its limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    """What a mode hands back to the harness."""
    metrics: Dict[str, float]            # end-to-end, by name
    attempted: int
    failed: int
    checks: List[Check]
    reading: dict                        # what per-layer readers see
    memory_peak_bytes: Optional[int] = None
    trace: Optional[object] = None       # trace.Reduction when traced


@dataclasses.dataclass
class Context:
    """Everything a mode needs about its cell."""
    cell: dict
    workload: dict
    config_file: dict
    traffic: Optional[dict]
    seed: int
    seconds: float
    trace: bool
    devices: list
    peaks: dict
    t_process: float
    # a fault planted under the timed path (tests), and whether to read
    # the control beside the program (control.py)
    fault: Optional[str] = None
    control: bool = False
    clock: Callable[[], float] = time.perf_counter


def build_context(args, devices, t_process, root=ROOT,
                  peaks: Optional[dict] = None) -> Context:
    bench = load_benchmark(root)
    cell = find_cell(bench, args.workload)
    cfg_entry = find_config(bench, cell["config"])
    data = os.path.join(root, "benchmarks", "chip")
    wl = load_json(os.path.join(data, "workloads", cell["name"] + ".json"))
    cfg_file = load_json(os.path.join(root, cfg_entry["file"]))
    traffic = None
    tpath = os.path.join(data, "traffic", cell["traffic"] + ".json")
    if os.path.exists(tpath):
        traffic = load_json(tpath)
    return Context(cell=cell, workload=wl, config_file=cfg_file,
                   traffic=traffic, seed=args.seed, seconds=args.seconds,
                   trace=bool(args.trace), devices=devices,
                   peaks=peaks or load_peaks(devices[0].device_kind),
                   t_process=t_process)


def enable_compile_cache(path: str = CACHE_DIR) -> None:
    import jax
    jax.config.update("jax_compilation_cache_dir", path)
    # every program of the cell, however quick to compile, is cached, so
    # a run's set-up does the same work whatever its predecessor did
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no size cap: one CURe shape class compiles to over 200 MB, and an
    # environment's cap (JAX_COMPILATION_CACHE_MAX_SIZE) that drops it
    # makes every run compile for ten minutes
    jax.config.update("jax_compilation_cache_max_size", -1)


def read_per_layer(bench: dict, cell: str, reading: dict) -> Dict[str, dict]:
    out = {}
    for m in per_layer_for(bench, cell):
        mod = load_module(os.path.join(HERE, "metrics", m["name"] + ".py"),
                          m["name"])
        v = mod.read(reading)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def result_line(bench: dict, ctx: Context, out: Outcome,
                device_info: dict) -> dict:
    if ctx.trace:
        metrics = read_per_layer(bench, ctx.cell["name"], out.reading)
    else:
        metrics = {}
        for m in end_to_end_for(bench, ctx.cell["name"]):
            if m["name"] in out.metrics:
                metrics[m["name"]] = {"value": float(out.metrics[m["name"]]),
                                      "unit": m["unit"]}
    line = {
        "correct": all(c.ok for c in out.checks) and bool(out.checks),
        "attempted": int(out.attempted),
        "failed": int(out.failed),
        "metrics": metrics,
        "device": device_info,
    }
    if ctx.trace and out.trace is not None:
        line["breakdown"] = out.trace.breakdown()
    # the numbers compared, each beside its limit, come last
    # (a number that is not finite, as the growth of a repeated index,
    # is written as 1e300, so the line stays plain JSON)
    line["checks"] = {c.name: {"value": _finite(c.value), "limit": c.limit}
                      for c in out.checks}
    return line


def _finite(x: float) -> float:
    return float(x) if math.isfinite(x) else 1e300


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def run_cell(ctx: Context, bench: dict) -> dict:
    """Run the cell's mode and build the result line."""
    mode = importlib.import_module("benchmarks.chip.modes."
                                   + ctx.workload["mode"])
    out = mode.run(ctx)
    dev = ctx.devices[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(ctx.devices),
            "memory_peak_bytes": out.memory_peak_bytes}
    if ctx.trace and out.trace is not None:
        info["busy_s"] = out.trace.busy_s
        info["window_s"] = out.trace.window_s
    return result_line(bench, ctx, out, info)


def main(argv, t_process: Optional[float] = None) -> int:
    t_process = time.perf_counter() if t_process is None else t_process
    args = parse_args(argv)
    bench = load_benchmark()
    cell = find_cell(bench, args.workload)
    import jax
    try:
        devices = check_devices(jax.devices(), int(cell["chips"]))
        ctx = build_context(args, devices, t_process)
    except DeviceError as e:
        sys.stderr.write(f"run.py: {e}\n")
        return 3
    enable_compile_cache()
    line = run_cell(ctx, bench)
    for name, c in line["checks"].items():
        sys.stderr.write(f"check {name} = {c['value']!r} "
                         f"(limit {c['limit']!r})\n")
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0
