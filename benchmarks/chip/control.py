"""Read the program and the control of one cell over several seeds, in
one process, on the chip (the readings its limits are set from):

    python3 benchmarks/chip/control.py --workload <name> --seconds <s> \
        --seeds 11 12 13 [--out control.jsonl]

Each seed runs the cell as ``run.py`` does (untraced), then reads every
number the cell compares, for the program and for the control: the
reference computed one precision step below what the configuration
states. One JSON line per seed.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from benchmarks.chip import harness  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    import jax
    bench = harness.load_benchmark()
    cell = harness.find_cell(bench, args.workload)
    devices = harness.check_devices(jax.devices(), int(cell["chips"]))
    harness.enable_compile_cache()
    for seed in args.seeds:
        t = time.perf_counter()
        ns = argparse.Namespace(workload=args.workload, seed=seed,
                                seconds=args.seconds, trace=0)
        ctx = harness.build_context(ns, devices, t)
        ctx.control = True
        import importlib
        mode = importlib.import_module("benchmarks.chip.modes."
                                       + ctx.workload["mode"])
        out = mode.run(ctx)
        row = {"seed": seed, "metrics": out.metrics,
               "program": {c.name: c.value for c in out.checks},
               "readings": out.reading.get("readings"),
               "control": out.reading.get("control"),
               "compared_tokens": out.reading.get("compared_tokens"),
               "attempted": out.attempted, "failed": out.failed,
               "memory_peak_bytes": out.memory_peak_bytes,
               "seconds": time.perf_counter() - t}
        line = json.dumps(row)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")


if __name__ == "__main__":
    main()
