"""Find a serving cell's knee once, on the chip: offer its traffic mix at
several fixed rates, one window each, in one process, and report per
rate the share of requests that met both latency limits and the tails:

    python3 benchmarks/chip/sweep.py --workload <name> --seconds 40 \
        --rates 0.5 1 2 4 --ttft-ms 3000 --tpot-ms 100 [--seed 7]

The knee is the highest rate at which at least 90% of the requests meet
both limits; a cell's fixed ``rate_rps`` is derived from
it once and written into its workload file. Not run by the benchmark.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from benchmarks.chip import driver, harness, traffic  # noqa: E402
from benchmarks.chip.modes import serve  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--ttft-ms", type=float, required=True)
    ap.add_argument("--tpot-ms", type=float, required=True)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    import jax
    bench = harness.load_benchmark()
    cell = harness.find_cell(bench, args.workload)
    devices = harness.check_devices(jax.devices(), int(cell["chips"]))
    harness.enable_compile_cache()
    ns = argparse.Namespace(workload=args.workload, seed=args.seed,
                            seconds=args.seconds, trace=0)
    ctx = harness.build_context(ns, devices, time.perf_counter())
    wl, mix = ctx.workload, ctx.traffic
    server, pc = serve.server_for(ctx.config_file, wl, args.seed)
    cfg = server.cfg
    serve._warm(server, mix, pc, wl["decode_window"], args.seed)
    for rate in args.rates:
        reqs = traffic.generate(mix, rate, args.seconds, args.seed,
                                cfg.vocab_size)
        win = driver.run_window(server, reqs, args.seconds,
                                drain_s=wl["drain_s"])
        met = 0
        for rid in win.rids:
            ttft, tpot = driver.request_latency(server.finished.get(rid))
            if (ttft is not None and ttft * 1e3 <= args.ttft_ms
                    and (tpot or 0.0) * 1e3 <= args.tpot_ms):
                met += 1
        n = len(win.rids)
        t = driver.latency_tails(server, win, 90.0)
        t50 = driver.latency_tails(server, win, 50.0)
        pre = win.counters1["prefill_time_s"] - win.counters0["prefill_time_s"]
        dec = win.counters1["decode_time_s"] - win.counters0["decode_time_s"]
        print(json.dumps({
            "rate_rps": rate, "requests": n, "attainment": met / max(n, 1),
            "ttft_p50_ms": 1e3 * t50["ttft_s"], "ttft_p90_ms": 1e3 * t["ttft_s"],
            "tpot_p50_ms": 1e3 * t50["tpot_s"], "tpot_p90_ms": 1e3 * t["tpot_s"],
            "failed": t["failed"], "drain_s": win.t_drained - win.t1,
            "prefill_share": pre / max(pre + dec, 1e-9),
            "late_max_s": win.late_max_s}), flush=True)


if __name__ == "__main__":
    main()
