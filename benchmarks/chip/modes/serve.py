"""A serving cell: open-loop traffic through ``repro.serving.Server``.

Set-up makes the weights on the device from the seed, builds the server
(its paged pool sized for ``slots`` sequences of ``max_len`` tokens), and
warms every prefill bucket the mix's prompts can fall into and every
decode window, through the server's own ``submit``/``step``. The window
then offers the mix at the cell's fixed rate for ``--seconds``; the
requests that arrived in it are drained. ``ttft_p50_ms`` and
``tpot_p50_ms`` are the medians over all of them.

Correctness: after the window, a sample of finished requests drawn from
the seed, the one with most served tokens among them, is replayed
through the float32 reference (``reference/decoder.py``) with its served
tokens; the number compared is the widest gap by which a served token's
logit lies below the reference's best, at any served position.
"""
from __future__ import annotations

import gc
import os

import numpy as np

from benchmarks.chip import driver, harness, traffic, weights
from benchmarks.chip import trace as tr
from benchmarks.chip.reference import decoder as ref


def _pool(wl: dict):
    from repro.serving import PagedConfig
    kv_rank = wl.get("cur", {}).get("kv_rank", 0)
    return PagedConfig.sized_for(wl["max_len"], wl["slots"],
                                 block_size=wl["block_size"],
                                 cur_kv=bool(kv_rank), kv_rank=kv_rank)


def model(ctx_cfg: dict, wl: dict, seed: int):
    """(ModelConfig, weights, CUR-KV projections or None) of the cell:
    dense, or CUR-compressed as the workload's ``cur`` says."""
    cur = wl.get("cur")
    if cur is None:
        return (harness.model_config(ctx_cfg),
                weights.make(ctx_cfg, seed), None)
    params, proj = weights.make_cur(ctx_cfg, cur, seed)
    return harness.model_config(ctx_cfg, unrolled=True), params, proj


def server_for(ctx_cfg: dict, wl: dict, seed: int):
    from repro.serving import Server
    cfg, params, proj = model(ctx_cfg, wl, seed)
    pc = _pool(wl)
    server = Server(params, cfg, pc, max_concurrency=wl["slots"],
                    max_decode_window=wl["decode_window"])
    if proj is not None:
        # the pool's CUR-KV columns and links are the benchmark's, made
        # from the seed like the weights, in place of the server's own
        server.cache = dict(server.cache, proj=proj)
    return server, pc


def _warm(server, mix: dict, pc, window: int, seed: int) -> None:
    """Compile and run every prefill bucket and decode window once."""
    from repro.serving.sampling import SamplingParams
    rng = np.random.default_rng(seed)
    for b in traffic.buckets(mix, pc.block_size, pc.max_len):
        n = min(b, mix["prompt"]["hi"])
        server.submit(rng.integers(0, server.cfg.vocab_size, n).tolist(),
                      2 * window, sampling=SamplingParams(temperature=0.0))
        server.drain()


def _plant(server, fault: str) -> None:
    """Test hook: break the timed path underneath the harness."""
    if fault == "token":
        # every token the sampler hands back is off by one
        inner = server._sample_batch

        def altered(logits, step_of):
            toks, lps = inner(logits, step_of)
            return (toks + 1) % server.cfg.vocab_size, lps
        server._sample_batch = altered
    elif fault is not None:
        raise ValueError(f"no fault {fault!r} for a serving cell")


def _sample(server, win: driver.Window, seed: int, wl: dict):
    """Finished requests of the window to compare: the longest, then
    others drawn from the seed, until ``check_tokens`` served tokens."""
    done = [server.finished[r] for r in win.rids
            if r in server.finished
            and server.finished[r].finish_reason in ("length", "eos")]
    if not done:
        return []
    done.sort(key=lambda q: q.rid)
    rng = np.random.default_rng([int(seed), 1])
    longest = max(done, key=lambda q: len(q.out_tokens))
    picked = [longest]
    total = len(longest.out_tokens)
    for i in rng.permutation(len(done)):
        if total >= wl["check_tokens"] or len(picked) >= wl["check_max"]:
            break
        q = done[int(i)]
        if q is not longest:
            picked.append(q)
            total += len(q.out_tokens)
    return [(list(q.prompt), list(q.out_tokens)) for q in picked]


def compare(cfg_file: dict, wl: dict, seed: int, sample,
            control: bool = False):
    """Widest reference gap of the served tokens (and of the float8
    control's picks when ``control``), over every sampled request."""
    import jax.numpy as jnp
    d = ref.Dims.of(cfg_file)
    if "cur" in wl:
        w, proj = weights.make_cur(cfg_file, wl["cur"], seed)
    else:
        w, proj = weights.make(cfg_file, seed), None
    widest, widest_ctl, n = 0.0, 0.0, 0
    for prompt, out in sample:
        seq = prompt + out[:-1]
        S = -(-len(seq) // 512) * 512
        toks = np.zeros(S, np.int32)
        toks[:len(seq)] = seq
        served = np.zeros(S, np.int32)
        served[:len(out)] = out
        gap, cgap = ref.served_gaps(
            w, jnp.asarray(toks), len(prompt) - 1, jnp.asarray(served),
            len(out), proj, d=d, control=control)
        widest = max(widest, float(gap.max()))
        widest_ctl = max(widest_ctl, float(cgap.max()))
        n += len(out)
    return widest, widest_ctl, n


def run(ctx: harness.Context) -> harness.Outcome:
    wl, mix = ctx.workload, ctx.traffic
    server, pc = server_for(ctx.config_file, wl, ctx.seed)
    cfg = server.cfg
    _warm(server, mix, pc, wl["decode_window"], ctx.seed)
    _plant(server, ctx.fault)
    requests = traffic.generate(mix, wl["rate_rps"], ctx.seconds, ctx.seed,
                                cfg.vocab_size)

    cap, annotate, on_tick = None, None, None
    if ctx.trace:
        cap = tr.Capture(os.path.join(harness.SCRATCH_DIR, "trace",
                                      ctx.cell["name"]))
        annotate = tr.annotate
        t_from = {}

        def on_tick(now):
            if "t0" not in t_from:
                t_from["t0"] = now
            start = t_from["t0"] + max(0.0, ctx.seconds - wl["trace_s"])
            if not cap.on and "ts" not in t_from and now >= start:
                cap.start()
                t_from["ts"] = ctx.clock()
            elif cap.on and now >= t_from["t0"] + ctx.seconds:
                cap.stop()
                t_from["te"] = ctx.clock()
    setup_s = ctx.clock() - ctx.t_process
    win = driver.run_window(server, requests, ctx.seconds,
                            drain_s=wl["drain_s"], clock=ctx.clock,
                            annotate=annotate, on_tick=on_tick)
    if cap is not None and cap.on:
        cap.stop()
        t_from["te"] = ctx.clock()
    mem = harness.memory_peak(ctx.devices)
    tails = driver.latency_tails(server, win, 50.0)
    reading = {"cfg": cfg, "cfg_file": ctx.config_file, "window": win,
               "peaks": ctx.peaks, "workload": wl, "trace": None,
               "slice": None, "pool": pc}
    reduction = None
    if cap is not None:
        reduction = tr.reduce(cap.load())
        cap.remove()
        reading["trace"] = reduction
        reading["slice"] = (t_from["ts"], t_from["te"])
    sample = _sample(server, win, ctx.seed, wl)
    # free the program's state before the reference takes the chip
    del server
    gc.collect()
    widest, widest_ctl, n_tok = compare(ctx.config_file, wl, ctx.seed,
                                        sample, ctx.control)
    if not sample:
        widest = 1e9                      # nothing finished: not correct
    checks = [harness.Check("widest_gap", widest,
                            wl["limits"]["widest_gap"])]
    reading["control"] = {"widest_gap": widest_ctl}
    reading["compared_tokens"] = n_tok
    metrics = {"setup_s": setup_s, "ttft_p50_ms": 1e3 * tails["ttft_s"],
               "tpot_p50_ms": 1e3 * tails["tpot_s"]}
    return harness.Outcome(metrics=metrics, attempted=tails["attempted"],
                           failed=tails["failed"], checks=checks,
                           reading=reading, memory_peak_bytes=mem,
                           trace=reduction)
