"""Serving entry point: continuous-batching runtime over an (optionally
CUR-compressed) model with a paged, optionally CUR-compressed KV cache.

  PYTHONPATH=src python -m repro.launch.serve --arch olmo-1b --smoke \
      --max-concurrency 8 [--cur-layers 2] [--cur-kv] [--block-size 16] \
      [--paged-kernel auto|on|off] [--prefill-backend auto|fold|reconstruct]

``--smoke`` drives a mixed workload — ragged prompt lengths, staggered
arrivals, per-request generation budgets — through the
``repro.serving.Server``. ``--legacy`` (or a non-attention arch, e.g.
mamba) falls back to the static-batch ``serve.engine.generate`` path.
``--paged-kernel`` sets REPRO_PAGED_KERNEL (the block-table Pallas
decode-attention kernel; auto = TPU only) and ``--prefill-backend`` sets
REPRO_PREFILL_BACKEND (CUR-KV prompt attention: rank-space fold vs the
reconstruct oracle) before the server compiles; both resolve through the
attention-backend registry (``repro.attention``).

Speculative decoding: ``--draft <dir> --spec-k K`` loads a CURed draft
checkpoint (written by ``launch/cure.py --emit-draft``, restored through
its ``template.json`` sidecar) and serves draft-K/verify-1 windows;
``--draft self`` self-drafts with the target's own weights (a sanity
mode: accept rate 1), and ``--draft self:N`` drafts with the target's
own first N layers (zero-training early-exit self-draft — the
bench_serving speculative scenario's draft). ``--draft-kv-rank`` gives
the draft its own CUR-KV pool rank.
"""
import argparse
import os
import time

import jax
import numpy as np

from repro import obs
from repro.obs import loadgen, slo as slo_mod
from repro.configs import ARCHS, get_config, get_smoke
from repro.configs.base import CURConfig
from repro.core import calibrate, compress_model
from repro.data.tokens import DataConfig, SyntheticLM
from repro.launch import compile_cache
from repro.models import init_params
from repro.serve.engine import generate
from repro.serving import PagedConfig, Server
from repro.serving.paged_cache import supports as paged_supports


def make_workload(n_requests: int, vocab: int, *, max_new: int = 16,
                  seed: int = 0, arrival_spacing_s: float = 0.02):
    """Mixed smoke workload: ragged prompts (8..40 tokens), per-request
    new-token budgets (4..max_new), staggered arrival offsets."""
    rng = np.random.RandomState(seed)
    lo = max(1, min(4, max_new))
    reqs = []
    for i in range(n_requests):
        plen = int(rng.choice([8, 12, 16, 24, 32, 40]))
        n_new = int(rng.randint(lo, max_new + 1))
        reqs.append({
            "prompt": rng.randint(0, vocab, size=plen).tolist(),
            "max_new_tokens": n_new,
            "arrival_offset_s": i * arrival_spacing_s,
        })
    return reqs


def run_continuous(server: Server, workload, *, temperature: float = 0.0,
                   verbose: bool = True):
    """Drive the engine against the workload's virtual-time arrivals
    (open-loop: the loadgen driver stamps each request with its
    scheduled arrival, so injection lateness lands in queue wait).
    Returns (finished dict, stats dict)."""
    loadgen.drive(server, workload, temperature=temperature)
    stats = server.stats()
    if verbose:
        print(f"completed {stats['completed']} requests, "
              f"{stats['tokens_generated']} tokens in "
              f"{stats['elapsed_s']:.2f}s "
              f"({stats['tokens_per_s']:.1f} tok/s)")
        print(f"ttft mean {stats['ttft_mean_s']*1e3:.0f}ms "
              f"max {stats['ttft_max_s']*1e3:.0f}ms | queue depth "
              f"mean {stats['queue_depth_mean']:.1f} "
              f"max {stats['queue_depth_max']} | "
              f"steps prefill={stats['n_prefill_steps']} "
              f"decode={stats['n_decode_steps']} "
              f"preempt={stats['n_preemptions']}")
        print(f"decode phase: {stats['decode_tok_s']:.1f} tok/s "
              f"({stats['decode_time_s']:.2f}s) | gather "
              f"{stats['gathered_bytes_per_step']/2**10:.1f} KiB/step")
        print(f"kv cache: {stats['cache_bytes']/2**20:.2f} MiB")
    return server.finished, stats


def main(argv=None):
    """Parse ``argv`` and serve; the paged path returns ``(stats,
    finished)``, the server's stats and its finished requests by id."""
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b", choices=list(ARCHS))
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4,
                    help="legacy static-batch size")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--n-requests", type=int, default=12)
    ap.add_argument("--cur-layers", type=int, default=0,
                    help="CUR-compress this many layers (weights)")
    ap.add_argument("--cur-kv", action="store_true",
                    help="CUR-compress the paged KV cache")
    ap.add_argument("--kv-rank", type=int, default=0,
                    help="CUR-KV rank (0: head_dim // 2)")
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--max-concurrency", type=int, default=8)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--paged-kernel", default=None,
                    choices=["auto", "on", "off"],
                    help="REPRO_PAGED_KERNEL: block-table Pallas decode "
                         "attention (auto: TPU only; on forces interpret "
                         "mode off-TPU). Unset: an exported "
                         "REPRO_PAGED_KERNEL is honored as-is")
    ap.add_argument("--prefill-backend", default=None,
                    choices=["auto", "fold", "reconstruct"],
                    help="REPRO_PREFILL_BACKEND: CUR-KV prompt attention "
                         "backend (auto = rank-space fold; reconstruct "
                         "keeps the full-head-dim oracle). Unset: an "
                         "exported REPRO_PREFILL_BACKEND is honored "
                         "as-is")
    ap.add_argument("--legacy", action="store_true",
                    help="seed static-batch engine instead of the "
                         "continuous-batching runtime")
    ap.add_argument("--draft", default=None,
                    help="speculative decoding: a draft checkpoint dir "
                         "from `cure.py --emit-draft`, 'self' to "
                         "self-draft with the target weights, or "
                         "'self:N' for an early-exit draft from the "
                         "target's first N layers")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="draft tokens per speculative window")
    ap.add_argument("--draft-kv-rank", type=int, default=0,
                    help="CUR-KV rank for the DRAFT's paged pool "
                         "(0: same pool config as the target)")
    # load generation (repro.obs.loadgen) + SLO evaluation
    ap.add_argument("--arrival", default="staggered",
                    choices=["staggered", "burst", "poisson", "gamma",
                             "bursty", "uniform"],
                    help="arrival process: 'staggered' keeps the legacy "
                         "fixed-spacing smoke workload; the rest are "
                         "seeded loadgen processes driven open-loop at "
                         "--rate QPS (virtual-time arrivals: lateness "
                         "counts as queue wait)")
    ap.add_argument("--rate", type=float, default=8.0,
                    help="offered rate (requests/s) for loadgen arrivals")
    ap.add_argument("--shared-prefix", type=float, default=0.0,
                    help="fraction of requests sharing one of 4 fixed "
                         "16-token prompt prefixes")
    ap.add_argument("--workload-trace", default=None,
                    help="replay a loadgen JSONL trace instead of "
                         "generating a workload")
    ap.add_argument("--save-trace", default=None,
                    help="save the generated workload as a JSONL trace")
    ap.add_argument("--slo-ttft-ms", type=float, default=0.0,
                    help="TTFT target (ms); with --slo-tpot-ms, prints "
                         "SLO attainment + goodput after the run")
    ap.add_argument("--slo-tpot-ms", type=float, default=0.0,
                    help="TPOT target (ms) for the SLO evaluation")
    # resilience (repro.serving.resilience) + chaos (repro.testing.chaos)
    ap.add_argument("--max-queue", type=int, default=0,
                    help="bound the admission queue (0: unbounded); a "
                         "full queue applies --overload-policy")
    ap.add_argument("--overload-policy", default="reject",
                    choices=["reject", "shed-oldest", "priority"],
                    help="what a full admission queue does: reject the "
                         "newcomer, shed the oldest queued request, or "
                         "shed the lowest priority class")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="total per-request deadline (ms from arrival); "
                         "expired requests are cancelled with their pool "
                         "blocks freed (0: none)")
    ap.add_argument("--ttft-deadline-ms", type=float, default=0.0,
                    help="TTFT deadline (ms from arrival); a request "
                         "whose first token cannot arrive in time is "
                         "cancelled (0: none)")
    ap.add_argument("--watchdog-s", type=float, default=0.0,
                    help="wall-clock bound per engine step; an over-"
                         "budget step raises ServerWedged with a "
                         "diagnostic snapshot (0: off)")
    ap.add_argument("--chaos", default=None, metavar="PLAN.json",
                    help="inject a seeded FaultPlan (repro.testing.chaos "
                         "JSON spec) into the serve run; the fault event "
                         "log is written to --obs-out/chaos_events.jsonl")
    # observability (repro.obs)
    ap.add_argument("--obs", action="store_true",
                    help="route serving metrics through the process-wide "
                         "registry and write metrics.json/.prom + "
                         "events.jsonl to --obs-out")
    ap.add_argument("--obs-out", default="results/obs/serve",
                    help="directory for obs artifacts")
    ap.add_argument("--trace", action="store_true",
                    help="record engine + per-request lifecycle spans "
                         "and write a Chrome/Perfetto trace.json")
    ap.add_argument("--prof", action="store_true",
                    help="capture a jax.profiler trace of the serve "
                         "loop under --obs-out/jaxprof")
    args = ap.parse_args(argv)
    if args.paged_kernel is not None:
        os.environ["REPRO_PAGED_KERNEL"] = {
            "auto": "auto", "on": "1", "off": "0"}[args.paged_kernel]
    if args.prefill_backend is not None:
        os.environ["REPRO_PREFILL_BACKEND"] = args.prefill_backend
    if args.obs:
        obs.enable()
    tracer = obs.Tracer(enabled=args.trace, process="repro.serve")
    prof = obs.JaxProfiler(
        os.path.join(args.obs_out, "jaxprof") if args.prof else None)

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    if cfg.input_mode != "tokens":
        raise SystemExit(f"{args.arch} uses the embeddings stub")
    params = init_params(jax.random.PRNGKey(0), cfg)

    if args.cur_layers:
        ds = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size,
                                    seq_len=args.prompt_len,
                                    global_batch=args.batch))
        calib = calibrate(params, cfg, [ds.batch_at(1)])
        params, cfg, info = compress_model(
            params, cfg,
            CURConfig(r_max=32, n_compress_layers=args.cur_layers,
                      fold_u=True),
            calib)
        print(f"CUR-compressed {info.layers} "
              f"({info.params_saved/1e3:.0f}k params saved)")

    if args.legacy or not paged_supports(cfg):
        if not args.legacy:
            print(f"{args.arch}: non-attention mixers -> legacy engine")
        ds = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size,
                                    seq_len=args.prompt_len,
                                    global_batch=args.batch))
        prompts = ds.batch_at(0)["tokens"]
        t0 = time.perf_counter()
        out = generate(params, cfg, prompts, args.new_tokens,
                       temperature=args.temperature)
        dt = time.perf_counter() - t0
        print(f"generated {out.tokens.size} tokens in {dt:.2f}s "
              f"({out.tokens.size/dt:.1f} tok/s)")
        print(out.tokens[:2])
        return

    wspec = None
    if args.workload_trace:
        workload = loadgen.load_trace(args.workload_trace)
        print(f"replaying {len(workload)} requests from "
              f"{args.workload_trace}")
    elif args.arrival != "staggered":
        wspec = loadgen.WorkloadSpec(
            n_requests=args.n_requests, rate_qps=args.rate,
            arrival=args.arrival,
            gen=loadgen.LengthDist(kind="fixed", mean=args.new_tokens,
                                   hi=max(1, args.new_tokens)),
            vocab_size=cfg.vocab_size,
            shared_prefix_fraction=args.shared_prefix)
        workload = loadgen.generate(wspec)
        print(f"loadgen: {args.arrival} arrivals at {args.rate:g} rps "
              f"({len(workload)} requests)")
    else:
        workload = make_workload(args.n_requests, cfg.vocab_size,
                                 max_new=args.new_tokens)
    if args.save_trace:
        loadgen.save_trace(args.save_trace, workload, spec=wspec)
        print(f"workload trace -> {args.save_trace}")
    max_len = max(len(r["prompt"]) + r["max_new_tokens"]
                  for r in workload)
    kv_rank = 0
    if args.cur_kv:
        kv_rank = args.kv_rank or max(1, cfg.resolved_head_dim // 2)
    pc = PagedConfig.sized_for(
        max_len, args.max_concurrency, block_size=args.block_size,
        cur_kv=args.cur_kv, kv_rank=kv_rank)
    draft_params, draft_cfg, draft_pc = None, None, None
    if args.draft == "self":
        draft_params = params
    elif args.draft and args.draft.startswith("self:"):
        from repro.serving.speculative import early_exit_draft
        n = int(args.draft.split(":", 1)[1])
        draft_params, draft_cfg = early_exit_draft(params, cfg, n)
        print(f"early-exit self-draft: first {draft_cfg.n_layers} of "
              f"{cfg.n_layers} layers")
    elif args.draft:
        from repro.dist.checkpoint import (CheckpointManager,
                                           load_tree_template)
        template = load_tree_template(
            os.path.join(args.draft, "template.json"))
        step, tree = CheckpointManager(args.draft).restore(template)
        draft_params = tree["params"]
        print(f"draft checkpoint {args.draft} (step {step})")
    if draft_params is not None and args.draft_kv_rank:
        import dataclasses
        draft_pc = dataclasses.replace(pc, cur_kv=True,
                                       kv_rank=args.draft_kv_rank)
    from repro.serving import ResilienceConfig
    res = ResilienceConfig(
        max_queue=args.max_queue, overload_policy=args.overload_policy,
        ttft_deadline_s=args.ttft_deadline_ms / 1e3,
        deadline_s=args.deadline_ms / 1e3, watchdog_s=args.watchdog_s)
    chaos = None
    if args.chaos:
        from repro.testing import ChaosEngine, FaultPlan
        chaos = ChaosEngine(FaultPlan.load(args.chaos))
        print(f"chaos: {len(chaos.plan.faults)} fault streams "
              f"(seed {chaos.plan.seed}) from {args.chaos}")
    server = Server(params, cfg, pc,
                    max_concurrency=args.max_concurrency,
                    draft_params=draft_params, draft_cfg=draft_cfg,
                    draft_pc=draft_pc,
                    spec_k=args.spec_k if draft_params is not None else 0,
                    # with --obs the server records straight into the
                    # process-wide registry, so one export carries both
                    obs=obs.default_registry() if args.obs else None,
                    tracer=tracer, resilience=res, chaos=chaos)
    from repro.attention import use_paged_kernel
    print(f"serving {args.n_requests} requests "
          f"(concurrency {args.max_concurrency}, block {args.block_size}, "
          f"pool {pc.n_blocks} blocks, cur_kv={args.cur_kv}, "
          f"paged_kernel={'on' if use_paged_kernel() else 'off'}, "
          f"prefill={server._prefill_backend}"
          + (f", window={server.window}" if server.window else "")
          + (f", spec_k={server.spec_k}" if server.spec_k else "") + ")")
    with prof.scope():
        finished, stats = run_continuous(server, workload,
                                         temperature=args.temperature)
    if chaos is not None:
        # close any open fault windows (held pool squeezes) and finish
        # whatever the faults displaced, then refresh the report
        chaos.finish(server)
        server.drain()
        stats = server.stats()
    failed = stats.get("failed", {})
    if any(failed.values()) or stats.get("degradation_transitions"):
        print(f"resilience: failed {failed} | degradation level "
              f"{stats['degradation_level']} "
              f"({stats['degradation_transitions']} transitions) | "
              f"step faults {stats['step_faults']}")
    print(f"slo: ttft p50 {stats['ttft_p50_s']*1e3:.0f}ms "
          f"p99 {stats['ttft_p99_s']*1e3:.0f}ms | tpot "
          f"p50 {stats['tpot_p50_s']*1e3:.1f}ms "
          f"p99 {stats['tpot_p99_s']*1e3:.1f}ms | queue-wait "
          f"p50 {stats['queue_wait_p50_s']*1e3:.0f}ms "
          f"p99 {stats['queue_wait_p99_s']*1e3:.0f}ms | "
          f"busy {stats['tokens_per_s_busy']:.1f} tok/s "
          f"(wall {stats['tokens_per_s']:.1f})")
    if args.slo_ttft_ms or args.slo_tpot_ms:
        import math
        spec = slo_mod.SLOSpec(
            ttft_s=args.slo_ttft_ms / 1e3 or math.inf,
            tpot_s=args.slo_tpot_ms / 1e3 or math.inf)
        rep = slo_mod.evaluate(finished.values(), spec,
                               stats["elapsed_s"])
        dec = slo_mod.decompose_stats(stats)
        print(f"slo spec (ttft<={args.slo_ttft_ms:g}ms, "
              f"tpot<={args.slo_tpot_ms:g}ms): attainment "
              f"{rep.attainment:.3f} ({rep.n_meeting}/{rep.n_requests})"
              f" | goodput {rep.goodput_tok_s:.1f} tok/s "
              f"(throughput {rep.throughput_tok_s:.1f})")
        print(f"latency split: queue {dec['queue_wait_frac']:.0%} "
              f"prefill {dec['prefill_frac']:.0%} "
              f"decode {dec['decode_frac']:.0%}")
    if server.spec_k:
        print(f"speculative: accept rate "
              f"{stats['spec_accept_rate']:.3f} over "
              f"{stats['n_spec_windows']} windows "
              f"({stats['n_spec_fallbacks']} fallbacks) | draft "
              f"{stats['spec_draft_time_s']:.2f}s verify "
              f"{stats['spec_verify_time_s']:.2f}s")
    first = finished[min(finished)]
    print(f"request 0: {len(first.out_tokens)} tokens "
          f"{first.out_tokens[:8]}{'...' if len(first.out_tokens) > 8 else ''}")

    if chaos is not None and chaos.events:
        os.makedirs(args.obs_out, exist_ok=True)
        path = chaos.save_events(
            os.path.join(args.obs_out, "chaos_events.jsonl"))
        print(f"  chaos events ({len(chaos.events)}) -> {path}")
    if args.obs or args.trace:
        os.makedirs(args.obs_out, exist_ok=True)
        if args.obs:
            log = obs.JsonlLog(os.path.join(args.obs_out, "events.jsonl"))
            for rid in sorted(finished):
                r = finished[rid]
                log.log("request", rid=rid, tokens=len(r.out_tokens),
                        ttft_s=r.ttft, reason=r.finish_reason,
                        preempted=r.n_preempted)
            log.log("stats", **stats)
            log.close()
            print(f"  obs events -> {log.path}")
        written = obs.write_all(
            args.obs_out, registry=server.obs if args.obs else None,
            tracer=tracer)
        for kind, path in written.items():
            print(f"  obs {kind} -> {path}")
    return stats, finished


if __name__ == "__main__":
    main()
