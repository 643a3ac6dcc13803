"""One-shot CURing at paper speed: the end-to-end compression story.

    PYTHONPATH=src python -m repro.launch.cure --arch olmo-1b --smoke \
        --layers 2 --r-max 32 --report results/cure/olmo.json

Stages (each timed, mirroring the paper's Table-1 "compression time"
claim): init arch -> calibrate (jitted, device-resident accumulators)
-> compress (batched shape-class pipeline by default) -> fold C@U ->
save via ``dist.CheckpointManager`` -> smoke-generate through
``repro.serving`` (mamba archs fall back to the legacy static engine).

``--report`` writes a JSON whose fields map onto the paper's Table 1:
``stages_s.total`` ~ compression Time (s), ``params.reduction_pct_model``
~ parameter reduction, ``weights[].rel_fro_err`` ~ per-weight relative
Frobenius error (and ``bound``/``bound_on`` the Theorem 3.1 bound and
the matrix it is valid for).
"""
import argparse
import dataclasses
import json
import os
import time

import jax
import numpy as np

from repro import obs
from repro.configs import ARCHS, get_config, get_smoke
from repro.configs.base import CURConfig
from repro.core import calibrate, compress_model
from repro.data.tokens import DataConfig, SyntheticLM
from repro.dist.checkpoint import CheckpointManager, save_tree_template
from repro.launch import compile_cache
from repro.models import init_params
from repro.plan import CompressionPlan, config_hash, plan_for_model
from repro.serve.engine import generate
from repro.serving import PagedConfig, SamplingParams, Server
from repro.serving.paged_cache import supports as paged_supports


def _smoke_generate(params, cfg, *, n_requests: int, prompt_len: int,
                    new_tokens: int, max_concurrency: int, seed: int):
    """Drive the compressed model through the serving runtime (paged
    continuous batching when the arch supports it, else the legacy
    static engine). Returns (n_tokens, engine_name)."""
    rng = np.random.RandomState(seed)
    if paged_supports(cfg):
        max_len = prompt_len + new_tokens
        pc = PagedConfig.sized_for(max_len, max_concurrency)
        server = Server(params, cfg, pc, max_concurrency=max_concurrency)
        for i in range(n_requests):
            prompt = rng.randint(0, cfg.vocab_size, size=prompt_len).tolist()
            server.submit(prompt, new_tokens,
                          sampling=SamplingParams(temperature=0.0, seed=i))
        finished = server.drain()
        return sum(len(r.out_tokens) for r in finished.values()), "serving"
    prompts = rng.randint(0, cfg.vocab_size,
                          size=(n_requests, prompt_len)).astype(np.int32)
    out = generate(params, cfg, prompts, new_tokens)
    return int(out.tokens.size), "legacy"


def _plan(args, params, cfg, ccfg, calib):
    """Per-weight ranks from --plan or a --budget-*, else uniform:
    returns (ccfg, plan, plan_source, layers)."""
    if args.plan:
        plan = CompressionPlan.load(args.plan)
        if plan.provenance.get("cfg_hash") != config_hash(cfg):
            print(f"  WARNING: plan {args.plan} was computed for a "
                  f"different model config (cfg_hash mismatch) — "
                  f"selections may not reproduce")
        # the plan pins everything the key stream + selections depend on
        return (plan.to_cur_config(
            dataclasses.replace(ccfg, pipeline=args.pipeline)),
            plan, "file", plan.layers)
    if args.budget is not None:
        kind, value = args.budget
        plan, _ = plan_for_model(
            params, cfg, ccfg, calib, budget_kind=kind, budget_value=value,
            n_layers=args.layers, grid=args.grid, solver=args.solver,
            arch=cfg.name)
        if args.emit_plan:
            os.makedirs(os.path.dirname(args.emit_plan) or ".",
                        exist_ok=True)
            plan.save(args.emit_plan)
        return (plan.to_cur_config(
            dataclasses.replace(ccfg, pipeline=args.pipeline)),
            plan, "budget", plan.layers)
    return ccfg, None, "uniform", None


def cure(args) -> dict:
    # per-stage timing lives on a span tracer (always on — it IS the
    # stages_s report, from the undotted stage spans; the dotted ones
    # are calibrate's and compress_model's sub-steps); --trace
    # additionally writes the Perfetto JSON
    tracer = getattr(args, "tracer", None) or obs.Tracer(
        enabled=True, process="repro.cure")
    if getattr(args, "obs", False):
        obs.enable()
    prof = obs.JaxProfiler(
        os.path.join(getattr(args, "obs_out", None) or "results/obs/cure",
                     "jaxprof")
        if getattr(args, "prof", False) else None)
    t_total = time.perf_counter()

    # ---- init ---------------------------------------------------------
    with tracer.span("init"):
        cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
        if cfg.input_mode != "tokens":
            raise SystemExit(f"{args.arch} uses the embeddings stub")
        params = jax.block_until_ready(
            init_params(jax.random.PRNGKey(args.seed), cfg))

    # ---- calibrate ----------------------------------------------------
    ds = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size,
                                seq_len=args.calib_len,
                                global_batch=args.calib_batch,
                                seed=args.seed))
    batches = [ds.batch_at(i) for i in range(args.calib_batches)]
    ccfg = CURConfig(r_max=args.r_max, n_compress_layers=args.layers,
                     selection=args.selection, svd=args.svd,
                     fold_u=not args.no_fold, pipeline=args.pipeline,
                     seed=args.seed)
    # one --prof capture holds calibrate, plan and compress: the
    # tracer's spans land in it beside the device's operations
    with prof.scope():
        with tracer.span("calibrate"):
            calib = calibrate(params, cfg, batches, tracer=tracer)

        # ---- plan (repro.plan: budget -> per-weight ranks) ------------
        with tracer.span("plan"):
            ccfg, plan, plan_source, layers = _plan(
                args, params, cfg, ccfg, calib)

        # ---- compress + fold ------------------------------------------
        t0 = time.perf_counter()
        cparams, ccfg_model, info = compress_model(
            params, cfg, ccfg, calib, layers=layers, tracer=tracer)
        dt = time.perf_counter() - t0
    # fold time is measured inside compress_model; split the wall span
    # into back-to-back compress/fold spans so durations() reports both
    tracer.add_span("compress", t0, dt - info.seconds_fold)
    tracer.add_span("fold", t0 + dt - info.seconds_fold,
                    info.seconds_fold)

    # ---- save ---------------------------------------------------------
    with tracer.span("save"):
        mgr = CheckpointManager(args.ckpt_dir, keep_n=1)
        mgr.save(0, {"params": cparams})
        save_tree_template(os.path.join(args.ckpt_dir, "template.json"),
                           {"params": cparams})

    # ---- draft (self-drafted speculative decoding companion) ----------
    draft_report = None
    if args.emit_draft:
        t0 = time.perf_counter()
        dccfg = CURConfig(r_max=args.r_max,
                          n_compress_layers=args.draft_layers,
                          selection=args.selection, svd=args.svd,
                          fold_u=not args.no_fold, pipeline=args.pipeline,
                          seed=args.seed)
        dplan, _ = plan_for_model(
            params, cfg, dccfg, calib, budget_kind="params",
            budget_value=args.draft_budget_params,
            n_layers=args.draft_layers, grid=args.grid,
            solver=args.solver, arch=cfg.name)
        dccfg = dplan.to_cur_config(
            dataclasses.replace(dccfg, pipeline=args.pipeline))
        dparams, _, dinfo = compress_model(params, cfg, dccfg, calib,
                                           layers=dplan.layers)
        draft_dir = os.path.join(args.ckpt_dir, "draft")
        dmgr = CheckpointManager(draft_dir, keep_n=1)
        dmgr.save(0, {"params": dparams})
        save_tree_template(os.path.join(draft_dir, "template.json"),
                           {"params": dparams})
        dplan.save(os.path.join(draft_dir, "plan.json"))
        tracer.add_span("draft", t0, time.perf_counter() - t0)
        dw = dinfo.weights
        d_before = sum(x.params_before for x in dw)
        d_after = sum(x.params_after for x in dw)
        draft_report = {
            "ckpt_dir": draft_dir,
            "budget_params": args.draft_budget_params,
            "layers_compressed": dinfo.layers,
            "ranks": {x.key: x.rank for x in dw},
            "params_deployed": d_after,
            "realized_fraction": round(d_after / max(d_before, 1), 6),
            "model_params_saved": dinfo.params_saved,
        }

    # ---- smoke-generate -----------------------------------------------
    with tracer.span("generate"):
        n_tokens, engine = _smoke_generate(
            cparams, ccfg_model, n_requests=args.n_requests,
            prompt_len=args.prompt_len, new_tokens=args.new_tokens,
            max_concurrency=args.max_concurrency, seed=args.seed)

    stages = {k: v for k, v in tracer.durations().items() if "." not in k}
    stages["total"] = time.perf_counter() - t_total

    w = info.weights
    before = sum(x.params_before for x in w)
    after_deployed = sum(x.params_after for x in w)
    # realized-vs-requested budget + the per-weight assigned ranks, for
    # every run (uniform runs report requested=None) — Table 1 rows are
    # only meaningful alongside the allocation that produced them
    plan_report = {
        "source": plan_source,                    # uniform | budget | file
        "ranks": {x.key: x.rank for x in w},
        "budget": {
            "kind": plan.budget_kind if plan else "params",
            "requested": plan.budget_requested if plan else None,
            "realized_params": after_deployed,
            "realized_fraction": round(after_deployed / max(before, 1), 6),
            "feasible": plan.feasible if plan else None,
        },
    }
    if plan:
        plan_report["solver"] = plan.solver
        plan_report["provenance"] = dict(plan.provenance)
        plan_report["budget"]["realized"] = dict(plan.realized)
    report = {
        "arch": args.arch,
        "smoke": args.smoke,
        "pipeline": args.pipeline,
        "svd": args.svd,
        "selection": args.selection,
        "fold": not args.no_fold,
        "r_max": args.r_max,
        "layers_compressed": info.layers,
        "n_weights": len(w),
        "plan": plan_report,
        "stages_s": {k: round(v, 4) for k, v in stages.items()},
        "params": {
            "model_total": cfg.param_count(),
            "targeted_before": before,
            "after_unfolded": sum(x.params_after_unfolded for x in w),
            "after_folded": sum(x.params_after_folded for x in w),
            "after_deployed": sum(x.params_after for x in w),
            "saved_deployed": info.params_saved,
            "saved_unfolded": info.params_saved_unfolded,
            "saved_folded": info.params_saved_folded,
            "reduction_pct_model": round(
                100.0 * info.params_saved / max(cfg.param_count(), 1), 3),
        },
        "weights": [{
            "key": x.key, "layer": x.layer, "name": x.name,
            "expert": x.expert, "shape": list(x.shape),
            "rank": x.rank,
            "rel_fro_err": round(x.fro_err / max(x.fro_w, 1e-30), 6),
            "bound": None if np.isnan(x.bound) else round(x.bound, 4),
            "bound_on": x.bound_on,
            "seconds": round(x.seconds, 5),
        } for x in w],
        "generate": {"tokens": n_tokens, "engine": engine,
                     "tok_per_s": round(
                         n_tokens / max(stages["generate"], 1e-9), 1)},
    }
    if draft_report is not None:
        report["draft"] = draft_report
    return report


def main(argv=None):
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b", choices=list(ARCHS))
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--layers", type=int, default=2,
                    help="CUR-compress this many layers (angular choice)")
    ap.add_argument("--r-max", type=int, default=32)
    ap.add_argument("--selection", default="wanda_deim",
                    choices=("wanda_deim", "wanda", "deim", "weight",
                             "random"))
    ap.add_argument("--svd", default="randomized",
                    choices=("exact", "randomized"),
                    help="randomized is the paper-speed default; exact "
                         "is the paper-faithful reference")
    ap.add_argument("--pipeline", default="batched",
                    choices=("batched", "loop"))
    ap.add_argument("--no-fold", action="store_true",
                    help="deploy {C,U0,dU,R} (healing form) instead of "
                         "the folded {CU,R}")
    # budget-driven planning (repro.plan)
    ap.add_argument("--plan", default=None,
                    help="execute a saved CompressionPlan JSON (pins "
                         "ranks/layers/selection/svd/seed — reproduces "
                         "the emitting run's exact selections)")
    ap.add_argument("--budget-params", type=float, default=None,
                    help="<=1: fraction of targeted dense params; >1: "
                         "absolute count — allocates per-weight ranks")
    ap.add_argument("--budget-bytes", type=float, default=None)
    ap.add_argument("--budget-latency-ms", type=float, default=None)
    ap.add_argument("--solver", default="greedy", choices=("greedy", "dp"))
    ap.add_argument("--grid", default=None,
                    help="comma-separated planning rank grid")
    ap.add_argument("--emit-plan", default=None,
                    help="write the allocated plan JSON here (budget "
                         "runs only)")
    # speculative-decoding draft companion
    ap.add_argument("--emit-draft", action="store_true",
                    help="also compress the SAME checkpoint to an "
                         "aggressive plan-allocated budget and save it "
                         "under <ckpt-dir>/draft — the self-drafted "
                         "speculative-decoding draft model "
                         "(serve with --draft <ckpt-dir>/draft)")
    ap.add_argument("--draft-budget-params", type=float, default=0.35,
                    help="draft parameter budget (fraction of targeted "
                         "dense params; repro.plan allocates the ranks)")
    ap.add_argument("--draft-layers", type=int, default=None,
                    help="layers to compress in the draft "
                         "(default: --layers)")
    ap.add_argument("--calib-batches", type=int, default=2)
    ap.add_argument("--calib-batch", type=int, default=2)
    ap.add_argument("--calib-len", type=int, default=64)
    ap.add_argument("--n-requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--max-concurrency", type=int, default=4)
    ap.add_argument("--ckpt-dir", default=None,
                    help="default results/cure/<arch>")
    ap.add_argument("--report", default=None,
                    help="write the per-stage timing/params/error JSON "
                         "here (Table-1 mapping)")
    ap.add_argument("--seed", type=int, default=0)
    # observability (repro.obs)
    ap.add_argument("--obs", action="store_true",
                    help="enable the process-wide metrics registry and "
                         "write metrics.json/.prom to --obs-out")
    ap.add_argument("--obs-out", default="results/obs/cure",
                    help="directory for obs artifacts")
    ap.add_argument("--trace", action="store_true",
                    help="write a Chrome/Perfetto trace.json of the "
                         "stage spans to --obs-out")
    ap.add_argument("--prof", action="store_true",
                    help="capture one jax.profiler trace of calibrate, "
                         "plan and compress under --obs-out/jaxprof")
    args = ap.parse_args(argv)
    if args.ckpt_dir is None:
        args.ckpt_dir = os.path.join("results", "cure", args.arch)
    budgets = [(k, v) for k, v in (
        ("params", args.budget_params), ("bytes", args.budget_bytes),
        ("latency_ms", args.budget_latency_ms)) if v is not None]
    if len(budgets) > 1 or (budgets and args.plan):
        raise SystemExit("pass at most one of --plan / --budget-params / "
                         "--budget-bytes / --budget-latency-ms")
    args.budget = budgets[0] if budgets else None
    if args.grid:
        args.grid = tuple(int(x) for x in args.grid.split(","))
    if args.draft_layers is None:
        args.draft_layers = args.layers

    args.tracer = obs.Tracer(
        enabled=True, process="repro.cure") if args.trace else None
    report = cure(args)
    if args.obs or args.trace:
        written = obs.write_all(
            args.obs_out,
            registry=obs.default_registry() if args.obs else None,
            tracer=args.tracer)
        for kind, path in written.items():
            print(f"  obs {kind} -> {path}")

    s = report["stages_s"]
    p = report["params"]
    print(f"cured {args.arch}{' (smoke)' if args.smoke else ''}: "
          f"{report['n_weights']} weights in layers "
          f"{report['layers_compressed']}")
    print("  " + "  ".join(f"{k}={s[k]:.3f}s" for k in
                           ("init", "calibrate", "plan", "compress",
                            "fold", "save", "draft", "generate", "total")
                           if k in s))
    if "draft" in report:
        d = report["draft"]
        print(f"  draft: {d['params_deployed']/1e3:.0f}k params "
              f"(fraction {d['realized_fraction']:.3f}) ranks "
              f"{d['ranks']} -> {d['ckpt_dir']}")
    pl = report["plan"]
    if pl["source"] != "uniform":
        b = pl["budget"]
        print(f"  plan[{pl['source']}/{pl.get('solver', '?')}] "
              f"budget[{b['kind']}]: requested {b['requested']:.4g} -> "
              f"realized fraction {b['realized_fraction']:.3f} "
              f"ranks {pl['ranks']}")
    print(f"  params: targeted {p['targeted_before']/1e3:.0f}k -> "
          f"deployed {p['after_deployed']/1e3:.0f}k "
          f"(folded {p['after_folded']/1e3:.0f}k / unfolded "
          f"{p['after_unfolded']/1e3:.0f}k); "
          f"model reduction {p['reduction_pct_model']:.2f}%")
    worst = max(report["weights"], key=lambda x: x["rel_fro_err"],
                default=None)
    if worst:
        print(f"  worst rel fro err: {worst['rel_fro_err']:.4f} "
              f"({worst['key']})")
    print(f"  generated {report['generate']['tokens']} tokens via "
          f"{report['generate']['engine']} "
          f"({report['generate']['tok_per_s']:.1f} tok/s)")
    if args.report:
        os.makedirs(os.path.dirname(args.report) or ".", exist_ok=True)
        with open(args.report, "w") as f:
            json.dump(report, f, indent=2)
        print(f"  report -> {args.report}")
    return report


if __name__ == "__main__":
    main()
