"""Distributed training entry point.

On real hardware this runs under the production mesh via pjit with the
same sharding rules the dry-run validates; on CPU it runs the reduced
configs for smoke-scale training. Fault tolerance: checkpoint-managed
auto-resume, straggler watchdog, deterministic skip-ahead data.

  PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --smoke \
      --steps 50 --ckpt-dir results/run1 [--resume]
"""
import argparse

import jax

from repro.configs import ARCHS, get_config, get_smoke
from repro.configs.base import OptimizerConfig, TrainConfig
from repro.data.tokens import DataConfig, SyntheticLM
from repro.dist.checkpoint import CheckpointManager
from repro.dist.compression import init_residuals
from repro.launch import compile_cache
from repro.models import init_params
from repro.optim.adamw import AdamW
from repro.train.train_loop import StragglerWatchdog, train


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b", choices=list(ARCHS))
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--grad-compress", default="none",
                    choices=["none", "ef_int8"],
                    help="error-feedback int8 gradient compression")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    if cfg.input_mode != "tokens":
        raise SystemExit(f"{args.arch} needs the embeddings stub; use the "
                         f"dry-run or smoke tests for this arch")
    params = init_params(jax.random.PRNGKey(0), cfg)
    opt_cfg = OptimizerConfig(lr=args.lr, warmup_steps=10,
                              total_steps=args.steps)
    compress = args.grad_compress == "ef_int8"
    start = 0
    opt_state = residuals = None
    mgr = None
    if args.ckpt_dir:
        mgr = CheckpointManager(args.ckpt_dir, keep_n=3)
        step = mgr.latest_valid_step() if args.resume else None
        if step is not None:
            opt = AdamW(opt_cfg)
            # templates only supply tree structure + leaf shapes, so build
            # them as ShapeDtypeStructs (no moment/residual allocation)
            base = {"params": params,
                    "opt_state": jax.eval_shape(opt.init, params)}
            n_base = len(jax.tree.leaves(base))
            # checkpoints written with --grad-compress carry extra EF
            # residual leaves; pick the template matching what's on disk
            # so toggling the flag between runs still resumes
            ckpt_has_res = mgr.leaf_count(step) > n_base
            template = (dict(base,
                             residuals=jax.eval_shape(init_residuals,
                                                      params))
                        if ckpt_has_res else base)
            start, state = mgr.restore(template, step=step)
            params = state["params"]
            opt_state = state["opt_state"]       # resume Adam moments + step
            if compress and ckpt_has_res:
                residuals = state["residuals"]   # resume EF residuals
            elif compress:
                print("note: checkpoint has no EF residuals "
                      "(written without --grad-compress); starting fresh")
            elif ckpt_has_res:
                print("note: checkpoint carries EF residuals but "
                      "--grad-compress is off; discarding them")
            print(f"resumed from step {start}")

    ds = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size,
                                seq_len=args.seq_len,
                                global_batch=args.batch))
    batches = [ds.batch_at(start + i) for i in range(args.steps - start)]
    wd = StragglerWatchdog()
    train(params, cfg, opt_cfg, batches,
          TrainConfig(microbatch=args.microbatch,
                      grad_compress=args.grad_compress),
          ckpt_manager=mgr, ckpt_every=args.ckpt_every, start_step=start,
          log_every=10, watchdog=wd, opt_state=opt_state,
          residuals=residuals)
    if wd.flagged:
        print(f"straggler watchdog flagged {len(wd.flagged)} slow steps")
    if mgr:
        mgr.wait()


if __name__ == "__main__":
    main()
