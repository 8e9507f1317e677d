"""End-to-end training driver.

CPU-scale demo:   python -m repro.launch.train --arch qwen3-0.6b --smoke \
                      --steps 50 --batch 4 --seq 64
Production shape: same flags minus --smoke, plus a real mesh (the dry-run
proves those configs compile; actually running them needs TPUs).

Fault tolerance is on by default: checkpoints every ``--ckpt-every`` steps,
resumes from the latest checkpoint, the watchdog logs stragglers, and the
deterministic pipeline replays the stream on restart.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp

from ..configs import SHAPES, get_config, get_smoke_config
from ..core.backend import enable_compile_cache
from ..core.executor import plan_and_compile
from ..core.ir import SystemCatalog
from ..core.plan_cache import (default_plan_cache, load_plan_cache,
                               save_plan_cache)
from ..data.pipeline import DataConfig, PrefetchPipeline
from ..models import build_model
from ..models.lm import CATALOG
from ..train.checkpoint import (latest_checkpoint, restore_checkpoint,
                                save_checkpoint, checkpoint_step)
from ..train.fault_tolerance import Watchdog
from ..train.optim import cosine_schedule, make_optimizer
from ..train.train_step import init_state, make_train_step
from .mesh import syscat_for_mesh


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--buffering", action="store_true")
    ap.add_argument("--engines", default="xla",
                    help="comma-separated engine names the planner may use "
                         "(registry: xla, pallas)")
    ap.add_argument("--plan-cache-dir", default=None,
                    help="persist the plan cache here and warm-start "
                         "planning from it on relaunch")
    ap.add_argument("--plan-threads", type=int, default=1,
                    help="generate physical candidates per scan-group in "
                         "this many threads (identical plans, lower "
                         "planning wall time)")
    ap.add_argument("--explain", action="store_true",
                    help="print the staged plan pipeline's EXPLAIN report")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = (get_smoke_config(args.arch) if args.smoke
           else get_config(args.arch))
    if args.smoke:
        cfg = cfg.replace(dtype="float32")
    model = build_model(cfg)
    syscat = SystemCatalog()

    plan = model.build_plan(args.batch, args.seq, mode="train")
    # planned through the content-hashed plan cache: re-launching the same
    # workload (or rebuilding the step in-process) reuses the staged plan;
    # with --plan-cache-dir the cache warm-starts across process restarts
    pc = default_plan_cache()
    if args.plan_cache_dir:
        load_plan_cache(args.plan_cache_dir, pc)
    fwd = plan_and_compile(plan, CATALOG, syscat, buffering=args.buffering,
                           global_batch=args.batch,
                           engines=tuple(args.engines.split(",")),
                           plan_threads=args.plan_threads)
    if args.plan_cache_dir:
        n = save_plan_cache(pc, args.plan_cache_dir)
        print(f"[train] plan cache: {pc.stats()['hits']} hits, "
              f"persisted {n} new staged plan(s) to {args.plan_cache_dir}")
    print(f"[train] plan {fwd.plan_id[:12]} choices: "
          f"{[(r['pattern'], r['chosen']) for r in fwd.report]}")
    if args.explain:
        print(fwd.explain())
    if fwd.buffering.enabled:
        print(f"[train] buffering: {fwd.buffering.num_microbatches} "
              f"microbatches over {len(fwd.buffering.chains)} chains")

    opt = make_optimizer(cfg.optimizer, cosine_schedule(
        args.lr, max(args.steps // 20, 1), args.steps))
    nmb = (fwd.buffering.num_microbatches if fwd.buffering.enabled
           else args.microbatches)
    step = jax.jit(make_train_step(fwd, opt, num_microbatches=nmb,
                                   grad_dtype="float32"))

    params, _ = model.init_params(jax.random.key(args.seed))
    state = init_state(params, opt)

    ckpt_dir = args.ckpt_dir or f"checkpoints/{cfg.name}"
    start = 0
    latest = latest_checkpoint(ckpt_dir)
    if latest:
        state = restore_checkpoint(latest, jax.eval_shape(lambda: state))
        start = checkpoint_step(latest)
        print(f"[train] resumed from {latest} at step {start}")

    dc = DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                    global_batch=args.batch, seed=args.seed,
                    frontend_tokens=cfg.frontend_tokens,
                    d_model=cfg.d_model, encdec=cfg.family == "encdec",
                    dtype=str(model.dtype))
    pipe = PrefetchPipeline(dc, start_step=start)
    wd = Watchdog()
    t_last = time.time()
    try:
        for i, (step_idx, batch) in enumerate(pipe):
            if step_idx >= args.steps:
                break
            jbatch = {k: jnp.asarray(v) for k, v in batch.items()}
            state, metrics = step(state, jbatch)
            dt = time.time() - t_last
            t_last = time.time()
            if wd.observe(step_idx, dt):
                print(f"[train] straggler step {step_idx}: {dt:.2f}s "
                      f"(median {wd.median():.2f}s) — checkpointing")
                save_checkpoint(ckpt_dir, step_idx + 1, state)
            if step_idx % args.log_every == 0:
                print(f"[train] step {step_idx:5d} "
                      f"loss {float(metrics['loss']):.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f} "
                      f"{dt * 1e3:.0f} ms")
            if (step_idx + 1) % args.ckpt_every == 0:
                save_checkpoint(ckpt_dir, step_idx + 1, state)
    finally:
        pipe.close()
    save_checkpoint(ckpt_dir, args.steps, state)
    print(f"[train] done at step {args.steps}; "
          f"final loss {float(metrics['loss']):.4f}")
    return float(metrics["loss"])


if __name__ == "__main__":
    main()
