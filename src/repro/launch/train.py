"""End-to-end training launcher.

CPU-scale by default (reduced configs, host mesh); the same code path lowers
on the production mesh (launch/dryrun.py proves it compiles there).  Handles
checkpoint/restart (--resume), elastic re-meshing (restore onto whatever
mesh exists now), straggler telemetry, and plan files from the autotuner.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b --reduced \
      --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/run1
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp

from repro.configs.registry import get_config
from repro.core.policy import RegionPlan, default_plan, null_plan
from repro.data.pipeline import DataConfig, Prefetcher, batch_at, iterate
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import model as model_mod
from repro.optim import adamw
from repro.train import checkpoint as ckpt_mod
from repro.train import trainer
from repro.train.elastic import StepWatchdog


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--plan", default="", help="tuned RegionPlan json")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--fail-at-step", type=int, default=0,
                    help="simulate a node failure (tests)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = model_mod.build(cfg)

    mesh = make_host_mesh(data=len(jax.devices()))
    plan = (RegionPlan.from_json(open(args.plan).read(), mesh=mesh)
            if args.plan else default_plan(mesh, "train"))
    if len(jax.devices()) == 1:
        plan = null_plan()

    key = jax.random.PRNGKey(args.seed)
    params = model.init(key)
    opt_state = adamw.init_state(params)
    start_step = 0

    if args.resume and args.ckpt_dir:
        found = ckpt_mod.latest_valid(args.ckpt_dir)
        if found:
            state, start_step = ckpt_mod.restore(
                args.ckpt_dir, {"params": params, "opt": opt_state})
            params, opt_state = state["params"], state["opt"]
            print(f"resumed from step {start_step}")

    opt_cfg = adamw.AdamWConfig(lr=args.lr)
    step_fn = jax.jit(trainer.make_train_step(
        model, plan, opt_cfg=opt_cfg, unroll=False,
        microbatch=args.microbatch, schedule_total=args.steps))

    data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                          global_batch=args.batch, seed=args.seed)
    data = Prefetcher(iterate(data_cfg, start_step))
    watchdog = StepWatchdog()

    t_start = time.time()
    for step in range(start_step, args.steps):
        batch = next(data)
        if cfg.family == "encdec":
            batch = dict(batch, frames=jnp.zeros(
                (args.batch, cfg.enc_len, cfg.d_model), jnp.bfloat16))
        if cfg.frontend == "vision_patches":
            batch = dict(batch, vision_embeds=jnp.zeros(
                (args.batch, 8, cfg.d_model), jnp.bfloat16))
        watchdog.start()
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        jax.block_until_ready(metrics["loss"])
        straggler = watchdog.stop(step)
        if straggler:
            print(f"[watchdog] step {step} flagged as straggler")
        if step % args.log_every == 0 or step == args.steps - 1:
            print(f"step {step:5d} loss {float(metrics['loss']):.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f} "
                  f"lr {float(metrics['lr']):.2e}")
        if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
            ckpt_mod.save(args.ckpt_dir, step + 1,
                          {"params": params, "opt": opt_state},
                          meta={"arch": cfg.name})
        if args.fail_at_step and step + 1 == args.fail_at_step:
            print(f"simulating node failure at step {step + 1}")
            raise SystemExit(42)
    dt = time.time() - t_start
    tok = (args.steps - start_step) * args.batch * args.seq
    print(f"done: {dt:.1f}s, {tok/dt:.0f} tok/s, final loss "
          f"{float(metrics['loss']):.4f}")
    return float(metrics["loss"])


if __name__ == "__main__":
    main()
