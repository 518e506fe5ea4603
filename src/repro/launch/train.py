"""End-to-end training driver.

Runs real training (CPU: reduced configs; TPU: full configs) with the complete
substrate: sharded params/optimizer, deterministic data pipeline, CARMEN
engine modes, checkpoint/restart.

    PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --reduced \
        --steps 50 --batch 8 --seq 64 --mode exact --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, get_config, reduced as reduce_cfg
from repro.core import EngineContext, FXP8, FXP16, PrecisionPolicy
from repro.data.pipeline import TokenPipeline
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import get_model
from repro.sharding import partition
from repro.train import checkpoint, optimizer as opt
from repro.train.train_loop import TrainConfig, make_train_step


def engine_ctx(mode: str, compute_dtype) -> EngineContext:
    if mode == "exact":
        return EngineContext(mode="exact", compute_dtype=compute_dtype)
    fmt = FXP16 if mode.endswith("16") else FXP8
    return EngineContext(
        mode=mode.replace("16", ""), policy=PrecisionPolicy.accurate(fmt),
        compute_dtype=compute_dtype,
    )


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default="olmo-1b")
    ap.add_argument("--reduced", action="store_true", help="small-config CPU run")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--mode", choices=["exact", "carmen", "carmen16", "int8"], default="exact")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--production-mesh", action="store_true")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg)
    model = get_model(cfg)
    dtype = jnp.float32 if args.reduced else cfg.compute_dtype
    ctx = engine_ctx(args.mode, dtype)
    tcfg = TrainConfig(
        optimizer=opt.AdamWConfig(lr=args.lr, warmup_steps=10, total_steps=args.steps),
        microbatches=args.microbatches,
        remat=not args.reduced,
    )

    mesh = make_production_mesh() if args.production_mesh else make_host_mesh()
    pipe = TokenPipeline(cfg, args.seq, args.batch)
    with jax.set_mesh(mesh):
        specs = model.specs()
        param_sh, _ = partition.param_shardings(specs, mesh)
        params = jax.jit(
            lambda k: model.init(k, dtype), out_shardings=param_sh
        )(jax.random.PRNGKey(0))
        opt_state = opt.init_state(params)
        start_step = 0
        if args.resume and args.ckpt_dir:
            latest = checkpoint.latest_step(args.ckpt_dir)
            if latest is not None:
                params = checkpoint.restore(args.ckpt_dir, latest, params, shardings=param_sh)
                opt_state = checkpoint.restore(
                    args.ckpt_dir + "/opt", latest, opt_state
                )
                start_step = latest
                print(f"resumed from step {latest}")

        step_fn = jax.jit(make_train_step(model, ctx, tcfg), donate_argnums=(0, 1))
        t0, losses = time.time(), []
        for step in range(start_step, args.steps):
            batch = pipe.batch(step)
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            losses.append(float(metrics["loss"]))
            if step % max(args.steps // 10, 1) == 0 or step == args.steps - 1:
                print(f"step {step:5d} loss {losses[-1]:.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f} lr {float(metrics['lr']):.2e}")
            if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
                checkpoint.save(args.ckpt_dir, step + 1, params, background=True)
                checkpoint.save(args.ckpt_dir + "/opt", step + 1, opt_state)
        dt = time.time() - t0
        tok_s = args.batch * args.seq * (args.steps - start_step) / max(dt, 1e-9)
        print(f"done: {args.steps - start_step} steps in {dt:.1f}s "
              f"({tok_s:.0f} tok/s), loss {losses[0]:.4f} -> {losses[-1]:.4f}")
        return losses


if __name__ == "__main__":
    main()
