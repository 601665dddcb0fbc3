"""Training launcher: local mesh, checkpoint/restart, deterministic data.

CPU-runnable end-to-end (reduced configs); the same step factory and
shardings drive the production mesh in the dry-run.  Fault tolerance:
crash-and-rerun resumes from the newest intact checkpoint with the data
pipeline replaying the exact token stream (stateless ``batch_at(step)``).

  PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
      --reduced --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ck --ckpt-every 20
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import manager as ckpt
from repro.configs import get_config, get_reduced
from repro.data.pipeline import DataConfig, batch_at
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.optim.adamw import OptConfig
from repro.train.step import (ParallelConfig, TrainState, init_train_state,
                              make_train_step)


def main(argv=None) -> dict:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--reduced", action="store_true")
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--lr", type=float, default=3e-3)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--data", type=int, default=1)
    p.add_argument("--model", type=int, default=1)
    p.add_argument("--ckpt-dir", default="")
    p.add_argument("--ckpt-every", type=int, default=0)
    p.add_argument("--grad-compress", action="store_true")
    p.add_argument("--log-every", type=int, default=10)
    args = p.parse_args(argv)
    use_compile_cache()

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    mesh = make_local_mesh(args.data, args.model)
    pcfg = ParallelConfig(fsdp=args.data > 1,
                          grad_compress=args.grad_compress)
    ocfg = OptConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                     total_steps=args.steps)
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                      global_batch=args.batch, seed=args.seed)

    state = init_train_state(cfg, jax.random.key(args.seed), pcfg)
    start_step = 0
    if args.ckpt_dir and ckpt.latest_step(args.ckpt_dir) is not None:
        state = ckpt.restore(state, args.ckpt_dir)
        start_step = int(state.step)
        print(f"[resume] from step {start_step}")

    _, compile_step, state_shardings = make_train_step(cfg, mesh, pcfg, ocfg)
    b0 = batch_at(dcfg, 0)
    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                          (state, b0))
    step_fn = compile_step(*shapes)

    losses = []
    t0 = time.time()
    for step in range(start_step, args.steps):
        batch = batch_at(dcfg, step)
        state, metrics = step_fn(state, batch)
        losses.append(float(metrics["loss"]))
        if step % args.log_every == 0 or step == args.steps - 1:
            print(f"step {step:5d} loss {losses[-1]:.4f} "
                  f"ce {float(metrics['ce']):.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f} "
                  f"lr {float(metrics['lr']):.2e}", flush=True)
        if args.ckpt_dir and args.ckpt_every and \
                (step + 1) % args.ckpt_every == 0:
            ckpt.save(state, args.ckpt_dir, step + 1)
    dt = time.time() - t0
    result = {"first_loss": losses[0] if losses else None,
              "last_loss": losses[-1] if losses else None,
              "steps": len(losses), "seconds": round(dt, 1)}
    print(json.dumps(result, allow_nan=False))
    return result


if __name__ == "__main__":
    main()
