"""Training driver: real steps on the available devices (CPU smoke / TPU),
with checkpointing, auto-resume, preemption tolerance and elastic restore.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m --reduced \
      --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt --resume auto
"""
from __future__ import annotations

import argparse
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import manager as ckpt
from repro.configs import get_config, get_reduced
from repro.data.pipeline import DataConfig, batch_at
from repro.launch.mesh import dp_axes, make_test_mesh
from repro.distributed import sharding as sh
from repro.models import lm
from repro.optim.trainer import TrainConfig, create_state, make_train_step
from jax.sharding import NamedSharding, PartitionSpec as P


def train(arch: str, *, reduced: bool = True, steps: int = 100,
          global_batch: int = 8, seq_len: int = 128, lr: float = 3e-4,
          ckpt_dir: Optional[str] = None, ckpt_every: int = 50,
          resume: str = "no", seed: int = 0, microbatches: int = 1,
          mesh=None, log_every: int = 10, stop_after: Optional[int] = None):
    cfg = get_reduced(arch) if reduced else get_config(arch)
    mesh = mesh or make_test_mesh()
    tc = TrainConfig(lr=lr, warmup_steps=max(10, steps // 10),
                     total_steps=steps, microbatches=microbatches)
    dc = DataConfig(vocab=cfg.vocab, seq_len=seq_len,
                    global_batch=global_batch, seed=seed)

    key = jax.random.PRNGKey(seed)
    with jax.set_mesh(mesh):
        params = lm.init_params(key, cfg)
        p_sh = sh.param_shardings(params, mesh, fsdp="data", tp="model")
        params = jax.device_put(params, p_sh)
        state = create_state(params)
        start = 0
        if resume == "auto" and ckpt_dir and ckpt.latest_step(ckpt_dir) is not None:
            state = ckpt.restore(state, ckpt_dir)
            start = int(state.step)
            print(f"[train] resumed from step {start}")
        step_fn = jax.jit(make_train_step(cfg, tc))
        dp = dp_axes(mesh)
        bsh = NamedSharding(mesh, P(dp, None))

        losses = []
        t0 = time.time()
        for step in range(start, steps):
            raw = batch_at(dc, step)
            batch = {k: jax.device_put(jnp.asarray(v), bsh)
                     for k, v in raw.items()}
            state, metrics = step_fn(state, batch)
            losses.append(float(metrics["loss"]))
            if step % log_every == 0 or step == steps - 1:
                print(f"[train] step {step:5d} loss {losses[-1]:.4f} "
                      f"lr {float(metrics['lr']):.2e} "
                      f"({(time.time() - t0):.1f}s)")
            if ckpt_dir and (step + 1) % ckpt_every == 0:
                ckpt.save(state, ckpt_dir, step + 1)
            if stop_after is not None and step + 1 - start >= stop_after:
                print(f"[train] simulated preemption after {stop_after} steps")
                break
        if ckpt_dir:
            ckpt.save(state, ckpt_dir, int(state.step))
    return state, losses


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", default="no", choices=["no", "auto"])
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    a = ap.parse_args()
    train(a.arch, reduced=a.reduced, steps=a.steps, global_batch=a.batch,
          seq_len=a.seq, lr=a.lr, ckpt_dir=a.ckpt_dir,
          ckpt_every=a.ckpt_every, resume=a.resume, seed=a.seed,
          microbatches=a.microbatches)


if __name__ == "__main__":
    main()
