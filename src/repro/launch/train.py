"""End-to-end training driver.

Composes every substrate layer: runtime-resolved distribution plan +
microbatching (the paper's technique), deterministic sharded data,
ZeRO-1 AdamW, async atomic checkpoints, failure injection + restart,
straggler monitoring.

Runs anywhere: ``--mesh local`` uses whatever devices the host exposes
(1 CPU in CI), ``--mesh prod`` the 16x16 production mesh.

  PYTHONPATH=src python -m repro.launch.train \\
      --arch smollm-135m --reduced --steps 60 --batch 8 --seq 128
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import Checkpointer
from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.core.mapper import MappingPolicy
from repro.data import data_config_for, make_batch
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh, make_production_mesh
from repro.launch.steps import (StepConfig, init_train_state, make_train_step,
                                resolve_microbatches)
from repro.models import build_model
from repro.optim import AdamWConfig
from repro.runtime import sharding as shd
from repro.runtime.fault import FailureInjector, SimulatedFailure
from repro.runtime.straggler import StragglerMonitor


@dataclasses.dataclass
class TrainRun:
    losses: list
    restarts: int
    steps: int
    final_state: object = None


def train(arch: str, *, steps: int = 50, global_batch: int = 8,
          seq_len: int = 128, reduced: bool = True, mesh=None,
          policy: MappingPolicy = MappingPolicy.AUTO,
          remat: str = "none", lr: float = 3e-3,
          ckpt_dir: Optional[str] = None, save_every: int = 20,
          fail_at: tuple[int, ...] = (), log_every: int = 10,
          compress_grads: bool = False, seed: int = 0,
          verbose: bool = True) -> TrainRun:
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    if mesh is None:
        mesh = make_local_mesh(1, 1)
    shape = ShapeConfig("cli", seq_len, global_batch, "train")
    plan = shd.resolve_plan(cfg, mesh, shape)
    mb = resolve_microbatches(cfg, shape, plan, policy=policy)
    step_cfg = StepConfig(remat=remat, microbatches=mb.num_microbatches,
                          compress_grads=compress_grads)
    opt_cfg = AdamWConfig(peak_lr=lr, warmup_steps=max(steps // 10, 1),
                          total_steps=steps)
    train_step = jax.jit(make_train_step(model, opt_cfg, plan, step_cfg),
                         donate_argnums=(0,))
    data_cfg = data_config_for(cfg, seq_len, global_batch, seed=seed)

    ckpt = Checkpointer(ckpt_dir, keep=2) if ckpt_dir else None
    injector = FailureInjector(fail_at)
    monitor = StragglerMonitor(n_hosts=max(plan.info.dp, 1))

    state = init_train_state(model, jax.random.key(seed), plan)
    step = 0
    losses, restarts = [], 0
    if verbose:
        print(f"[train] {cfg.name}: {model.param_count():,} params, "
              f"mesh={dict(mesh.shape)}, microbatches={mb.num_microbatches}, "
              f"policy={policy.value}")
    while step < steps:
        try:
            while step < steps:
                injector.check(step)
                batch = {k: jnp.asarray(v)
                         for k, v in make_batch(data_cfg, step, 0, 1).items()}
                if cfg.family == "vlm":
                    batch["patches"] = batch["patches"].astype(model.dtype)
                if cfg.family == "encdec":
                    batch["frames"] = batch["frames"].astype(model.dtype)
                t0 = time.perf_counter()
                state, metrics = train_step(state, batch)
                loss = float(metrics["loss"])
                monitor.record_step({0: time.perf_counter() - t0})
                losses.append(loss)
                if verbose and (step % log_every == 0 or step == steps - 1):
                    print(f"  step {step:5d} loss {loss:8.4f} "
                          f"lr {float(metrics['lr']):.2e} "
                          f"gnorm {float(metrics['grad_norm']):.3f}")
                step += 1
                if ckpt and step % save_every == 0:
                    ckpt.save(step, state)
            if ckpt:
                ckpt.wait()
        except SimulatedFailure as e:
            restarts += 1
            if verbose:
                print(f"  !! {e} — restarting from checkpoint")
            if ckpt is None or ckpt.latest_step() is None:
                state = init_train_state(model, jax.random.key(seed), plan)
                step = 0
            else:
                ckpt.wait()
                state, step = ckpt.restore(state)
    return TrainRun(losses=losses, restarts=restarts, steps=step,
                    final_state=state)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--full", action="store_true",
                    help="full config (default: reduced smoke config)")
    ap.add_argument("--mesh", default="local", choices=["local", "prod"])
    ap.add_argument("--policy", default="auto",
                    choices=["naive", "fixed", "auto"])
    ap.add_argument("--remat", default="none",
                    choices=["none", "dots", "full"])
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--fail-at", default="",
                    help="comma-separated steps for failure injection")
    ap.add_argument("--compress-grads", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()
    mesh = (make_production_mesh() if args.mesh == "prod"
            else make_local_mesh(1, 1))
    fail_at = tuple(int(x) for x in args.fail_at.split(",") if x)
    run = train(args.arch, steps=args.steps, global_batch=args.batch,
                seq_len=args.seq, reduced=not args.full, mesh=mesh,
                policy=MappingPolicy(args.policy), remat=args.remat,
                lr=args.lr, ckpt_dir=args.ckpt_dir, fail_at=fail_at,
                compress_grads=args.compress_grads)
    first = np.mean(run.losses[:5])
    last = np.mean(run.losses[-5:])
    print(f"[train] done: loss {first:.4f} -> {last:.4f} "
          f"({run.restarts} restarts)")


if __name__ == "__main__":
    main()
