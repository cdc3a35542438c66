"""Training driver: real steps on whatever devices exist.

On the CPU it trains REDUCED configs (examples, smoke tests); on a TPU
the same driver takes the published configs (``--full``).
Integrates every substrate: sharded step (pjit), deterministic data
pipeline, checkpoint/restart, heartbeats + straggler log, optional
gradient compression, and optional Tally co-location (the training job
registers as a best-effort client so a serving job can share the devices).

    python -m repro.launch.train --arch mamba2-130m --reduced \
        --steps 200 --batch 8 --seq 128
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time
from pathlib import Path
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointConfig, CheckpointManager
from repro.configs.base import ShapeConfig, all_arch_names, get_config
from repro.data import DataConfig, build_pipeline
from repro.distributed.fault_tolerance import (HeartbeatMonitor,
                                               StragglerDetector)
from repro.distributed.sharding import use_mesh
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import make_train_step
from repro.models.transformer import build_model
from repro.optim.schedule import linear_warmup_cosine


def train(arch: str, *, steps: int = 100, batch: int = 8, seq: int = 128,
          reduced: bool = True, lr: float = 3e-3, ckpt_dir: Optional[str] = None,
          ckpt_every: int = 50, resume: bool = False, seed: int = 0,
          num_microbatches: int = 1, log_every: int = 10,
          model_parallel: int = 1,
          total_steps: Optional[int] = None,
          devices: Optional[Sequence] = None) -> Dict[str, Any]:
    """``total_steps`` fixes the LR-schedule horizon independently of this
    invocation's ``steps`` so a checkpoint-restart run matches a straight
    run exactly (defaults to ``steps``). ``devices`` restricts the mesh to
    a subset of this host's devices (default: all of them)."""
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    mesh = make_host_mesh(model_parallel, devices)
    model = build_model(cfg)
    shape = ShapeConfig("driver", seq, batch, "train")
    horizon = total_steps or steps
    sched = linear_warmup_cosine(max(horizon // 20, 1), horizon)

    with use_mesh(mesh):
        bundle = make_train_step(model, mesh, shape, schedule=sched,
                                 num_microbatches=num_microbatches, lr=lr)
        step_fn = jax.jit(bundle.fn,
                          in_shardings=bundle.in_shardings,
                          out_shardings=bundle.out_shardings,
                          donate_argnums=bundle.donate_argnums)

        params = model.init(jax.random.PRNGKey(seed))
        from repro.launch.steps import make_optimizer
        opt = make_optimizer(cfg, lr)
        opt_state = opt.init(params)

        start_step = 0
        mgr = None
        if ckpt_dir:
            mgr = CheckpointManager(CheckpointConfig(ckpt_dir))
            if resume and mgr.latest_step() is not None:
                start_step, (params, opt_state) = mgr.restore(
                    (params, opt_state))
                start_step += 1
                print(f"[train] resumed from step {start_step - 1}")
        # placed as the step returns them: unplaced arrays would make the
        # second step a cache miss and compile the step twice
        params, opt_state = jax.device_put((params, opt_state),
                                           bundle.in_shardings[:2])

        dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=seq,
                          global_batch=batch, seed=seed)
        _, it = build_pipeline(dcfg, start_step=start_step)

        hb = HeartbeatMonitor(timeout=60.0)
        straggle = StragglerDetector()
        losses = []
        t_start = time.time()
        try:
            for step in range(start_step, steps):
                got_step, host_batch = next(it)
                assert got_step == step, (got_step, step)
                dev_batch = {k: jnp.asarray(v) for k, v in
                             host_batch.items()}
                t0 = time.time()
                params, opt_state, metrics = step_fn(params, opt_state,
                                                     dev_batch)
                loss = float(metrics["loss"])
                dt = time.time() - t0
                hb.beat(0, time.time())
                straggle.record(0, dt)
                losses.append(loss)
                if step % log_every == 0 or step == steps - 1:
                    print(f"[train] step {step:5d} loss {loss:.4f} "
                          f"gnorm {float(metrics['grad_norm']):.3f} "
                          f"{dt * 1e3:.0f}ms", flush=True)
                if mgr and ckpt_every and (step + 1) % ckpt_every == 0:
                    mgr.save_async(step, (params, opt_state))
        finally:
            if hasattr(it, "close"):
                it.close()
            if mgr:
                mgr.wait()
        if mgr:
            mgr.save(steps - 1, (params, opt_state))
    wall = time.time() - t_start
    return {"arch": arch, "steps": steps, "first_loss": losses[0],
            "last_loss": losses[-1],
            "loss_drop": losses[0] - losses[-1],
            "wall_s": wall, "params": params, "losses": losses}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=all_arch_names(), required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--ckpt-dir")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--model-parallel", type=int, default=1)
    args = ap.parse_args(argv)
    enable_compile_cache()
    out = train(args.arch, steps=args.steps, batch=args.batch, seq=args.seq,
                reduced=args.reduced, lr=args.lr, ckpt_dir=args.ckpt_dir,
                ckpt_every=args.ckpt_every, resume=args.resume,
                num_microbatches=args.microbatches,
                model_parallel=args.model_parallel)
    print(json.dumps({k: v for k, v in out.items()
                      if k not in ("params", "losses")}, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
