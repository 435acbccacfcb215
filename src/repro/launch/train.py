"""Training driver: ``python -m repro.launch.train --arch llama3.2-1b
--smoke --steps 50``.

Fault tolerance built in:
  * resume-from-latest on start (``--ckpt-dir``), async atomic checkpoints
  * SIGTERM/SIGINT preemption handler: checkpoint synchronously, exit 143
    (cluster schedulers re-queue; restart resumes exactly)
  * NaN/Inf gradient skipping (inside the jitted step)
  * straggler watchdog: per-step wall-clock EMA; steps slower than
    ``--straggler-factor`` x EMA are logged (on a cluster, the hook point
    for drain/replace decisions)
  * elastic restart: checkpoints are mesh-agnostic; restarting on a
    different mesh re-shards automatically (see launch/elastic_demo.py)
"""
from __future__ import annotations

import argparse
import os
import signal
import sys
import time
from typing import Optional

import jax
import numpy as np

from ..configs import get_config, get_smoke, ARCH_IDS
from ..core.pcontext import ParallelCtx
from ..models.transformer import make_plan, init_params
from ..parallel.steps import build_train_step
from ..parallel import sharding as shd
from ..training.optimizer import adamw_init
from ..training.data import SyntheticLMData
from ..training import checkpoint as ckpt
from .mesh import make_test_mesh, make_production_mesh, make_ctx, tp_size


def run_training(arch: str, *, steps: int = 50, smoke: bool = True,
                 seq_len: int = 64, global_batch: int = 8,
                 microbatches: int = 2, ckpt_dir: Optional[str] = None,
                 ckpt_every: int = 25, base_lr: float = 1e-2,
                 mesh=None, ctx: Optional[ParallelCtx] = None,
                 grad_reduce: str = "rd", straggler_factor: float = 3.0,
                 log_every: int = 10, seed: int = 0):
    cfg = get_smoke(arch) if smoke else get_config(arch)
    if mesh is None:
        mesh = make_test_mesh((1, 1), ("data", "model"))
    if ctx is None:
        ctx = ParallelCtx(tp_fast=("model",), dp=("data",), fsdp=("data",),
                          ep=("model",), sp=("model",),
                          grad_reduce_strategy=grad_reduce)
    tp = tp_size(mesh, ctx)
    ap = make_plan(cfg, tp)
    built = build_train_step(ap, ctx, mesh, microbatches=microbatches,
                             base_lr=base_lr, warmup=5, total_steps=steps,
                             frame_embeds=cfg.family == "encdec",
                             patch_embeds=cfg.family == "vlm")
    step_fn = built.jit()

    params = init_params(jax.random.PRNGKey(seed), ap)
    opt = adamw_init(params)
    start_step = 0
    saver = None
    if ckpt_dir:
        saver = ckpt.AsyncCheckpointer(ckpt_dir)
        if ckpt.latest_step(ckpt_dir) is not None:
            start_step, state = ckpt.restore(
                ckpt_dir, {"params": params, "opt": opt})
            params, opt = state["params"], state["opt"]
            print(f"[train] resumed from step {start_step}")

    data = SyntheticLMData(cfg.vocab_size, seq_len, global_batch, seed=seed)
    rng = np.random.default_rng(seed)

    preempted = {"flag": False}

    def on_signal(signum, frame):
        preempted["flag"] = True

    old_handlers = {}
    for sig in (signal.SIGTERM, signal.SIGINT):
        old_handlers[sig] = signal.signal(sig, on_signal)

    history = []
    ema = None
    try:
        for step in range(start_step, steps):
            batch = data.batch(step)
            if cfg.family == "encdec":
                batch["frames"] = rng.standard_normal(
                    (global_batch, cfg.enc_seq, cfg.d_model)).astype(
                        np.float32)
            if cfg.family == "vlm":
                batch["patches"] = rng.standard_normal(
                    (global_batch, cfg.n_patches, cfg.d_model)).astype(
                        np.float32)
            t0 = time.perf_counter()
            params, opt, metrics = step_fn(params, opt, batch)
            loss = float(metrics["loss"])
            dt = time.perf_counter() - t0
            ema = dt if ema is None else 0.9 * ema + 0.1 * dt
            if dt > straggler_factor * ema and step > start_step + 3:
                print(f"[train] straggler: step {step} took {dt:.2f}s "
                      f"(ema {ema:.2f}s)")
            history.append({"step": step, "loss": loss,
                            "grad_norm": float(metrics["grad_norm"]),
                            "skipped": float(metrics["skipped"]),
                            "wall_s": dt})
            if step % log_every == 0 or step == steps - 1:
                print(f"[train] step {step:5d} loss {loss:.4f} "
                      f"gnorm {float(metrics['grad_norm']):.2f} "
                      f"lr {float(metrics['lr']):.2e} {dt*1e3:.0f}ms")
            if saver and (step + 1) % ckpt_every == 0:
                saver.save(step + 1, {"params": params, "opt": opt})
            if preempted["flag"]:
                print("[train] preemption signal: checkpointing + exit")
                if saver:
                    saver.wait()
                if ckpt_dir:
                    ckpt.save(ckpt_dir, step + 1,
                              {"params": params, "opt": opt})
                return {"history": history, "params": params, "opt": opt,
                        "preempted": True, "stopped_at": step + 1}
        if saver:
            saver.save(steps, {"params": params, "opt": opt})
            saver.wait()
    finally:
        for sig, h in old_handlers.items():
            signal.signal(sig, h)
    return {"history": history, "params": params, "opt": opt,
            "preempted": False, "stopped_at": steps}


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", choices=list(ARCH_IDS), required=True)
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--smoke", action="store_true", default=True)
    p.add_argument("--full", dest="smoke", action="store_false")
    p.add_argument("--seq-len", type=int, default=64)
    p.add_argument("--global-batch", type=int, default=8)
    p.add_argument("--microbatches", type=int, default=2)
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--ckpt-every", type=int, default=25)
    p.add_argument("--lr", type=float, default=1e-2)
    p.add_argument("--grad-reduce", default="rd",
                   choices=["flat", "rd"])
    args = p.parse_args(argv)
    out = run_training(args.arch, steps=args.steps, smoke=args.smoke,
                       seq_len=args.seq_len, global_batch=args.global_batch,
                       microbatches=args.microbatches,
                       ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                       base_lr=args.lr, grad_reduce=args.grad_reduce)
    print(f"[train] done: final loss "
          f"{out['history'][-1]['loss']:.4f}, preempted={out['preempted']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
