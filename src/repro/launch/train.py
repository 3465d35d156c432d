"""Training launcher: EFTA-protected LM training with the full FT runtime
(async checkpoints, straggler monitor, fault-rate escalation, resume).

CPU-scale demo:
  PYTHONPATH=src python -m repro.launch.train --arch gpt2-smoke \
      --steps 60 --batch 8 --seq 64 --ckpt-dir /tmp/ckpt --ckpt-every 20

Production notes: on a pod this runs under the 16x16 / 2x16x16 mesh from
launch/mesh.py (pass --mesh pod|multipod).
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.data import make_pipeline
from repro.ft_runtime import (AsyncCheckpointer, FaultRateMonitor,
                              StragglerMonitor, latest_step, restore)
from repro.models import build_model
from repro.optim import AdamW, warmup_cosine
from repro.train import TrainState, init_state, make_train_step
from repro.utils import get_logger
from repro.utils.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gpt2-smoke")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--mesh", choices=["none", "pod", "multipod"],
                    default="none")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    log = get_logger("train")
    enable_compile_cache()

    cfg = get_config(args.arch)
    model = build_model(cfg)
    mesh = None
    if args.mesh != "none":
        from repro.launch.mesh import make_production_mesh
        mesh = make_production_mesh(multi_pod=args.mesh == "multipod")

    opt = AdamW(lr=warmup_cosine(args.lr, warmup=10, total=args.steps))
    state = init_state(model, opt, jax.random.PRNGKey(args.seed))
    start_step = 0
    if args.resume and args.ckpt_dir:
        ck = latest_step(args.ckpt_dir)
        if ck is not None:
            state, start_step, _ = restore(ck, state)
            log.info("resumed from %s at step %d", ck, start_step)

    step_fn = jax.jit(make_train_step(model, opt, mesh=mesh,
                                      microbatches=args.microbatches),
                      donate_argnums=(0,))
    data = make_pipeline(cfg, global_batch=args.batch, seq_len=args.seq,
                         seed=args.seed)
    ckpt = AsyncCheckpointer()
    straggler = StragglerMonitor()
    faults = FaultRateMonitor()

    for step in range(start_step, args.steps):
        straggler.step_start()
        batch = {k: jnp.asarray(v) for k, v in data.batch(step).items()}
        state, metrics = step_fn(state, batch)
        jax.block_until_ready(metrics["loss"])
        v = straggler.step_end()
        status = faults.observe(int(np.sum(np.asarray(
            metrics["ft_detected"]))))
        if status == "cordon":
            log.warning("sustained EFTA fault rate: cordon + elastic restart "
                        "advised (see ft_runtime.elastic)")
        if v.is_straggler:
            log.warning("straggler step %d: %.3fs (median %.3fs)", step,
                        v.step_time, v.median)
        if step % args.log_every == 0 or step == args.steps - 1:
            log.info("step %4d loss %.4f ce %.4f ft=%s %.3fs/step", step,
                     float(metrics["loss"]), float(metrics["ce"]),
                     np.asarray(metrics["ft_detected"]).tolist(), v.step_time)
        if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
            ckpt.save_async(os.path.join(args.ckpt_dir, f"step_{step+1}"),
                            state, step=step + 1)
    ckpt.wait()
    log.info("done: %d steps", args.steps - start_step)


if __name__ == "__main__":
    main()
