"""Training driver: data pipeline → pjit train step → checkpoints,
with fault tolerance (auto-resume, watchdog) and optional QAT.

Runs real training for smoke/small configs on CPU and is the same code
path the dry-run lowers for the production mesh. Examples:

  PYTHONPATH=src python -m repro.launch.train --arch llama3_8b --smoke \\
      --steps 100 --batch 8 --seq 128
  PYTHONPATH=src python -m repro.launch.train --arch llama3_8b --smoke \\
      --steps 100 --qat-weight-bits 4 --qat-act-bits 8 --resume
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Optional

import jax
import numpy as np

from repro.configs import ShapeSpec, get_config, smoke_config
from repro.checkpoint.checkpointer import Checkpointer
from repro.data.synthetic import LMStreamConfig, lm_batches
from repro.launch.fault import Watchdog
from repro.launch.sharding import ShardOptions
from repro.launch.steps import TrainState, build_train_step, uniform_levels
from repro.models import init_params
from repro.optim.adamw import AdamWConfig, init_adam
from repro.utils.compile_cache import use_compile_cache
from repro.utils.logging import get_logger

log = get_logger("repro.train")


def train(arch: str, smoke: bool, steps: int, batch: int, seq: int,
          ckpt_dir: Optional[str], resume: bool, ckpt_every: int,
          qat_weight_bits: Optional[int], qat_act_bits: Optional[int],
          watchdog_s: Optional[float], lr: float = 3e-3,
          log_every: int = 10) -> dict:
    cfg = smoke_config(arch) if smoke else get_config(arch)
    cfg = dataclasses.replace(cfg, remat=False)  # small models: speed
    shape = ShapeSpec("cli", seq, batch, "train")
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    opts = ShardOptions(seq_parallel=False, zero1=False)

    qat = None
    if qat_weight_bits is not None:
        qat = uniform_levels(cfg, qat_weight_bits, qat_act_bits)

    adam = AdamWConfig(lr=lr, warmup_steps=min(20, steps // 5),
                       total_steps=steps)
    build = build_train_step(cfg, shape, mesh, opts, adam=adam, qat=qat)

    params = init_params(cfg, jax.random.key(0))
    state = TrainState(params, init_adam(params))
    start_step = 0

    ckpt = Checkpointer(ckpt_dir) if ckpt_dir else None
    if ckpt and resume:
        latest = ckpt.latest_step()
        if latest is not None:
            state = ckpt.restore(latest, state)
            start_step = latest
            log.info("resumed from step %d", latest)

    stream_cfg = LMStreamConfig(
        vocab_size=cfg.vocab_size, seq_len=seq, global_batch=batch,
        num_codebooks=cfg.num_codebooks if cfg.family == "audio" else 0,
        img_tokens=cfg.img_tokens if cfg.family == "vlm" else 0,
        d_model=cfg.d_model, seed=0)
    stream = lm_batches(stream_cfg)
    # fast-forward the stream deterministically on resume
    for _ in range(start_step):
        next(stream)

    wd = Watchdog(watchdog_s) if watchdog_s else None
    losses = []
    t0 = time.time()
    for step in range(start_step, steps):
        batch_np = next(stream)
        if wd:
            wd.arm()
        state, metrics = build.fn(state, batch_np)
        loss = float(metrics["loss"])
        if wd:
            wd.disarm()
        losses.append(loss)
        if step % log_every == 0 or step == steps - 1:
            log.info("step %d loss %.4f lr %.2e gnorm %.2f", step, loss,
                     float(metrics["lr"]), float(metrics["grad_norm"]))
        if ckpt and ckpt_every and (step + 1) % ckpt_every == 0:
            ckpt.save(step + 1, state, blocking=False)
    if ckpt:
        ckpt.save(steps, state, blocking=True)
        ckpt.wait()
    if wd:
        wd.stop()
    dt = time.time() - t0
    log.info("trained %d steps in %.1fs (%.3f s/step); final loss %.4f",
             steps - start_step, dt, dt / max(steps - start_step, 1), losses[-1])
    return {"final_loss": losses[-1], "losses": losses, "steps": steps}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--qat-weight-bits", type=int, default=None)
    ap.add_argument("--qat-act-bits", type=int, default=None)
    ap.add_argument("--watchdog-s", type=float, default=None)
    ap.add_argument("--lr", type=float, default=3e-3)
    args = ap.parse_args()
    use_compile_cache()
    train(args.arch, args.smoke, args.steps, args.batch, args.seq,
          args.ckpt_dir, args.resume, args.ckpt_every,
          args.qat_weight_bits, args.qat_act_bits, args.watchdog_s, args.lr)


if __name__ == "__main__":
    main()
