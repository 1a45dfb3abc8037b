"""Training CLI — HeMT-DP end-to-end driver.

CPU-runnable on any `--arch` via `--reduced` (the same code path a TPU
fleet runs; slice heterogeneity comes from calibrated speed profiles).

  PYTHONPATH=src python -m repro.launch.train --arch granite-3-8b --reduced \
      --steps 20 --mode hemt --slices 1.0,0.4 --ckpt /tmp/ckpt
"""
from __future__ import annotations

import argparse
import dataclasses
import json
from typing import Sequence, Tuple

import jax

from repro.configs import ARCH_IDS, ArchBundle, ModelConfig, get_bundle, get_reduced
from repro.checkpoint import CheckpointManager
from repro.launch.compile_cache import enable_compile_cache
from repro.runtime import telemetry
from repro.runtime.hemt_driver import HeMTTrainer, SliceSpec
from repro.runtime.train_loop import TrainState, train_state_init


def build_trainer(cfg: ModelConfig, bundle: ArchBundle, *,
                  speeds: Sequence[float], grain_batch: int,
                  global_batch: int, seq_len: int, steps: int, lr: float,
                  mode: str = "hemt", seed: int = 0,
                  ) -> Tuple[HeMTTrainer, TrainState]:
    """The trainer and its initial state: one slice per relative speed,
    a warmup-cosine schedule sized to ``steps``."""
    bundle = bundle.replace(
        model=cfg,
        train=dataclasses.replace(bundle.train, lr=lr,
                                  total_steps=max(steps, 10),
                                  warmup_steps=max(steps // 10, 1)))
    slices = [SliceSpec(f"slice{i}", [(0.0, v)], grain_overhead=0.05)
              for i, v in enumerate(speeds)]
    trainer = HeMTTrainer(cfg, bundle, slices, grain_batch=grain_batch,
                          global_batch=global_batch, seq_len=seq_len,
                          mode=mode, seed=seed)
    return trainer, train_state_init(jax.random.PRNGKey(seed), cfg, bundle)


def step_times(step: int) -> Tuple[float, float]:
    """Measured milliseconds of training step ``step``, from its spans: the
    whole step, and the host's part of it (the step less its wait for the
    device)."""
    recs = [r for r in telemetry.records()[-64:] if r.step == step]
    ms = lambda name: sum(r.t1_ns - r.t0_ns for r in recs if r.name == name) / 1e6
    step_ms = ms("repro.train.step")
    return step_ms, step_ms - ms("repro.train.wait")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-8b", choices=ARCH_IDS)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--mode", default="hemt",
                    choices=["hemt", "homt", "static-even"])
    ap.add_argument("--slices", default="1.0,0.4",
                    help="comma-separated relative slice speeds")
    ap.add_argument("--grain-batch", type=int, default=2)
    ap.add_argument("--global-batch", type=int, default=16)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    enable_compile_cache()
    trainer, state = build_trainer(
        get_reduced(args.arch), get_bundle(args.arch),
        speeds=[float(s) for s in args.slices.split(",")],
        grain_batch=args.grain_batch, global_batch=args.global_batch,
        seq_len=args.seq_len, steps=args.steps, lr=args.lr, mode=args.mode,
        seed=args.seed)

    mgr = CheckpointManager(args.ckpt) if args.ckpt else None
    start = 0
    if mgr is not None:
        restored = mgr.restore_latest(state)
        if restored is not None:
            start, state, _ = restored
            print(f"resumed from step {start}")

    for _ in range(args.steps - start):
        state, rep = trainer.run_step(state)
        step_ms, host_ms = step_times(rep.step)
        print(json.dumps({
            "step": rep.step, "loss": round(rep.loss, 4),
            "makespan_s": round(rep.makespan, 2),
            "idle_s": round(rep.idle_time, 2),
            "step_ms": round(step_ms, 3), "host_ms": round(host_ms, 3),
            "grains": rep.grain_counts}), flush=True)
        if mgr is not None and (rep.step + 1) % args.ckpt_every == 0:
            mgr.save_async(rep.step + 1, state)
    if mgr is not None:
        mgr.wait()
        mgr.save(args.steps, state)
    print(f"total fleet time {trainer.total_time():.1f}s  "
          f"mean barrier idle {trainer.mean_idle():.2f}s  mode={args.mode}")
    print(json.dumps({"telemetry": telemetry.summary()}), flush=True)


if __name__ == "__main__":
    main()
