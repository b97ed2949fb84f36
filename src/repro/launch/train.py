"""Training launcher.

Examples:
  # tiny-config local run (any machine):
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --reduced \
      --steps 50 --batch 4 --seq 64

  # production pod (on real TPU hardware; the mesh comes up from the
  # runtime's device set — same code path the dry-run proves out):
  PYTHONPATH=src python -m repro.launch.train --arch deepseek-v2-236b \
      --steps 1000 --batch 256 --seq 4096 --mesh production
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import get_arch
from repro.data.pipeline import DataConfig
from repro.dist.act_sharding import use_mesh_rules
from repro.ft.elastic import make_mesh_for
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_production_mesh
from repro.optim import AdamWConfig
from repro.train import TrainConfig, Trainer


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--mesh", default="auto",
                    choices=["auto", "production", "multi_pod"])
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.mesh == "production":
        mesh = make_production_mesh()
    elif args.mesh == "multi_pod":
        mesh = make_production_mesh(multi_pod=True)
    else:
        mesh = make_mesh_for(jax.devices())
    print(f"arch={cfg.name} devices={len(jax.devices())} "
          f"mesh={dict(mesh.shape)}")
    dcfg = DataConfig(seq_len=args.seq, global_batch=args.batch,
                      vocab=cfg.vocab,
                      src_len=128 if cfg.family == "encdec" else 0)
    tcfg = TrainConfig(
        opt=AdamWConfig(lr=args.lr, total_steps=args.steps),
        microbatches=args.microbatches,
        compress_dp_grads=args.compress_grads)
    trainer = Trainer(cfg, tcfg, dcfg, ckpt_dir=args.ckpt_dir)
    with use_mesh_rules(mesh):
        params, state, history = trainer.run(args.steps)
    losses = [h["loss"] for h in history]
    print(f"done: loss {losses[0]:.4f} -> {losses[-1]:.4f} "
          f"(mean step {np.mean([h['step_time_s'] for h in history[1:]]) * 1e3:.0f} ms)")


if __name__ == "__main__":
    main()
