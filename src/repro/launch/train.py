"""Training driver.

Runs real training on this host's default device (reduced/smoke configs
on a CPU) or lowers the production step for a mesh (``--dryrun``-style use
should go through repro.launch.dryrun instead).

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch starcoder2-7b --smoke \
      --adapter shira-wm --steps 100
  PYTHONPATH=src python -m repro.launch.train --preset 100m --steps 300
"""
from __future__ import annotations

import argparse
import json
import os

from repro.configs import (AdapterConfig, ModelConfig, RunConfig, TrainConfig,
                           get_config, get_smoke_config)
from repro.configs.base import ShapeSpec
from repro.launch.compile_cache import enable_compile_cache
from repro.runtime import Trainer
from repro.runtime.trainer import TrainerConfig

PRESET_100M = ModelConfig(
    name="dense-100m", family="dense", num_layers=12, d_model=768,
    num_heads=12, num_kv_heads=12, d_ff=3072, vocab_size=32000,
    tie_embeddings=True,
)


def parse_adapter(spec: str) -> AdapterConfig:
    """'none' | 'lora' | 'dora' | 'shira-<mask>' | 'shira-<mask>-hook'."""
    if spec == "none":
        return AdapterConfig(kind="none")
    if spec in ("lora", "dora"):
        return AdapterConfig(kind=spec, rank=16)
    if spec.startswith("shira-dora"):
        return AdapterConfig(kind="shira-dora", mask="wm")
    if spec.startswith("shira"):
        parts = spec.split("-")
        mask = parts[1] if len(parts) > 1 else "wm"
        hook = len(parts) > 2 and parts[2] == "hook"
        return AdapterConfig(kind="shira", mask=mask, packed=not hook)
    raise ValueError(spec)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--preset", default=None, choices=[None, "100m"])
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config for --arch")
    ap.add_argument("--adapter", default="none")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--task", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--out", default=None, help="write loss history JSON")
    args = ap.parse_args()
    enable_compile_cache()

    if args.preset == "100m":
        cfg = PRESET_100M
    elif args.arch:
        cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    else:
        raise SystemExit("need --arch or --preset")

    shape = ShapeSpec("cli", args.seq, args.batch, "train")
    run = RunConfig(model=cfg, shape=shape, adapter=parse_adapter(args.adapter),
                    train=TrainConfig(learning_rate=args.lr, seed=args.seed,
                                      total_steps=args.steps,
                                      warmup_steps=max(args.steps // 20, 1)))
    trainer = Trainer(run, TrainerConfig(ckpt_dir=args.ckpt_dir,
                                         log_every=max(args.steps // 20, 1)))
    from repro.data import TaskSpec, batch_iterator
    batches = batch_iterator(cfg, shape, seed=args.seed,
                             task=TaskSpec(task_id=args.task))
    out = trainer.fit(args.steps, batches=batches)
    losses = [h["loss"] for h in out["history"]]
    print(f"[train] {cfg.name} adapter={args.adapter} "
          f"loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"arch": cfg.name, "adapter": args.adapter,
                       "losses": losses}, f)


if __name__ == "__main__":
    main()
