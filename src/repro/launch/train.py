"""Training driver.

  PYTHONPATH=src python -m repro.launch.train --arch granite-3-2b \
      --smoke --steps 50 --ckpt-dir /tmp/ck

--smoke runs the reduced same-family config on CPU; the full config is the
production path (requires the real mesh).  Either way the loop exercises
checkpoint/restart, the deterministic data stream, and OFU monitoring.
"""
from __future__ import annotations

import argparse
import json

from repro.configs.base import SHAPES, ShapeSpec, get_config
from repro.core.peaks import TPU_V5E
from repro.flops.accounting import step_flops
from repro.launch.cache import enable_compile_cache
from repro.optim import adamw
from repro.train.trainer import TrainConfig, Trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=10)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
        shape = ShapeSpec("smoke", args.seq, args.batch, "train")
    else:
        shape = SHAPES[args.shape]

    fl = step_flops(cfg, shape, executed=True).total
    trainer = Trainer(
        cfg, shape,
        opt_cfg=adamw.OptConfig(warmup_steps=5, decay_steps=args.steps),
        train_cfg=TrainConfig(total_steps=args.steps,
                              ckpt_every=args.ckpt_every,
                              ckpt_dir=args.ckpt_dir,
                              # --smoke models a v5e's counters anywhere;
                              # the full config reads the device's kind
                              chip=TPU_V5E if args.smoke else None),
        flops_per_step=fl)
    out = trainer.run()
    print(json.dumps(out, indent=1, default=float))


if __name__ == "__main__":
    main()
