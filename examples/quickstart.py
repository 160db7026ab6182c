"""Quickstart: train a small model end-to-end with OFU monitoring,
atomic checkpointing, and crash recovery — the full §VI loop on CPU.

  PYTHONPATH=src python examples/quickstart.py [--steps 60] [--arch qwen3-4b]

The default runs the reduced same-family config of the chosen architecture.
On a real v5e pod, drop --smoke-scale and point --arch at any of the ten
assigned architectures (see src/repro/configs/).

Fleet engine quickstart (vectorized telemetry, repro.fleet.engine):
simulate thousands of devices x hours of 30 s scrapes in well under a
second, then roll them up into streaming per-job/per-precision/fleet
OFU percentiles:

    from repro.fleet import JobSpec, StreamingRollup, simulate_fleet

    specs = [JobSpec(f"job{i}", "granite-3-2b", chips=1000,
                     true_duty=0.35, duration_s=3600) for i in range(4)]
    roll = StreamingRollup(bucket_s=300)
    for tel in simulate_fleet(specs, max_devices=1000):
        roll.add_job(tel)
    print(roll.summary())                    # fleet-wide weighted OFU
    series = roll.job_ofu("job0")            # feed to detect_regressions
    p50 = roll.fleet_stats().percentiles[50]  # bucketed fleet median

`simulate_fleet(..., engine="scalar")` selects the per-device reference
backend instead; `benchmarks/fleet_engine.py` measures the gap (~45x on
a laptop core, ~15M simulated device-seconds per wall-second).  See
examples/fleet_monitoring.py for the full §V/§VI monitoring loop.
"""
import argparse
import json
import sys

sys.path.insert(0, "src")

from repro.configs.base import ShapeSpec, get_config
from repro.core.peaks import TPU_V5E
from repro.flops.accounting import step_flops
from repro.optim import adamw
from repro.train.trainer import TrainConfig, Trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b")
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_quickstart")
    args = ap.parse_args()

    cfg = get_config(args.arch).smoke()
    shape = ShapeSpec("quickstart", args.seq, args.batch, "train")
    print(f"training {cfg.name} ({cfg.family}) seq={args.seq} "
          f"batch={args.batch} for {args.steps} steps")

    trainer = Trainer(
        cfg, shape,
        opt_cfg=adamw.OptConfig(peak_lr=1e-3, warmup_steps=5,
                                decay_steps=args.steps),
        train_cfg=TrainConfig(total_steps=args.steps, ckpt_every=10,
                              ckpt_dir=args.ckpt_dir, log_every=5,
                              chip=TPU_V5E),
        flops_per_step=step_flops(cfg, shape, executed=True).total)
    out = trainer.run()

    if out["final_loss"] is None:
        print(f"checkpoint at step {out['final_step']} already >= "
              f"--steps {args.steps}: nothing to do (delete "
              f"{args.ckpt_dir} or raise --steps to continue training).")
        return
    print(json.dumps(out["metrics"][-3:], indent=1, default=float))
    print(f"final loss {out['final_loss']:.3f} after {out['final_step']} "
          f"steps; OFU per step logged via the simulated counter backend.")
    print("kill it mid-run and re-run: it resumes from the atomic "
          "checkpoint with an identical data stream.")


if __name__ == "__main__":
    main()
