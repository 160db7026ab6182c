"""Chip smoke: the main path once on a TPU, through its normal entry points.

    python chip_smoke.py             # one chip: fleet engine + device
                                     # ingest, detectors, a train step
    python chip_smoke.py --chips 4   # four chips: the mesh-sharded engine
                                     # and ingest against one chip, only

Everything runs in this one process, which holds the chip.  Each phase
checks its results against the repo's own reference and any failure
exits non-zero; nothing falls back to the CPU.  Timings printed on the
way are one-off smoke timings of a single run, not benchmark numbers.
The last line of stdout is one JSON object:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import tempfile
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

#: the fleet slot of benchmarks/fleet_engine.py, at 1M devices x 1 h
N_DEVICES = 1_000_000
DURATION_S = 3600.0
INTERVAL_S = 30.0
BUCKET_S = 300.0
#: Granite-3.0-2B cut to 8 of its 40 layers, batch 4 x seq 2048
TRAIN_LAYERS, TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 8, 4, 2048, 4
#: relative tolerance of the first bf16 step's loss vs an f32 forward
LOSS_RTOL = 0.01


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def timed(fn, *args, **kw):
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    return out, time.perf_counter() - t0


def device_bytes(dev) -> str:
    stats = dev.memory_stats() or {}
    return (f"in_use={stats.get('bytes_in_use', 'n/a')} "
            f"peak={stats.get('peak_bytes_in_use', 'n/a')}")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def phase_device(jax, n_chips: int):
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, but JAX's first device "
                         f"is {dev.platform!r} ({dev.device_kind}); it "
                         f"does not run on another backend")
    check(len(devs) >= n_chips,
          f"--chips {n_chips} needs {n_chips} devices, JAX sees {len(devs)}")
    from repro.core.peaks import device_chip
    from repro.launch.cache import enable_compile_cache
    cache = enable_compile_cache()
    chip = device_chip(dev)
    say("device", f"platform={dev.platform} kind={dev.device_kind!r} "
        f"count={len(devs)} spec={chip.name} "
        f"peak_bf16_tflops={chip.peak_tflops('bf16')} "
        f"hbm_gbps={chip.hbm_gbps} compile_cache={cache}")
    from libtpu.sdk import tpumonitoring
    say("device", "libtpu tpumonitoring metrics: "
        f"{tpumonitoring.list_supported_metrics()}")
    return devs, chip


def generate(jax, mesh):
    """The benchmarks/fleet_engine.py slot through simulate_jobs_jax,
    waited on."""
    from benchmarks.fleet_engine import EVENTS, PROFILE
    from repro.fleet.engine import JobSlot
    from repro.fleet.engine_jax import simulate_jobs_jax
    slot = JobSlot(PROFILE, DURATION_S, INTERVAL_S, events=EVENTS,
                   stragglers=np.ones(N_DEVICES))
    (g,) = simulate_jobs_jax([slot], seed=0, mesh=mesh)
    jax.block_until_ready((g.tpa, g.clock_mhz))
    return g


def fold(grid, chip):
    """add_grid one grid into a fresh rollup; returns (rollup, secs, the
    routes the fused ingest took)."""
    from repro.fleet.streaming import StreamingRollup
    from repro.kernels import fleet_hist
    before = fleet_hist.ROUTES.copy()
    roll = StreamingRollup(bucket_s=BUCKET_S)
    _, secs = timed(roll.add_grid, "smoke", grid, chip=chip,
                    chips=N_DEVICES)
    return roll, secs, fleet_hist.ROUTES - before


def job_hist(roll):
    return roll._hists[("job", "smoke")], roll._sums[("job", "smoke")]


def flagged(regressions) -> dict:
    """{job: [(start bucket, end bucket)]} of a scan_rollup result."""
    return {j: [(r.start_idx, r.end_idx) for r in rs]
            for j, rs in regressions.items()}


def phase_fleet(jax, devs, chip) -> None:
    from repro.fleet.regression import scan_rollup
    from repro.fleet.streaming import StreamingRollup
    from repro.kernels.fleet_hist import bucket_hist_ref
    from repro.telemetry.scrape import DeviceGrid

    g, t_first = timed(generate, jax, "auto")
    del g
    g, t_steady = timed(generate, jax, "auto")
    say("fleet", f"simulate_jobs_jax {g.tpa.shape} f32 x2 "
        f"({2 * g.tpa.nbytes / 1e9:.3f} GB on device): first call "
        f"(compile + run) {t_first:.3f} s, second {t_steady:.3f} s "
        f"[one-off smoke timing]")

    roll, t_fold, routes = fold(g, chip)
    check(routes == {"pallas": 1},
          f"add_grid took routes {dict(routes)}, expected the compiled "
          f"pallas kernel once")
    _, t_fold2, _ = fold(g, chip)
    say("fleet", f"add_grid via compiled pallas kernel: first "
        f"{t_fold:.3f} s, second {t_fold2:.3f} s [one-off smoke timing]; "
        f"device {device_bytes(devs[0])}")

    tpa, clock = np.asarray(g.tpa), np.asarray(g.clock_mhz)
    del g
    t_s = DeviceGrid(INTERVAL_S, tpa[:1], clock[:1]).times_s
    col = np.maximum(np.ceil(t_s / BUCKET_S).astype(int) - 1, 0)
    (h_ref, s_ref), t_ref = timed(
        bucket_hist_ref, tpa, clock, inv_fmax=1.0 / chip.f_max_mhz,
        edges=roll.edges, col_bucket=col, n_buckets=int(col[-1]) + 1)
    h_dev, s_dev = job_hist(roll)
    check(h_dev.shape == h_ref.shape, f"{h_dev.shape} != {h_ref.shape}")
    check(np.array_equal(h_dev, h_ref),
          f"device histogram differs from bucket_hist_ref in "
          f"{np.count_nonzero(h_dev != h_ref)} cells")
    rel = float(np.max(np.abs(s_dev / s_ref - 1)))
    check(rel <= 1e-5, f"bucket sums differ from the reference by {rel:.3g}")
    say("fleet", f"histogram {h_dev.shape} bitwise equal to "
        f"bucket_hist_ref ({int(h_ref.sum())} samples); sums max rel "
        f"diff {rel:.3g}; reference took {t_ref:.3f} s on the host")

    host = StreamingRollup(bucket_s=BUCKET_S)
    _, t_host = timed(host.add_grid, "smoke", DeviceGrid(INTERVAL_S, tpa,
                                                         clock),
                      chip=chip, chips=N_DEVICES)
    moved = int(np.abs(job_hist(host)[0] - h_dev).sum()) // 2
    kw = dict(window=2, min_duration=1)      # 12 buckets: 2 of baseline
    regs_dev = scan_rollup(roll, **kw)
    spans, spans_host = flagged(regs_dev), flagged(scan_rollup(host, **kw))
    check(spans == spans_host,
          f"device rollup flags {spans}, host rollup flags {spans_host}")
    check(spans == {"smoke": [(2, 4)]},
          f"expected the 600-1200 s slowdown in buckets [2, 4), got {spans}")
    say("fleet", f"scan_rollup flags {spans} on both the device and the "
        f"host rollup (factor {regs_dev['smoke'][0].factor:.3f}); host "
        f"add_grid took {t_host:.3f} s; {moved} of {tpa.size} samples "
        f"fall in a neighbouring bin on the host path (f64 OFU)")


def phase_detectors() -> None:
    from repro.kernels import fleet_hist
    from repro.scenarios.scorecard import check_floors, run_scorecard
    before = fleet_hist.ROUTES.copy()
    doc, secs = timed(run_scorecard, engine="jax")
    routes = fleet_hist.ROUTES - before
    check(routes.get("pallas", 0) > 0 and set(routes) == {"pallas"},
          f"scorecard ingest took routes {dict(routes)}")
    violations = check_floors(doc)
    check(not violations, f"scorecard floor violations: {violations}")
    say("detectors", f"run_scorecard(engine='jax'): "
        f"{len(doc['scenarios'])} scenarios, {routes['pallas']} compiled "
        f"kernel folds, no floor violations, {secs:.3f} s "
        f"[one-off smoke timing]")


def phase_trainer(jax, devs, chip) -> None:
    import jax.numpy as jnp

    from repro.configs.base import ShapeSpec, get_config
    from repro.data.pipeline import synthetic_batch
    from repro.flops.accounting import step_flops
    from repro.models import api as models
    from repro.optim import adamw
    from repro.train.steps import loss_fn
    from repro.train.trainer import TrainConfig, Trainer

    full = get_config("granite-3-2b")
    cfg = dataclasses.replace(full, num_layers=TRAIN_LAYERS)
    shape = ShapeSpec("chip_smoke", TRAIN_SEQ, TRAIN_BATCH, "train")
    print(f"reduced: {cfg.name} num_layers {full.num_layers} -> "
          f"{cfg.num_layers} (widths as published: d_model {cfg.d_model}, "
          f"heads {cfg.num_heads}/{cfg.num_kv_heads}, d_ff {cfg.d_ff}, "
          f"vocab {cfg.vocab_size}); batch {TRAIN_BATCH} x seq "
          f"{TRAIN_SEQ}", flush=True)

    # f32 reference: the trainer's own initial parameters and first batch
    seed = TrainConfig().seed
    params = jax.tree.map(lambda x: x.astype(jnp.float32),
                          models.init_params(cfg, jax.random.key(seed)))
    batch = {k: jnp.asarray(v) for k, v in
             synthetic_batch(cfg, shape, 0, seed=seed).items()}
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    with jax.default_matmul_precision("highest"):     # true f32 matmuls
        ref, t_ref = timed(lambda: float(jax.jit(
            lambda p, b: loss_fn(cfg32, p, b)[0])(params, batch)))
    del params, batch
    say("trainer", f"f32 reference loss {ref:.6f} ({t_ref:.3f} s incl. "
        f"compile)")

    flops = step_flops(cfg, shape, executed=True).total
    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as ckpt:
        trainer = Trainer(
            cfg, shape,
            opt_cfg=adamw.OptConfig(warmup_steps=2,
                                    decay_steps=TRAIN_STEPS),
            train_cfg=TrainConfig(total_steps=TRAIN_STEPS,
                                  ckpt_every=TRAIN_STEPS, ckpt_dir=ckpt,
                                  log_every=1),
            flops_per_step=flops)
        check(trainer.chip is chip, "Trainer did not take the device's spec")
        out, t_run = timed(trainer.run)
    losses = [m["loss"] for m in out["metrics"]]
    times = [m["step_time_s"] for m in out["metrics"]]
    check(out["final_step"] == TRAIN_STEPS and len(losses) == TRAIN_STEPS,
          f"trainer stopped at {out['final_step']} with {len(losses)} logs")
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    rel = abs(losses[0] - ref) / abs(ref)
    check(rel <= LOSS_RTOL, f"first-step loss {losses[0]} vs f32 {ref}: "
          f"rel {rel:.3g} > {LOSS_RTOL}")
    say("trainer", f"{TRAIN_STEPS} steps, losses {losses}; step 1 vs f32 "
        f"rel diff {rel:.3g} (tolerance {LOSS_RTOL}); step times {times} "
        f"s (step 1 includes compile); run() incl. checkpoint "
        f"{t_run:.3f} s [one-off smoke timing]; device "
        f"{device_bytes(devs[0])}")


def phase_mesh(jax, devs, chip) -> None:
    """The same slot sharded over a 4-chip mesh against one chip."""
    mesh = jax.sharding.Mesh(np.array(devs[:4]), ("devices",))
    gs, t_gen = timed(generate, jax, mesh)
    rows = {s.device.id: s.data.shape[0] for s in gs.tpa.addressable_shards}
    say("mesh", f"sharded grid {gs.tpa.shape}: rows per chip {rows}")
    check(sorted(rows.values()) == [N_DEVICES // 4] * 4,
          f"rows per chip {rows}, expected a quarter each")
    roll_s, t_s, routes = fold(gs, chip)
    check(routes == {"pallas": 1}, f"sharded add_grid took {dict(routes)}")

    g1 = generate(jax, None)
    check(g1.tpa.devices() == {devs[0]},
          f"mesh=None grid lives on {g1.tpa.devices()}")
    roll_1, t_1, routes = fold(g1, chip)
    check(routes == {"pallas": 1}, f"one-chip add_grid took {dict(routes)}")
    same_grid = (np.array_equal(np.asarray(gs.tpa), np.asarray(g1.tpa))
                 and np.array_equal(np.asarray(gs.clock_mhz),
                                    np.asarray(g1.clock_mhz)))
    (h_s, s_s), (h_1, s_1) = job_hist(roll_s), job_hist(roll_1)
    check(np.array_equal(h_s, h_1),
          f"sharded histogram differs in {np.count_nonzero(h_s != h_1)} "
          f"cells")
    rel = float(np.max(np.abs(s_s / s_1 - 1)))
    check(rel <= 1e-5, f"sharded bucket sums differ by {rel:.3g}")
    say("mesh", f"histogram {h_s.shape} bitwise equal to the mesh=None "
        f"run on {devs[0]}; sums max rel diff {rel:.3g}; grids bitwise "
        f"equal: {same_grid}; generate (incl. compile) {t_gen:.3f} s, "
        f"fold sharded {t_s:.3f} s, one chip {t_1:.3f} s "
        f"[one-off smoke timing]")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh-sharded fleet phase")
    args = ap.parse_args()

    import jax
    devs, chip = phase_device(jax, args.chips)
    if args.chips == 4:
        phase_mesh(jax, devs, chip)
    else:
        phase_fleet(jax, devs, chip)
        phase_detectors()
        phase_trainer(jax, devs, chip)
    dev = devs[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
