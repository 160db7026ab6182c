"""Chip smoke: the main path once on a TPU, through its normal entry points.

    python chip_smoke.py             # one chip: the detector scorecard
                                     # on the jax engine and pallas ingest
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
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

#: one evented fleet slot, 1M devices x 1 h at 30 s scrapes
N_DEVICES = 1_000_000
DURATION_S = 3600.0
INTERVAL_S = 30.0
BUCKET_S = 300.0


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def timed(fn, *args, **kw):
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    return out, time.perf_counter() - t0


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
    """The slot through simulate_jobs_jax, waited on: duty 0.42, slowed
    2.5x from 600 s to 1200 s."""
    from repro.fleet.engine import JobSlot
    from repro.fleet.engine_jax import simulate_jobs_jax
    from repro.telemetry.counters import Event, StepProfile
    slot = JobSlot(StepProfile(0.84, 2.0), DURATION_S, INTERVAL_S,
                   events=[Event(600, 1200, slowdown=2.5)],
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
        phase_detectors()
    dev = devs[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
