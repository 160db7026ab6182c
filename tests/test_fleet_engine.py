"""Vectorized fleet engine: statistical equivalence against the scalar
reference backend (and of the fused multi-job grid against the per-job
loop), streaming-rollup correctness, and the fleet-scale performance
contract (1,000 devices x 1 hour in seconds, not minutes)."""
import hashlib
import time

import numpy as np
import pytest

from repro.core.ofu import hist_percentile, hist_percentile_grid, ofu_series
from repro.core.peaks import TPU_V6E_LIKE
from repro.fleet import (JobSpec, StreamingRollup, simulate_devices,
                         simulate_fleet, simulate_job)
from repro.fleet.engine import EngineParams, JobSlot, simulate_jobs_fused
from repro.fleet.jobs import _job_draws, build_profile
from repro.fleet.regression import detect_regressions
from repro.fleet.streaming import precision_label
from repro.telemetry import Event, SimulatedDeviceBackend, StepProfile, scrape


def _profile(duty=0.4, step_s=2.0):
    return StepProfile(mxu_time_s=duty * step_s, step_time_s=step_s)


def _scalar_grid(profile, *, duration_s, interval_s, events=(),
                 stragglers=(1.0,), seed=0):
    """Reference: one SimulatedDeviceBackend per device, polled serially."""
    rng = np.random.default_rng(seed)
    tpa, clk = [], []
    for s in stragglers:
        be = SimulatedDeviceBackend(profile, events=list(events),
                                    straggler_factor=float(s),
                                    seed=int(rng.integers(0, 2 ** 31)))
        series = scrape(be, duration_s, interval_s)
        tpa.append(series.tpa)
        clk.append(series.clock_mhz)
    return np.array(tpa), np.array(clk)


# ---------------------------------------------------------------------------
# equivalence: engine vs scalar backend (same generative model)
# ---------------------------------------------------------------------------
def test_steady_state_tpa_and_clock_statistics_match():
    prof = _profile(0.42)
    n_dev, dur, iv = 16, 1800.0, 30.0
    grid = simulate_devices(prof, duration_s=dur, interval_s=iv,
                            n_devices=n_dev, seed=0)
    s_tpa, s_clk = _scalar_grid(prof, duration_s=dur, interval_s=iv,
                                stragglers=np.ones(n_dev), seed=0)
    assert grid.tpa.shape == s_tpa.shape == (n_dev, 60)
    # duty is deterministic up to tiny jitter: means must agree tightly
    assert grid.tpa.mean() == pytest.approx(s_tpa.mean(), abs=0.005)
    # clock: same OU stationary distribution (1% of f_max in the mean,
    # generous band on the spread)
    assert grid.clock_mhz.mean() == pytest.approx(s_clk.mean(), abs=15.0)
    assert grid.clock_mhz.std() == pytest.approx(s_clk.std(), rel=0.5)
    # derived OFU agrees within a fraction of a percentage point
    assert ofu_series(grid.tpa, grid.clock_mhz).mean() == pytest.approx(
        ofu_series(s_tpa, s_clk).mean(), abs=0.005)


def test_event_injection_statistics_match():
    """The 2.5x host-sync collapse must look identical through both
    paths, window by window."""
    prof = _profile(0.45)
    ev = [Event(start_s=300, end_s=900, slowdown=2.5)]
    grid = simulate_devices(prof, duration_s=900, interval_s=30.0,
                            events=ev, n_devices=8, seed=3)
    s_tpa, _ = _scalar_grid(prof, duration_s=900, interval_s=30.0,
                            events=ev, stragglers=np.ones(8), seed=3)
    v_before, v_during = grid.tpa[:, :10].mean(), grid.tpa[:, 10:].mean()
    r_before, r_during = s_tpa[:, :10].mean(), s_tpa[:, 10:].mean()
    assert v_before == pytest.approx(r_before, abs=0.01)
    assert v_during == pytest.approx(r_during, abs=0.01)
    assert v_before / v_during == pytest.approx(2.5, rel=0.05)


def test_mxu_scale_event_and_straggler_equivalence():
    prof = _profile(0.5, step_s=1.0)
    ev = [Event(start_s=120, end_s=360, mxu_scale=0.5, kind="shrunk_gemm")]
    stragglers = np.array([1.0, 1.0, 2.0, 1.3])
    grid = simulate_devices(prof, duration_s=600, interval_s=30.0,
                            events=ev, stragglers=stragglers, seed=11)
    s_tpa, _ = _scalar_grid(prof, duration_s=600, interval_s=30.0,
                            events=ev, stragglers=stragglers, seed=11)
    # per-device means match: straggler halves duty, event halves MXU work
    np.testing.assert_allclose(grid.tpa.mean(axis=1), s_tpa.mean(axis=1),
                               atol=0.01)
    assert grid.tpa[2].mean() == pytest.approx(grid.tpa[0].mean() / 2,
                                               rel=0.05)


def test_simulate_job_engines_agree():
    """simulate_job against the per-device oracle: one
    SimulatedDeviceBackend per sampled device, scraped serially, on the
    job's own straggler draws and per-device seeds."""
    spec = JobSpec("eq", "granite-3-2b", chips=32, true_duty=0.35,
                   duration_s=600, seed=5)
    got = simulate_job(spec, max_devices=8)
    prof, _, _ = build_profile(spec)
    stragglers, seeds = _job_draws(spec.seed, spec.straggler_sigma, 8)
    ref = [scrape(SimulatedDeviceBackend(prof, chip=spec.chip,
                                         straggler_factor=float(s),
                                         seed=int(seeds[1 + d])),
                  spec.duration_s, spec.scrape_interval_s)
           for d, s in enumerate(stragglers)]
    assert got.grid.tpa.shape == (8, 20) == (len(ref), len(ref[0].tpa))
    assert len(got.device_series) == 8
    ref_tpa = np.stack([r.tpa for r in ref])
    ref_clk = np.stack([r.clock_mhz for r in ref])
    assert got.ofu == pytest.approx(
        ofu_series(ref_tpa, ref_clk, spec.chip).mean(), abs=0.01)


@pytest.mark.parametrize("engine", ["warp", "auto", "vector", "scalar"])
def test_unknown_engine_is_rejected(engine):
    """Only 'fused' (the NumPy reference) and 'jax' are engines."""
    spec = JobSpec("eq", "granite-3-2b", chips=8, duration_s=300)
    with pytest.raises(ValueError, match="unknown engine"):
        simulate_job(spec, engine=engine)
    with pytest.raises(ValueError, match="unknown engine"):
        simulate_fleet([spec], engine=engine)


# ---------------------------------------------------------------------------
# fused multi-job grid: one padded pass over the whole fleet
# ---------------------------------------------------------------------------
def _sweep_specs(n=24):
    """Ragged sweep: mixed durations/duties, an evented job, a straggler."""
    return [JobSpec(f"j{i}", "granite-3-2b", chips=16,
                    true_duty=0.2 + 0.03 * (i % 8),
                    duration_s=300.0 + 150.0 * (i % 4), seed=i,
                    events=[Event(120, 360, slowdown=2.5)] if i % 7 == 0
                    else (),
                    straggler_sigma=0.2 if i % 5 == 0 else 0.0)
            for i in range(n)]


def test_fused_fleet_matches_per_job_loop():
    """Same-seed tolerance test (acceptance): the fused default must be
    statistically indistinguishable from simulating each job alone."""
    specs = _sweep_specs()
    fused = simulate_fleet(specs, max_devices=4)          # default = fused
    perjob = [simulate_job(s, max_devices=4) for s in specs]
    for f, p in zip(fused, perjob):
        assert f.app_mfu == p.app_mfu                     # shared profile math
        assert f.ofu == pytest.approx(p.ofu, abs=0.01)
        assert len(f.device_series) == len(p.device_series)
        for sf, sp in zip(f.device_series, p.device_series):
            assert sf.tpa.shape == sp.tpa.shape           # ragged S preserved
            assert sf.interval_s == sp.interval_s


def test_fused_is_the_default_and_deterministic():
    specs = _sweep_specs(6)
    a = simulate_fleet(specs)
    b = simulate_fleet(specs, engine="fused")
    for ta, tb in zip(a, b):
        for sa, sb in zip(ta.device_series, tb.device_series):
            np.testing.assert_array_equal(sa.tpa, sb.tpa)
            np.testing.assert_array_equal(sa.clock_mhz, sb.clock_mhz)


#: a mixed fleet for the bitwise pins: evented and plain jobs, 30 s and
#: 15 s scrapes (two fused groups), ragged durations and stragglers
PIN_EVENTS = (Event(300, 900, slowdown=2.5), Event(600, 700, mxu_scale=0.5))


def _pin_specs():
    return [JobSpec(f"p{i}", "granite-3-2b", chips=8,
                    true_duty=0.2 + 0.05 * i,
                    duration_s=600.0 + 300.0 * (i % 3),
                    scrape_interval_s=15.0 if i % 2 else 30.0,
                    events=PIN_EVENTS if i % 3 == 0 else (),
                    straggler_sigma=0.25 if i % 4 == 1 else 0.0, seed=40 + i)
            for i in range(6)]


def _digest(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.asarray(a)
        h.update(f"{a.dtype.str}{a.shape}".encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def test_numpy_engine_grids_are_pinned_bitwise():
    """The NumPy reference's grids, frozen bit for bit: a refactor of the
    engine must draw the same streams in the same order and round the
    same way (dtype, shape and bytes of every tpa and clock grid)."""
    tels = simulate_fleet(_pin_specs(), max_devices=4)
    assert [t.grid.tpa.shape for t in tels] == [
        (4, 20), (4, 60), (4, 40), (4, 40), (4, 30), (4, 80)]
    assert _digest([x for t in tels for x in (t.grid.tpa, t.grid.clock_mhz)]
                   ) == ("e243ee03a2fe38fb5827709d7c05a8a244c2c331"
                         "5997ac1921510373992f5b5f")
    one = simulate_job(_pin_specs()[0], max_devices=4)
    assert _digest([one.grid.tpa, one.grid.clock_mhz]) == (
        "d6d0c78ecde07bc93eef94a846e95811cdea99459fdf06fb3a62a41f8f5d91d1")


def test_fused_event_collapse_window_by_window():
    """The 2.5x host-sync signature must appear in the fused grid exactly
    where the per-job path puts it."""
    ev = [Event(start_s=300, end_s=900, slowdown=2.5)]
    specs = [JobSpec("quiet", "granite-3-2b", chips=8, true_duty=0.4,
                     duration_s=900, seed=1),
             JobSpec("gloo", "granite-3-2b", chips=8, true_duty=0.45,
                     duration_s=900, seed=2, events=ev)]
    quiet, gloo = simulate_fleet(specs, max_devices=8)
    g = np.stack([s.tpa for s in gloo.device_series])
    assert g[:, :10].mean() / g[:, 10:].mean() == pytest.approx(2.5,
                                                                rel=0.05)
    q = np.stack([s.tpa for s in quiet.device_series])
    assert q[:, :10].mean() == pytest.approx(q[:, 10:].mean(), abs=0.01)


def test_fused_groups_heterogeneous_intervals_and_chips():
    """Jobs that cannot share a grid (different scrape interval or clock
    domain) land in separate fused groups but one call still serves all."""
    slots = [JobSlot(StepProfile(0.8, 2.0), 600, 30.0,
                     stragglers=np.ones(3)),
             JobSlot(StepProfile(0.8, 2.0), 600, 15.0,
                     stragglers=np.ones(2)),
             JobSlot(StepProfile(0.9, 2.0), 450, 30.0,
                     chip=TPU_V6E_LIKE, stragglers=np.ones(4)),
             JobSlot(StepProfile(0.5, 2.0), 10.0, 30.0)]   # S == 0
    grids = simulate_jobs_fused(slots, seed=0)
    assert [g.tpa.shape for g in grids] == [(3, 20), (2, 40), (4, 15),
                                            (1, 0)]
    assert grids[1].interval_s == 15.0
    # each job's clock lives in its own chip's domain
    assert grids[0].clock_mhz.max() <= 1500.0
    assert grids[2].clock_mhz.mean() > 1500.0


def test_fused_straggler_scaling():
    slot = JobSlot(StepProfile(1.0, 2.0), 600, 30.0,
                   stragglers=np.array([1.0, 2.0]))
    (grid,) = simulate_jobs_fused([slot], seed=4)
    assert grid.tpa[1].mean() == pytest.approx(grid.tpa[0].mean() / 2,
                                               rel=0.05)


def test_simulate_job_accepts_fused_and_profile_cache_not_chip_aliased():
    import dataclasses

    spec = JobSpec("one", "granite-3-2b", chips=8, true_duty=0.35,
                   duration_s=300, seed=3)
    fused = simulate_job(spec, max_devices=4, engine="fused")
    default = simulate_job(spec, max_devices=4)
    np.testing.assert_array_equal(fused.grid.tpa, default.grid.tpa)
    # a customized chip must not alias the stock entry in the profile
    # cache (same .name, different physics)
    slow = dataclasses.replace(spec.chip, f_max_mhz=spec.chip.f_max_mhz / 2)
    halved = simulate_job(dataclasses.replace(spec, chip=slow),
                          max_devices=4)
    assert halved.step_time_s == pytest.approx(fused.step_time_s * 2)


def test_engine_params_default_not_shared():
    """Regression guard for the mutable-default bug: each call constructs
    its own EngineParams, and an explicit params object is honored."""
    import inspect
    sig = inspect.signature(simulate_devices)
    assert sig.parameters["params"].default is None
    grid = simulate_devices(StepProfile(0.8, 2.0), duration_s=300,
                            interval_s=30.0, n_devices=2, seed=0,
                            params=EngineParams(n_sub_max=8))
    assert grid.tpa.shape == (2, 10)


def test_simulate_devices_rejects_device_count_mismatch():
    """Regression (ISSUE 6): n_devices=1 alongside 5 stragglers used to
    silently simulate 5 devices; conflicting counts now raise, and each
    argument alone still infers the other."""
    prof = StepProfile(0.8, 2.0)
    with pytest.raises(ValueError,
                       match=r"n_devices=1 conflicts .*stragglers\)=5"):
        simulate_devices(prof, duration_s=300, interval_s=30.0,
                         n_devices=1, stragglers=np.ones(5))
    grid = simulate_devices(prof, duration_s=300, interval_s=30.0,
                            stragglers=np.full(5, 1.2), seed=0)
    assert grid.tpa.shape == (5, 10)        # inferred from stragglers
    grid = simulate_devices(prof, duration_s=300, interval_s=30.0,
                            n_devices=3, seed=0)
    assert grid.tpa.shape == (3, 10)        # unit stragglers materialized
    grid = simulate_devices(prof, duration_s=300, interval_s=30.0,
                            n_devices=2, stragglers=np.ones(2), seed=0)
    assert grid.tpa.shape == (2, 10)        # agreeing counts still fine


# ---------------------------------------------------------------------------
# streaming rollup: buckets, percentiles, detector feeds
# ---------------------------------------------------------------------------
def test_hist_percentile_grid_matches_scalar_readout():
    """Satellite: the vectorized per-bucket percentile readout must agree
    with the scalar hist_percentile loop bucket for bucket."""
    rng = np.random.default_rng(0)
    edges = np.linspace(0.0, 1.1, 129)
    h = rng.integers(0, 20, size=(12, 128)).astype(float) \
        * rng.uniform(0.5, 64, size=(12, 1))
    h[3] = 0.0                                   # an empty bucket row
    h[7, :64] = 0.0
    qs = (0, 10, 50, 90, 100)
    grid = hist_percentile_grid(edges, h, qs)
    assert grid.shape == (5, 12)
    for k, q in enumerate(qs):
        ref = [hist_percentile(edges, h[b], q) for b in range(12)]
        np.testing.assert_allclose(grid[k], ref, atol=1e-12, equal_nan=True)
    assert hist_percentile_grid(edges, np.empty((0, 128)), qs).shape == (5, 0)


def test_rollup_percentiles_and_groups():
    specs = [
        JobSpec("lo", "granite-3-2b", chips=64, true_duty=0.2,
                duration_s=1200, seed=1),
        JobSpec("hi", "granite-3-2b", chips=64, true_duty=0.5,
                duration_s=1200, seed=2),
        JobSpec("fp8", "granite-3-2b", chips=64, true_duty=0.35,
                duration_s=1200, seed=3,
                precisions={"bf16": 0.4, "fp8": 0.6}),
    ]
    roll = StreamingRollup(bucket_s=300)
    for t in simulate_fleet(specs, max_devices=4):
        roll.add_job(t)
    assert set(roll.groups) == {"bf16", "bf16+fp8"}
    assert precision_label(specs[2].precisions) == "bf16+fp8"
    f = roll.fleet_stats()
    # fleet p10 tracks the low job, p90 the high job; median in between
    assert f.percentiles[10][1] < 0.3 < f.percentiles[90][1]
    assert np.all(f.percentiles[10][:4] <= f.percentiles[50][:4] + 1e-9)
    assert np.all(f.percentiles[50][:4] <= f.percentiles[90][:4] + 1e-9)
    # per-job bucket means recover each job's true efficiency band
    assert roll.job_ofu("lo").mean() == pytest.approx(0.2, abs=0.03)
    assert roll.job_ofu("hi").mean() == pytest.approx(0.48, abs=0.04)
    # chip-weighting: every job contributes chips x samples of weight
    assert np.nansum(f.weight) == pytest.approx(3 * 64 * 40)


def test_rollup_feeds_regression_detector_at_fleet_scale():
    """Paper SecVI-A at scale: a 512-chip job collapses 2.5x mid-run; the
    bucketed rollup series must trip the existing detector."""
    spec = JobSpec("gloo", "granite-3-2b", chips=512, true_duty=0.45,
                   duration_s=7200, seed=7,
                   events=[Event(start_s=3600, end_s=7200, slowdown=2.5)])
    (tel,) = simulate_fleet([spec], max_devices=64)
    roll = StreamingRollup(bucket_s=120)
    roll.add_job(tel)
    series = roll.job_ofu("gloo")
    assert len(series) >= 60
    assert not np.isnan(series).any()
    regs = detect_regressions(series, factor_threshold=1.5)
    assert len(regs) == 1
    # TPA collapses exactly 2.5x but the idler clock throttles less, so
    # the OFU factor lands a bit under 2.5; the detector also dilutes the
    # reference through its drift tracker — accept the documented band
    assert series[:29].mean() / series[32:].mean() == pytest.approx(
        2.42, rel=0.05)
    assert 2.0 < regs[0].factor < 2.6
    # divergence bridge: the same rollup yields analyzable job points
    pts = roll.to_job_points()
    assert len(pts) == 1 and pts[0].job_id == "gloo"
    assert pts[0].ofu == pytest.approx(tel.ofu, abs=0.02)


def test_rollup_forward_fill_and_empty_scopes():
    roll = StreamingRollup(bucket_s=10)
    roll.observe("a", np.array([5.0, 25.0]), np.array([0.4, 0.2]),
                 group="bf16")
    filled = roll.job_ofu("a")
    assert filled == pytest.approx([0.4, 0.4, 0.2])   # gap forward-filled
    raw = roll.job_stats("a", qs=()).mean
    assert np.isnan(raw[1]) and raw[0] == pytest.approx(0.4)
    assert len(roll.job_stats("missing").mean) == 0


# ---------------------------------------------------------------------------
# the fleet-scale performance contract (acceptance criterion)
# ---------------------------------------------------------------------------
def test_thousand_devices_one_hour_under_ten_seconds():
    spec = JobSpec("fleet", "granite-3-2b", chips=1000, true_duty=0.35,
                   duration_s=3600, scrape_interval_s=30, seed=0)
    t0 = time.perf_counter()
    (tel,) = simulate_fleet([spec], max_devices=1000)
    elapsed = time.perf_counter() - t0
    assert elapsed < 10.0, f"fleet sim took {elapsed:.1f}s"
    assert len(tel.device_series) == 1000
    assert len(tel.device_series[0].tpa) == 120
    assert tel.ofu == pytest.approx(0.35 * 0.96, abs=0.03)
