"""Scenario library + fault-injection layer (ISSUE 8 tentpole).

Covers the `CounterFault` post-hoc perturbation engine (masking, device
subsets, periodic/diurnal gating, clipping, validation), the central
post-hoc guarantee — simulating WITH faults equals applying faults to
the SAME simulation without them, on every engine backend — and the
labeled scenario library's registry, determinism, and label hygiene.
"""
import numpy as np
import pytest

from repro.fleet.engine import CounterFault, apply_faults, fault_factors
from repro.fleet.jobs import JobSpec, simulate_fleet, simulate_job
from repro.scenarios import SCENARIOS, GroundTruthEvent, Scenario, build
from repro.telemetry.scrape import DeviceGrid


def _times(n, interval=30.0):
    return interval + interval * np.arange(n)


# ---------------------------------------------------------------------------
# fault_factors: the (duty, clock) mask algebra
# ---------------------------------------------------------------------------
def test_fault_window_masks_time_and_all_devices():
    t = _times(10)
    duty, clock = fault_factors(
        [CounterFault(start_s=120.0, end_s=240.0, duty_scale=0.4,
                      clock_scale=0.7)], t, 3)
    on = (t >= 120.0) & (t < 240.0)
    assert duty.shape == clock.shape == (3, 10)
    np.testing.assert_allclose(duty[:, on], 0.4)
    np.testing.assert_allclose(duty[:, ~on], 1.0)
    np.testing.assert_allclose(clock[:, on], 0.7)
    np.testing.assert_allclose(clock[:, ~on], 1.0)


def test_fault_device_subsets():
    t = _times(4)
    # explicit device rows
    duty, _ = fault_factors([CounterFault(duty_scale=0.5, devices=(0, 2))],
                            t, 4)
    np.testing.assert_allclose(duty[[0, 2]], 0.5)
    np.testing.assert_allclose(duty[[1, 3]], 1.0)
    # fractional: ceil(0.5 * 4) = first 2 rows
    duty, _ = fault_factors([CounterFault(duty_scale=0.5,
                                          device_frac=0.5)], t, 4)
    np.testing.assert_allclose(duty[:2], 0.5)
    np.testing.assert_allclose(duty[2:], 1.0)
    with pytest.raises(ValueError, match="device"):
        fault_factors([CounterFault(devices=(5,))], t, 4)


def test_fault_periodic_gating():
    t = _times(12, interval=10.0)          # 10..120
    duty, _ = fault_factors(
        [CounterFault(start_s=10.0, duty_scale=0.2, period_s=40.0,
                      active_frac=0.5)], t, 1)
    # active while (t - 10) mod 40 < 20
    on = np.mod(t - 10.0, 40.0) < 20.0
    on &= t >= 10.0
    np.testing.assert_allclose(duty[0, on], 0.2)
    np.testing.assert_allclose(duty[0, ~on], 1.0)


def test_fault_diurnal_wave():
    t = _times(8, interval=100.0)
    duty, _ = fault_factors(
        [CounterFault(diurnal_amp=0.25, diurnal_period_s=800.0)], t, 2)
    want = 1.0 + 0.25 * np.sin(2 * np.pi * t / 800.0)
    np.testing.assert_allclose(duty[0], want, rtol=1e-6)
    np.testing.assert_allclose(duty[1], want, rtol=1e-6)


def test_faults_compound_multiplicatively():
    t = _times(6)
    f1 = CounterFault(duty_scale=0.5)
    f2 = CounterFault(start_s=90.0, duty_scale=0.4, clock_scale=0.8)
    duty, clock = fault_factors([f1, f2], t, 1)
    on = t >= 90.0
    np.testing.assert_allclose(duty[0, on], 0.2)
    np.testing.assert_allclose(duty[0, ~on], 0.5)
    np.testing.assert_allclose(clock[0, on], 0.8)


def test_fault_validation():
    with pytest.raises(ValueError):
        CounterFault(start_s=100.0, end_s=50.0)
    with pytest.raises(ValueError):
        CounterFault(device_frac=0.0)
    with pytest.raises(ValueError):
        CounterFault(device_frac=1.5)
    with pytest.raises(ValueError):
        CounterFault(period_s=100.0, active_frac=0.0)
    with pytest.raises(ValueError):
        CounterFault(diurnal_amp=1.5)


# ---------------------------------------------------------------------------
# apply_faults: grid semantics
# ---------------------------------------------------------------------------
def _grid(n_dev=2, n_s=6, tpa=0.5, clock=1200.0):
    return DeviceGrid(30.0, np.full((n_dev, n_s), tpa),
                      np.full((n_dev, n_s), clock), t0_s=0.0)


def test_apply_faults_empty_is_noop():
    g = _grid()
    out = apply_faults(g, [])
    np.testing.assert_array_equal(out.tpa, g.tpa)
    np.testing.assert_array_equal(out.clock_mhz, g.clock_mhz)
    assert out.interval_s == g.interval_s and out.t0_s == g.t0_s


def test_apply_faults_scales_and_clips():
    g = _grid(tpa=0.8, clock=1000.0)
    out = apply_faults(g, [CounterFault(duty_scale=1.5, clock_scale=0.5)])
    np.testing.assert_allclose(out.tpa, 1.0)          # clipped at 1
    np.testing.assert_allclose(out.clock_mhz, 500.0)
    assert out.t0_s == g.t0_s and out.interval_s == g.interval_s
    # and the input grid is untouched
    np.testing.assert_allclose(g.tpa, 0.8)


# ---------------------------------------------------------------------------
# The post-hoc guarantee: faults never change the underlying realization
# ---------------------------------------------------------------------------
FAULTS = [CounterFault(start_s=300.0, duty_scale=0.4, clock_scale=0.9)]


def _spec(faults=(), **kw):
    kw.setdefault("duration_s", 600.0)
    kw.setdefault("chips", 8)
    return JobSpec("posthoc", "llama3.2-3b", seed=3, faults=list(faults),
                   **kw)


@pytest.mark.parametrize("engine", ["fused", "jax"])
def test_posthoc_equals_apply_after_the_fact(engine):
    base = simulate_job(_spec(), engine=engine)
    faulted = simulate_job(_spec(FAULTS), engine=engine)
    want = apply_faults(base.grid, FAULTS)
    np.testing.assert_array_equal(faulted.grid.tpa, want.tpa)
    np.testing.assert_array_equal(faulted.grid.clock_mhz, want.clock_mhz)
    # app-side numbers are untouched: the app doesn't know it regressed
    assert faulted.app_mfu == base.app_mfu
    assert faulted.step_time_s == base.step_time_s


def test_posthoc_fused_fleet_faults_only_hit_their_job():
    specs = [_spec(), JobSpec("bystander", "qwen3-4b", seed=4,
                              duration_s=600.0, chips=8)]
    plain = simulate_fleet(specs, engine="fused")
    specs_f = [_spec(FAULTS), JobSpec("bystander", "qwen3-4b", seed=4,
                                      duration_s=600.0, chips=8)]
    faulted = simulate_fleet(specs_f, engine="fused")
    want = apply_faults(plain[0].grid, FAULTS)
    np.testing.assert_array_equal(faulted[0].grid.tpa, want.tpa)
    # the unfaulted job's realization is bit-identical
    np.testing.assert_array_equal(faulted[1].grid.tpa, plain[1].grid.tpa)


# ---------------------------------------------------------------------------
# the library
# ---------------------------------------------------------------------------
def test_library_has_the_required_scenarios():
    names = set(SCENARIOS)
    assert len(names) >= 6
    assert {"gloo_regression_2p5x", "mixed_precision_transition",
            "straggler_hosts", "thermal_throttle", "preemption_wave",
            "moe_expert_imbalance", "diurnal_inference"} <= names


def test_build_is_deterministic():
    a, b = build("gloo_regression_2p5x"), build("gloo_regression_2p5x")
    assert [s.job_id for s in a.specs] == [s.job_id for s in b.specs]
    assert a.labels == b.labels
    ga = simulate_fleet(a.specs, engine="fused")
    gb = simulate_fleet(b.specs, engine="fused")
    for ta, tb in zip(ga, gb):
        np.testing.assert_array_equal(ta.grid.tpa, tb.grid.tpa)
        np.testing.assert_array_equal(ta.grid.clock_mhz, tb.grid.clock_mhz)


def test_build_unknown_name():
    with pytest.raises(KeyError, match="unknown scenario"):
        build("nope")


def test_paper_scenario_carries_the_2p5x_ground_truth():
    sc = build("gloo_regression_2p5x")
    (lbl,) = sc.labels
    assert lbl.detector == "regression"
    assert lbl.magnitude == pytest.approx(2.5)
    (bad,) = [s for s in sc.specs if s.faults]
    assert bad.job_id == lbl.job_id
    assert bad.faults[0].duty_scale == pytest.approx(0.4)   # 1/2.5


def test_diurnal_scenario_is_the_false_positive_probe():
    sc = build("diurnal_inference")
    assert sc.labels == []
    assert all(s.faults for s in sc.specs)      # benign faults everywhere


def test_scenario_label_hygiene():
    spec = JobSpec("a", "llama3.2-3b")
    with pytest.raises(ValueError, match="unknown job"):
        Scenario("x", "d", [spec],
                 [GroundTruthEvent("ghost", "regression", 10.0)])
    with pytest.raises(ValueError, match="unknown detector"):
        GroundTruthEvent("a", "oracle", 10.0)
    with pytest.raises(ValueError, match="empty"):
        GroundTruthEvent("a", "regression", 10.0, end_s=5.0)
    with pytest.raises(ValueError, match="duplicate"):
        Scenario("x", "d", [spec, JobSpec("a", "qwen3-4b")], [])
