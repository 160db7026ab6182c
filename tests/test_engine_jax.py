"""jax fleet engine backend (ISSUE 6 tentpole): statistical equivalence
against the NumPy fused reference at the fused-vs-scalar tolerances, the
fused pallas/XLA histogram ingest producing rollups bucketwise IDENTICAL
to the host path, and the `simulate_fleet(engine="jax")` dispatch."""
import numpy as np
import pytest

from _propcheck import given, settings, st

jax = pytest.importorskip("jax")
jnp = jax.numpy

from repro.fleet import JobSpec, simulate_fleet, simulate_job  # noqa: E402
from repro.fleet.engine import JobSlot, simulate_jobs_fused  # noqa: E402
from repro.fleet.engine_jax import default_mesh, simulate_jobs_jax  # noqa: E402
from repro.fleet.streaming import StreamingRollup, WindowedRollup  # noqa: E402
from repro.kernels.fleet_hist import (_aligned_spb, _block_rows,  # noqa: E402
                                      bucket_hist_ref, ofu_bucket_hist)
from repro.telemetry import Event, StepProfile  # noqa: E402
from repro.telemetry.scrape import DeviceGrid  # noqa: E402


def _profile(duty=0.4, step_s=2.0):
    return StepProfile(mxu_time_s=duty * step_s, step_time_s=step_s)


def _host_grid(g: DeviceGrid) -> DeviceGrid:
    """Device grid -> identical-valued NumPy grid (host ingest path)."""
    return DeviceGrid(g.interval_s, np.asarray(g.tpa),
                      np.asarray(g.clock_mhz), t0_s=g.t0_s)


def _scope_state_equal(a: StreamingRollup, b: StreamingRollup):
    """Bucketwise identity: same scopes, identical histogram counts,
    value sums equal to f32-accumulation tolerance."""
    assert set(a._hists) == set(b._hists)
    for scope in b._hists:
        np.testing.assert_array_equal(a._hists[scope], b._hists[scope],
                                      err_msg=str(scope))
        np.testing.assert_allclose(a._sums[scope], b._sums[scope],
                                   rtol=1e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# equivalence: jax backend vs the NumPy fused reference
# ---------------------------------------------------------------------------
def test_steady_state_statistics_match_numpy():
    slot = JobSlot(_profile(0.42), 1800.0, 30.0, stragglers=np.ones(16))
    (ref,) = simulate_jobs_fused([slot], seed=0)
    (g,) = simulate_jobs_jax([slot], seed=0)
    tpa, clk = np.asarray(g.tpa), np.asarray(g.clock_mhz)
    assert tpa.shape == ref.tpa.shape == (16, 60)
    # same tolerances the fused-vs-scalar suite freezes (test_fleet_engine)
    assert tpa.mean() == pytest.approx(ref.tpa.mean(), abs=0.005)
    assert clk.mean() == pytest.approx(ref.clock_mhz.mean(), abs=15.0)
    assert clk.std() == pytest.approx(ref.clock_mhz.std(), rel=0.5)
    ofu_j = tpa * clk / 1558.0
    ofu_n = ref.tpa * ref.clock_mhz / 1558.0
    assert ofu_j.mean() == pytest.approx(ofu_n.mean(), abs=0.005)


def test_event_collapse_window_by_window():
    """The 2.5x host-sync collapse lands in the same windows on both
    backends."""
    ev = [Event(start_s=300, end_s=900, slowdown=2.5)]
    slot = JobSlot(_profile(0.45), 900.0, 30.0, events=ev,
                   stragglers=np.ones(8))
    (ref,) = simulate_jobs_fused([slot], seed=3)
    (g,) = simulate_jobs_jax([slot], seed=3)
    tpa = np.asarray(g.tpa)
    assert tpa[:, :10].mean() == pytest.approx(ref.tpa[:, :10].mean(),
                                               abs=0.01)
    assert tpa[:, 10:].mean() == pytest.approx(ref.tpa[:, 10:].mean(),
                                               abs=0.01)
    assert tpa[:, :10].mean() / tpa[:, 10:].mean() \
        == pytest.approx(2.5, rel=0.05)


def test_straggler_and_mxu_scale_event_equivalence():
    ev = [Event(start_s=120, end_s=360, mxu_scale=0.5, kind="shrunk_gemm")]
    stragglers = np.array([1.0, 1.0, 2.0, 1.3])
    slot = JobSlot(_profile(0.5, step_s=1.0), 600.0, 30.0, events=ev,
                   stragglers=stragglers)
    (ref,) = simulate_jobs_fused([slot], seed=11)
    (g,) = simulate_jobs_jax([slot], seed=11)
    tpa = np.asarray(g.tpa)
    np.testing.assert_allclose(tpa.mean(axis=1), ref.tpa.mean(axis=1),
                               atol=0.01)
    assert tpa[2].mean() == pytest.approx(tpa[0].mean() / 2, rel=0.05)


def test_multi_job_grouping_and_ragged_slices_match_numpy_layout():
    """Heterogeneous slots land in the same groups with the same output
    shapes and clock domains as the NumPy backend (incl. the S == 0
    degenerate slot)."""
    from repro.core.peaks import TPU_V6E_LIKE
    slots = [JobSlot(StepProfile(0.8, 2.0), 600, 30.0,
                     stragglers=np.ones(3)),
             JobSlot(StepProfile(0.8, 2.0), 600, 15.0,
                     stragglers=np.ones(2)),
             JobSlot(StepProfile(0.9, 2.0), 450, 30.0,
                     chip=TPU_V6E_LIKE, stragglers=np.ones(4)),
             JobSlot(StepProfile(0.5, 2.0), 10.0, 30.0)]
    grids = simulate_jobs_jax(slots, seed=0)
    assert [np.asarray(g.tpa).shape for g in grids] \
        == [(3, 20), (2, 40), (4, 15), (1, 0)]
    assert grids[1].interval_s == 15.0
    assert np.asarray(grids[0].clock_mhz).max() <= 1500.0
    assert np.asarray(grids[2].clock_mhz).mean() > 1500.0


@settings(max_examples=10, derandomize=True, deadline=None)
@given(duty=st.floats(0.15, 0.6), n_dev=st.integers(1, 12),
       n_samp=st.integers(1, 80), sigma=st.floats(0.0, 0.3),
       evented=st.booleans(), seed=st.integers(0, 2 ** 16))
def test_property_jax_matches_numpy_and_ingest_is_bucketwise_identical(
        duty, n_dev, n_samp, sigma, evented, seed):
    """Same-seed property suite (acceptance): over random jobs the jax
    backend matches NumPy statistics within sample-count-scaled
    tolerances, and its device grid ingested through add_grid yields a
    rollup bucketwise identical to host ingestion of the same values."""
    dur = n_samp * 30.0
    strag = np.exp(np.random.default_rng(seed).standard_normal(n_dev)
                   * sigma)
    events = [Event(dur / 4, 3 * dur / 4, slowdown=2.0)] if evented else ()
    slot = JobSlot(_profile(duty), dur, 30.0, events=events,
                   stragglers=strag)
    (ref,) = simulate_jobs_fused([slot], seed=seed)
    (g,) = simulate_jobs_jax([slot], seed=seed)
    tpa, clk = np.asarray(g.tpa), np.asarray(g.clock_mhz)
    assert tpa.shape == ref.tpa.shape == (n_dev, n_samp)
    n = max(n_dev * n_samp, 1)
    # deterministic duty + tiny jitter: tight; OU noise: se ~ sigma/sqrt(n)
    assert tpa.mean() == pytest.approx(ref.tpa.mean(), abs=0.01)
    assert clk.mean() == pytest.approx(
        ref.clock_mhz.mean(), abs=15.0 + 110.0 / np.sqrt(n))
    ofu_j = (tpa * clk / 1558.0).mean()
    ofu_n = (ref.tpa * ref.clock_mhz / 1558.0).mean()
    assert ofu_j == pytest.approx(ofu_n, abs=0.005 + 0.06 / np.sqrt(n))

    r_dev, r_host = StreamingRollup(bucket_s=300), StreamingRollup(
        bucket_s=300)
    # integer chips-per-device weight: repeated-add (host) and count *
    # weight (device) stay binary-identical
    r_dev.add_grid("j", g, chips=4 * n_dev, group="bf16")
    r_host.add_grid("j", _host_grid(g), chips=4 * n_dev, group="bf16")
    _scope_state_equal(r_dev, r_host)


# ---------------------------------------------------------------------------
# device-side rollup ingest: add_grid over jax grids
# ---------------------------------------------------------------------------
def test_add_grid_device_path_matches_host_bucketwise():
    ev = [Event(1200, 2400, slowdown=2.5)]
    slot = JobSlot(_profile(0.42), 3600.0, 30.0, events=ev,
                   stragglers=np.ones(8))
    (g,) = simulate_jobs_jax([slot], seed=3)
    r_dev, r_host = StreamingRollup(bucket_s=300), StreamingRollup(
        bucket_s=300)
    ofu_dev = r_dev.add_grid("j", g, chips=128, group="bf16", app_mfu=0.4)
    ofu_host = r_host.add_grid("j", _host_grid(g), chips=128, group="bf16",
                               app_mfu=0.4)
    _scope_state_equal(r_dev, r_host)
    # identical readouts all the way to percentiles and job metadata
    sd, sh = r_dev.job_stats("j"), r_host.job_stats("j")
    np.testing.assert_array_equal(sd.weight, sh.weight)
    for q in (10, 50, 90):
        np.testing.assert_array_equal(sd.percentiles[q], sh.percentiles[q])
    assert r_dev.job_meta("j") == r_host.job_meta("j")
    # the returned OFU series stays a device array with the host's values
    assert type(ofu_dev).__module__.startswith(("jax", "jaxlib"))
    np.testing.assert_allclose(np.asarray(ofu_dev), ofu_host, rtol=1e-6)


def test_add_grid_device_path_windowed_with_eviction():
    """Windowed ingest evicts identically: a grid longer than the window
    folds its oldest buckets into the all-time totals on both paths."""
    slot = JobSlot(_profile(0.42), 3600.0, 30.0, stragglers=np.ones(4))
    (g,) = simulate_jobs_jax([slot], seed=5)
    w_dev = WindowedRollup(bucket_s=300, retain=6)
    w_host = WindowedRollup(bucket_s=300, retain=6)
    w_dev.add_grid("j", g, chips=32, group="bf16")
    w_host.add_grid("j", _host_grid(g), chips=32, group="bf16")
    assert w_dev.bucket0 == w_host.bucket0 == 6
    _scope_state_equal(w_dev, w_host)
    for scope in w_host._ev_hist:
        np.testing.assert_array_equal(w_dev._ev_hist[scope],
                                      w_host._ev_hist[scope])
        assert w_dev._ev_sum[scope] == pytest.approx(
            w_host._ev_sum[scope], rel=1e-5)
    assert w_dev.job_alltime("j")["weight"] \
        == w_host.job_alltime("j")["weight"]


def test_observe_hist_validates_bin_count():
    roll = StreamingRollup(bucket_s=300, bins=128)
    with pytest.raises(ValueError, match="64 bins"):
        roll.observe_hist("j", np.zeros((2, 64)), np.zeros(2))
    roll.observe_hist("j", np.zeros((0, 64)), np.zeros(0))  # empty: no-op
    assert roll.n_buckets == 0


# ---------------------------------------------------------------------------
# the fused histogram kernel itself (pallas + XLA vs the NumPy oracle)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("D,S,spb", [
    (513, 40, 10),          # ragged last row tile (512-row tiles)
    (1100, 25, 10),         # three tiles; short last bucket
    (40, 2880, 7),          # a day of columns: 16-row tiles
    (4, 10, 10),            # fewer rows than a sublane tile
])
def test_hist_kernel_pallas_and_xla_match_reference_exactly(D, S, spb):
    rng = np.random.default_rng(0)
    tpa = rng.uniform(0, 1, (D, S)).astype(np.float32)
    clk = rng.uniform(900, 1558, (D, S)).astype(np.float32)
    edges = np.linspace(0.0, 1.1, 129)
    col = np.arange(S) // spb
    kw = dict(inv_fmax=1 / 1558.0, edges=edges, col_bucket=col,
              n_buckets=int(col[-1]) + 1)
    hr, sr = bucket_hist_ref(tpa, clk, **kw)
    assert hr.sum() == D * S            # every sample lands exactly once
    for use_pallas in (True, False):
        h, s = ofu_bucket_hist(jnp.asarray(tpa), jnp.asarray(clk),
                               use_pallas=use_pallas, **kw)
        np.testing.assert_array_equal(np.asarray(h), hr)
        np.testing.assert_allclose(np.asarray(s), sr, rtol=1e-5)


def test_hist_kernel_ragged_bucket_map_falls_back_to_xla():
    rng = np.random.default_rng(1)
    tpa = rng.uniform(0, 1, (64, 25)).astype(np.float32)
    clk = rng.uniform(900, 1558, (64, 25)).astype(np.float32)
    edges = np.linspace(0.0, 1.1, 129)
    col = np.repeat([0, 1, 2, 3], [3, 9, 9, 4])  # uneven bucket widths
    assert _aligned_spb(col, 4) is None
    kw = dict(inv_fmax=1 / 1558.0, edges=edges, col_bucket=col,
              n_buckets=4)
    hr, sr = bucket_hist_ref(tpa, clk, **kw)
    h, s = ofu_bucket_hist(jnp.asarray(tpa), jnp.asarray(clk),
                           use_pallas=True, **kw)   # still correct via XLA
    np.testing.assert_array_equal(np.asarray(h), hr)
    np.testing.assert_allclose(np.asarray(s), sr, rtol=1e-5)


def test_hist_kernel_rejects_bad_edges():
    tpa = np.ones((2, 2), np.float32)
    with pytest.raises(ValueError, match="strictly-increasing"):
        ofu_bucket_hist(tpa, tpa, inv_fmax=1.0,
                        edges=np.array([0.0, 1.0, 0.5]),
                        col_bucket=np.zeros(2, int), n_buckets=1)


def test_kernel_tiles_are_sublane_aligned_or_whole():
    assert _block_rows(1_000_000, 120) == 512       # 64K samples a tile
    assert _block_rows(100_000, 2880) == 16
    assert _block_rows(4, 10) == 4                  # the whole array
    assert _block_rows(10**6, 10**6) == 8


def test_aligned_spb_detection():
    assert _aligned_spb(np.arange(30) // 10, 3) == 10
    assert _aligned_spb(np.arange(25) // 10, 3) == 10   # short last bucket
    assert _aligned_spb(np.array([0, 0, 1, 1, 1]), 2) is None
    assert _aligned_spb(np.empty(0, int), 0) is None


# ---------------------------------------------------------------------------
# dispatch + sharding knobs
# ---------------------------------------------------------------------------
def test_simulate_fleet_jax_dispatch():
    specs = [JobSpec("a", "granite-3-2b", chips=16, true_duty=0.35,
                     duration_s=600, seed=1),
             JobSpec("b", "granite-3-2b", chips=16, true_duty=0.5,
                     duration_s=900, seed=2)]
    jx = simulate_fleet(specs, max_devices=4, engine="jax")
    ref = simulate_fleet(specs, max_devices=4)           # fused NumPy
    for tj, tr in zip(jx, ref):
        assert tj.app_mfu == tr.app_mfu                  # shared profile math
        assert np.asarray(tj.grid.tpa).shape == tr.grid.tpa.shape
        assert float(tj.ofu) == pytest.approx(tr.ofu, abs=0.015)
    with pytest.raises(ValueError, match="unknown engine"):
        simulate_fleet(specs, engine="warp")


def test_simulate_job_jax_dispatch():
    spec = JobSpec("eq", "granite-3-2b", chips=32, true_duty=0.35,
                   duration_s=600, seed=5)
    jx = simulate_job(spec, max_devices=8, engine="jax")
    ref = simulate_job(spec, max_devices=8, engine="vector")
    assert jx.app_mfu == ref.app_mfu
    assert float(jx.ofu) == pytest.approx(ref.ofu, abs=0.015)
    assert len(jx.device_series) == 8


def test_mesh_knobs_and_materialize():
    slot = JobSlot(_profile(0.4), 600.0, 30.0, stragglers=np.ones(4))
    # explicit 1-device mesh: the sharding constraint is semantically a
    # no-op, so results are bit-identical to the unconstrained run
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("devices",))
    (a,) = simulate_jobs_jax([slot], seed=9, mesh=mesh, materialize=True)
    (b,) = simulate_jobs_jax([slot], seed=9, mesh=None, materialize=True)
    assert isinstance(a.tpa, np.ndarray)
    np.testing.assert_array_equal(a.tpa, b.tpa)
    np.testing.assert_array_equal(a.clock_mhz, b.clock_mhz)
    # auto mesh on a single-device host resolves to None
    if len(jax.devices()) == 1:
        assert default_mesh() is None
    with pytest.raises(ValueError, match="mesh spec"):
        simulate_jobs_jax([slot], mesh="torus")
