"""Unit tests for the OFU metric core (paper Eq. 1, 5, 8, 9, 12)."""
import numpy as np
import pytest
from _propcheck import given, settings, st

from repro.core import (TPU_V5E, AccuracyReport, adjusted_ofu, effective_peak,
                        hist_percentile, mae, mfu_from_throughput, ofu_mean,
                        ofu_point, ofu_series, pct_within, pearson_r)


def test_peak_derivation_matches_published():
    # Eq. 5 audit: 4 MXUs x 128x128 x 2 x 1500 MHz = 196.6 TF/s (~197 pub.)
    assert TPU_V5E.peak_tflops("bf16") == pytest.approx(196.608)
    assert TPU_V5E.peak_tflops("int8") == pytest.approx(393.216)
    assert TPU_V5E.peak_tflops("fp32") == pytest.approx(196.608 / 4)


def test_peaks_looked_up_by_device_kind():
    from repro.core.peaks import chip_for_kind, device_chip
    assert chip_for_kind("TPU v5 lite") is TPU_V5E
    with pytest.raises(ValueError, match="no peak table entry"):
        chip_for_kind("TPU v9 imaginary")
    with pytest.raises(ValueError, match="'cpu'"):
        device_chip(type("Dev", (), {"device_kind": "cpu"})())


def test_ofu_point_eq1():
    # full duty at full clock = 1.0; clock throttle scales linearly
    assert ofu_point(1.0, TPU_V5E.f_max_mhz) == pytest.approx(1.0)
    assert ofu_point(0.5, TPU_V5E.f_max_mhz * 0.9) == pytest.approx(0.45)


@given(st.floats(0, 1), st.floats(0.5, 1.0))
@settings(max_examples=50, deadline=None)
def test_ofu_bounded(tpa, clock_frac):
    v = ofu_point(tpa, TPU_V5E.f_max_mhz * clock_frac)
    assert 0.0 <= v <= 1.0 + 1e-9


def test_adjusted_ofu_eq8():
    # hardware executed 10% extra FLOPs -> OFU_adj shrinks by that factor
    assert adjusted_ofu(0.55, 100.0, 110.0) == pytest.approx(0.5)
    assert adjusted_ofu(0.55, 100.0, 0.0) == 0.55  # degenerate guard


def test_effective_peak_harmonic_mean_eq12():
    # all bf16 -> bf16 peak; all int8 -> int8 peak
    assert effective_peak({"bf16": 1e12}) == pytest.approx(196.608)
    assert effective_peak({"int8": 1e12}) == pytest.approx(393.216)
    # 50/50 FLOPs split -> harmonic mean
    p = effective_peak({"bf16": 1.0, "int8": 1.0})
    expect = 2 / (1 / 196.608 + 1 / 393.216)
    assert p == pytest.approx(expect)
    # mixed peak sits strictly between the two
    assert 196.608 < p < 393.216


def test_effective_peak_bf16_only_raises_mfu():
    """Paper §VI-B: constant throughput, BF16-only -> lower peak -> higher
    MFU.  The effective-peak denominator must reproduce that."""
    tflops_per_chip = 80.0
    p_mixed = effective_peak({"bf16": 0.4, "fp8": 0.6})
    p_bf16 = effective_peak({"bf16": 1.0})
    assert mfu_from_throughput(tflops_per_chip, p_bf16) > \
        mfu_from_throughput(tflops_per_chip, p_mixed)


# ---------------------------------------------------------------------------
# property-based hardening of the metric core
# ---------------------------------------------------------------------------
_PRECS = ["bf16", "int8", "fp8", "fp32"]


@given(st.integers(0, 2 ** 31 - 1), st.integers(1, 64))
@settings(max_examples=25, deadline=None)
def test_ofu_series_matches_pointwise(seed, n):
    """Eq. 11 must be exactly the element-wise map of Eq. 1."""
    rng = np.random.default_rng(seed)
    tpa = rng.uniform(0, 1, n)
    clk = rng.uniform(0.6, 1.0, n) * TPU_V5E.f_max_mhz
    series = ofu_series(tpa, clk)
    assert series.shape == (n,)
    for i in range(n):
        assert series[i] == pytest.approx(ofu_point(tpa[i], clk[i]))
    assert ofu_mean(tpa, clk) == pytest.approx(float(series.mean()))


@given(st.lists(st.tuples(st.sampled_from(_PRECS),
                          st.floats(1e6, 1e15)),
                min_size=1, max_size=4))
@settings(max_examples=50, deadline=None)
def test_effective_peak_bounded_by_component_peaks(mix):
    """Eq. 12: the harmonic mean can never leave [min, max] of the
    per-precision peaks present in the mix."""
    flops = {}
    for p, f in mix:
        flops[p] = flops.get(p, 0.0) + f
    peaks = [TPU_V5E.peak_tflops(p) for p in flops]
    eff = effective_peak(flops, TPU_V5E)
    assert min(peaks) - 1e-9 <= eff <= max(peaks) + 1e-9


@given(st.floats(0.01, 1.0), st.floats(1.0, 1e12),
       st.floats(1.0, 2.0), st.floats(1.0, 2.0))
@settings(max_examples=50, deadline=None)
def test_adjusted_ofu_monotonicity(ofu, th, k_prof, k_th):
    """Eq. 8: OFU_adj grows with theoretical FLOPs, shrinks as the
    hardware executes more padding, and never exceeds raw OFU when
    profiled >= theoretical (padding can only inflate the raw metric)."""
    prof = th * k_prof                     # profiled >= theoretical
    base = adjusted_ofu(ofu, th, prof)
    assert base <= ofu + 1e-12
    assert adjusted_ofu(ofu, th * k_th, prof) >= base - 1e-12
    assert adjusted_ofu(ofu, th, prof * k_th) <= base + 1e-12


@given(st.integers(0, 2 ** 31 - 1), st.integers(2, 100))
@settings(max_examples=50, deadline=None)
def test_pearson_r_bounded(seed, n):
    rng = np.random.default_rng(seed)
    a = rng.normal(size=n) * rng.uniform(0.1, 100)
    b = rng.normal(size=n) * rng.uniform(0.1, 100)
    assert -1.0 - 1e-9 <= pearson_r(a, b) <= 1.0 + 1e-9
    # degenerate series: zero variance must not divide by zero
    assert pearson_r(np.full(n, 3.0), b) == 0.0
    # perfect (anti-)correlation hits the bounds
    assert pearson_r(a, 2 * a + 1) == pytest.approx(1.0)
    assert pearson_r(a, -3 * a) == pytest.approx(-1.0)


@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=25, deadline=None)
def test_hist_percentile_matches_exact_on_fine_bins(seed):
    """The streaming-rollup readout must agree with np.percentile up to
    one bin width."""
    rng = np.random.default_rng(seed)
    vals = rng.uniform(0, 1, 500)
    edges = np.linspace(0, 1.1, 129)
    counts, _ = np.histogram(vals, edges)
    for q in (10, 50, 90):
        est = hist_percentile(edges, counts, q)
        assert abs(est - np.percentile(vals, q)) <= 1.1 / 128 + 1e-9
    assert np.isnan(hist_percentile(edges, np.zeros(128), 50))


def test_accuracy_stats():
    est = [10.0, 12.0, 20.0]
    tru = [11.0, 12.0, 15.0]
    assert mae(est, tru) == pytest.approx(2.0)
    assert pct_within(est, tru, 2.0) == pytest.approx(2 / 3)
    r = pearson_r([1, 2, 3, 4], [2, 4, 6, 8])
    assert r == pytest.approx(1.0)
    rep = AccuracyReport.build("ofu", est, tru)
    assert rep.within_5pp == 1.0
