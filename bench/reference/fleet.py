"""Plain NumPy reference of the fleet monitor's generate, histogram and
detect layers.  It imports nothing of the program.

Generate: the stated generative model of a job's counters.  Each scrape
column's true duty is the job's, divided by the slowdown where the column's
hardware window lies inside the planted event; a sample's tpa is that duty
times a lognormal step jitter, its clock an Ornstein-Uhlenbeck process
around f_max * (1 - throttle * duty), clipped to [f_min, f_max], which at a
30 s scrape (exp(-theta * 30) ~ 1e-26) draws each sample afresh.  The
program's draws are its own, so a grid is held to the model by its
statistics: each duty group's tpa mean and spread, and its clock's mean
and variance against the clipped normal's, in standard errors.
`generate_job` is the plain generator of the same model, which the
control puts in the program's place.

Histogram and detect: what a round's rollup and verdict must be, from the
round's raw counters.

OFU is tpa * clock * (1 / f_max) in float32, the telemetry's type; a
sample's bin is the number of bin edges (float32) at or below its OFU,
less one, clipped to the bins; a sample at time t = (column + 1) * scrape
interval lies in bucket ceil(t / bucket) - 1.  Sums are float64 sums of
the float32 samples.  The detectors are the paper's rolling-window
regression rule and the rel-error divergence rule, written out again.
"""
from __future__ import annotations

import numpy as np

#: numerical types the control may put in the program's place
BF16 = "bfloat16"


def edges32(config: dict) -> np.ndarray:
    return np.linspace(config["ofu_lo"], config["ofu_hi"],
                       config["bins"] + 1).astype(np.float32)


def col_bucket(n_cols: int, config: dict) -> np.ndarray:
    t = (np.arange(n_cols) + 1.0) * config["scrape_interval_s"]
    return np.maximum(np.ceil(t / config["bucket_s"]).astype(np.int64) - 1,
                      0)


def ofu_samples(tpa, clock, config: dict, precision: str = "float32"):
    """Per-sample OFU; `precision="bfloat16"` rounds every operand and
    product to bfloat16, as the control computes it."""
    inv = np.float32(1.0 / config["simulated_chip"]["f_max_mhz"])
    tpa = np.asarray(tpa, np.float32)
    clock = np.asarray(clock, np.float32)
    if precision == BF16:
        import ml_dtypes
        r = lambda x: np.asarray(x, ml_dtypes.bfloat16).astype(np.float32)
        return r(r(r(tpa) * r(clock)) * r(inv))
    return tpa * clock * inv


def bin_index(v: np.ndarray, edges: np.ndarray) -> np.ndarray:
    """Count of edges <= v, less one, clipped to the bins: a first guess
    from the value, then exact float32 comparisons with the edges."""
    bins = len(edges) - 1
    lo, hi = float(edges[0]), float(edges[-1])
    k = np.floor((v.astype(np.float64) - lo) / (hi - lo) * bins)
    k = np.clip(k, 0, bins - 1).astype(np.int64)
    for _ in range(2):
        k = np.clip(np.where(v < edges[k], k - 1, k), 0, bins - 1)
        k = np.clip(np.where(v >= edges[k + 1], k + 1, k), 0, bins - 1)
    return k


def job_hist(tpa, clock, config: dict, precision: str = "float32"):
    """(hist (buckets, bins) int64, sums (buckets,) float64) of one job."""
    ofu = ofu_samples(tpa, clock, config, precision)
    edges = edges32(config)
    bins = len(edges) - 1
    cb = col_bucket(ofu.shape[1], config)
    n_b = int(cb[-1]) + 1
    seg = np.broadcast_to(cb[None, :], ofu.shape).ravel()
    hist = np.bincount(seg * bins + bin_index(ofu.ravel(), edges),
                       minlength=n_b * bins).reshape(n_b, bins)
    sums = np.bincount(seg, weights=ofu.ravel().astype(np.float64),
                       minlength=n_b)
    return hist, sums


def _ffill(mean: np.ndarray) -> np.ndarray:
    out = mean.copy()
    good = ~np.isnan(out)
    if not good.any():
        return out
    last = out[int(np.argmax(good))]
    for i in range(len(out)):
        if np.isnan(out[i]):
            out[i] = last
        else:
            last = out[i]
    return out


def regressions(ofu, *, window: int, min_duration: int,
                factor_threshold: float) -> list:
    """[(start, end or None)] of sustained drops below the trailing
    healthy mean by more than factor_threshold."""
    ofu = [float(x) for x in ofu]
    out, ref, start = [], None, None
    for i in range(len(ofu)):
        w = ofu[max(0, i - window):i + 1]
        tail = w[-min(len(w), min_duration):]
        cur = sum(tail) / len(tail)
        if ref is None and i >= window:
            ref = sum(ofu[:window]) / window
        if ref is None:
            continue
        if start is None:
            if cur < ref / factor_threshold:
                start = i - min_duration + 1
            else:
                ref = 0.9 * ref + 0.1 * cur
        elif cur > ref / factor_threshold:
            out.append((start, i))
            start = None
    if start is not None:
        out.append((start, None))
    return out


def verdict(hists: dict, app_mfu: dict, config: dict):
    """({job: [(start, end)]}, sorted flagged jobs) from per-job
    (hist, sums), as the plain detectors judge the exact rollup."""
    reg, flagged = {}, []
    rk, dk = config["regression"], config["divergence"]
    for job, (hist, sums) in sorted(hists.items()):
        w = hist.sum(axis=1).astype(np.float64)
        with np.errstate(invalid="ignore", divide="ignore"):
            mean = np.where(w > 0, sums / np.maximum(w, 1e-12), np.nan)
        spans = regressions(_ffill(mean), **rk)
        if spans:
            reg[job] = spans
        ofu = float(sums.sum() / max(w.sum(), 1e-12))
        mfu = app_mfu[job]
        rel = abs(mfu - ofu) / max(ofu, 1e-6)
        if ofu >= dk["ofu_floor"] and rel > dk["flag_rel_err"]:
            flagged.append(job)
    return reg, sorted(flagged)


def column_duty(duty: float, event: dict | None, n_cols: int,
                config: dict) -> np.ndarray:
    """(n_cols,) true duty of each scrape column.  An event has to cover
    whole hardware windows, so that the model states each column's duty."""
    d = np.full(n_cols, float(duty))
    if event:
        t_end = (np.arange(n_cols) + 1.0) * config["scrape_interval_s"]
        lo = t_end - min(config["scrape_interval_s"], config["hw_window_s"])
        inside = (lo >= event["start_s"]) & (t_end <= event["end_s"])
        touch = (lo < event["end_s"]) & (t_end > event["start_s"])
        if (touch & ~inside).any():
            raise ValueError("the event cuts a hardware window")
        d[inside] /= event["slowdown"]
    return d


def _clock_law(duty: float, config: dict):
    """(mu, sd, f_min, f_max) of one clock sample at a column's duty."""
    c, f_max = config["simulated_clock"], config["simulated_chip"]["f_max_mhz"]
    a = np.exp(-c["theta_per_s"] * config["scrape_interval_s"])
    if a > 1e-9:
        raise ValueError("clock samples are not independent at this "
                         "scrape interval; the moments below assume so")
    sd = c["sigma_mhz"] * np.sqrt(1.0 - a * a)
    return (f_max * (1.0 - c["throttle_frac"] * duty), sd,
            c["f_min_frac"] * f_max, f_max)


def clock_moments(duty: float, config: dict) -> tuple:
    """(mean, variance, fourth central moment) of clip(mu + sd * Z,
    f_min, f_max): point masses at the clips, the normal between them."""
    from math import erf, erfc, sqrt
    mu, sd, lo, hi = _clock_law(duty, config)
    al, be = (lo - mu) / sd, (hi - mu) / sd
    z = np.linspace(al, be, 8001)
    w = np.exp(-0.5 * z * z) / np.sqrt(2 * np.pi)
    x = mu + sd * z
    p_lo, p_hi = 0.5 * (1 + erf(al / sqrt(2))), 0.5 * erfc(be / sqrt(2))
    dz = z[1] - z[0]

    def expect(g):
        y = g(x) * w
        return (g(lo) * p_lo + g(hi) * p_hi
                + dz * (y.sum() - 0.5 * (y[0] + y[-1])))

    m = expect(lambda v: v)
    var = expect(lambda v: (v - m) ** 2)
    return m, var, expect(lambda v: (v - m) ** 4)


def generate_stats(tpa, clock, cols: np.ndarray, config: dict) -> dict:
    """How far one job's grid lies from the stated model: the worst duty
    group's relative gap of the tpa mean, the tpa's relative spread (the
    step jitter), and the worst standard-error distance of the clock's
    mean and variance."""
    rel = np.asarray(tpa, np.float64) / cols[None, :]
    clock = np.asarray(clock, np.float64)
    jitter = float(rel.std())
    bias = np.exp(0.5 * jitter * jitter)        # lognormal mean
    gap = z = 0.0
    for d in np.unique(cols):
        c = cols == d
        gap = max(gap, abs(float(rel[:, c].mean()) / bias - 1.0))
        x = clock[:, c]
        n = x.size
        m, var, m4 = clock_moments(float(d), config)
        got_m = float(x.mean())
        got_var = float(np.mean((x - got_m) ** 2))
        z = max(z, abs(got_m - m) / np.sqrt(var / n),
                abs(got_var - var) / np.sqrt(max(m4 - var * var, 0.0) / n))
    return {"tpa_mean_gap": gap, "jitter_rel_sd": jitter,
            "clock_moment_z": z}


def noise(tpa, clock, cols: np.ndarray) -> tuple:
    """A job's draws as deviations from their duty group's mean, flat,
    for the check that two rounds draw afresh."""
    tpa = np.asarray(tpa, np.float64) / cols[None, :]
    clock = np.asarray(clock, np.float64)
    out = []
    for x in (tpa, clock):
        dev = np.empty_like(x)
        for d in np.unique(cols):
            c = cols == d
            dev[:, c] = x[:, c] - x[:, c].mean()
        out.append(dev.ravel())
    return tuple(out)


def noise_corr_z(a: tuple, b: tuple) -> float:
    """Worst correlation, in standard errors (sqrt(n)), of two rounds'
    draws of one job; independent draws read about 1."""
    z = 0.0
    for x, y in zip(a, b):
        den = np.sqrt(np.dot(x, x) * np.dot(y, y))
        corr = float(np.dot(x, y) / den) if den > 0 else 1.0
        z = max(z, abs(corr) * np.sqrt(x.size))
    return z


def generate_job(cols: np.ndarray, n_dev: int, config: dict, rng,
                 precision: str = "float32") -> tuple:
    """(tpa, clock) of one job drawn from the stated model: the jitter at
    the geometric middle of its stated range; `precision="bfloat16"`
    rounds every sample to bfloat16, as the control computes it."""
    n_min, n_max = config["jitter_samples"]
    jitter = config["step_jitter"] / np.sqrt(n_min * n_max)
    S = len(cols)
    tpa = cols[None, :] * np.exp(jitter * rng.standard_normal((n_dev, S)))
    clock = np.empty((n_dev, S))
    for d in np.unique(cols):
        c = cols == d
        mu, sd, lo, hi = _clock_law(float(d), config)
        clock[:, c] = np.clip(mu + sd * rng.standard_normal(
            (n_dev, int(c.sum()))), lo, hi)
    tpa, clock = tpa.astype(np.float32), clock.astype(np.float32)
    if precision == BF16:
        import ml_dtypes
        r = lambda x: x.astype(ml_dtypes.bfloat16).astype(np.float32)
        tpa, clock = r(tpa), r(clock)
    return tpa, clock


def sums_rel_err(program: np.ndarray, reference: np.ndarray) -> float:
    ref = np.asarray(reference, np.float64)
    gap = np.abs(np.asarray(program, np.float64) - ref)
    rel = gap / np.maximum(np.abs(ref), 1e-300)
    return float(rel.max()) if rel.size else 0.0
