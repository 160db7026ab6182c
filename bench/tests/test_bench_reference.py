"""The plain references agree with the program where both compute the
same thing: the same weights and batches from the seeds, the same loss,
the same bins, histograms and verdicts.  (The references import nothing
of the program; only these tests hold the two side by side.)"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.drivers.train import program_config
from bench.reference import dense_lm
from bench.reference import fleet as ref
from bench.tests import tiny


@pytest.fixture(scope="module")
def train_cfg():
    return tiny.train_cell().config


def test_init_matches_the_program_bit_for_bit(train_cfg):
    from repro.models import api as models
    prog = models.init_params(program_config(train_cfg), jax.random.key(99))
    ours = dense_lm.init_params(train_cfg, 99)
    flat_p = jax.tree_util.tree_flatten_with_path(prog)[0]
    flat_o = dict((jax.tree_util.keystr(k), v) for k, v in
                  jax.tree_util.tree_flatten_with_path(ours)[0])
    assert len(flat_p) == len(flat_o)
    for k, v in flat_p:
        assert v.dtype == jnp.bfloat16
        mine = flat_o[jax.tree_util.keystr(k)]
        assert mine.dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(v.astype(jnp.float32)),
                                      np.asarray(mine.astype(jnp.float32)))


def test_batches_match_the_program_pipeline(train_cfg):
    from repro.configs.base import ShapeSpec
    from repro.data.pipeline import synthetic_batch
    cfg = program_config(train_cfg)
    seed = tiny.BIG_SEED
    for step in (0, 5):
        b = synthetic_batch(cfg, ShapeSpec("x", 32, 4, "train"), step,
                            seed=seed)
        tok, lab = dense_lm.batch(train_cfg, 4, 32, step, seed)
        np.testing.assert_array_equal(b["tokens"], tok)
        np.testing.assert_array_equal(b["labels"], lab)


def test_loss_matches_the_program_in_float32(train_cfg):
    from repro.train.steps import loss_fn
    cfg32 = dataclasses.replace(program_config(train_cfg), dtype="float32")
    params = dense_lm.init_params(train_cfg, 3)
    params32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    tok, lab = dense_lm.batch(train_cfg, 4, 32, 0, 3)
    with jax.default_matmul_precision("highest"):
        want = float(loss_fn(cfg32, params32, {"tokens": jnp.asarray(tok),
                                             "labels": jnp.asarray(lab)})[0])
    got, _ = dense_lm.loss_and_grads(train_cfg, "f32", params, tok, lab)
    assert got == pytest.approx(want, rel=1e-5)


def test_bin_index_is_the_count_of_edges_at_or_below():
    cfg = tiny.fleet_cell().config
    edges = ref.edges32(cfg)
    rng = np.random.default_rng(0)
    v = np.concatenate([rng.uniform(-0.2, 1.3, 100_000).astype(np.float32),
                        edges, np.nextafter(edges, np.float32(-1)),
                        np.nextafter(edges, np.float32(2))])
    want = np.clip(np.searchsorted(edges, v, side="right") - 1, 0,
                   len(edges) - 2)
    np.testing.assert_array_equal(ref.bin_index(v, edges), want)


def test_job_hist_matches_the_program_oracle():
    from repro.kernels.fleet_hist import bucket_hist_ref
    cfg = tiny.fleet_cell().config
    rng = np.random.default_rng(1)
    tpa = rng.uniform(0, 1, (50, 120)).astype(np.float32)
    clock = rng.uniform(900, 1500, (50, 120)).astype(np.float32)
    hist, sums = ref.job_hist(tpa, clock, cfg)
    col = ref.col_bucket(120, cfg)
    h2, s2 = bucket_hist_ref(tpa, clock, inv_fmax=1 / 1500.0,
                             edges=np.linspace(0, 1.1, 129), col_bucket=col,
                             n_buckets=12)
    np.testing.assert_array_equal(hist, h2)
    np.testing.assert_allclose(sums, s2, rtol=1e-6)


@pytest.mark.parametrize("seed", range(5))
def test_regressions_match_the_program_detector(seed):
    from repro.fleet.regression import detect_regressions
    rng = np.random.default_rng(seed)
    ofu = rng.uniform(0.3, 0.4, 24)
    ofu[8 + seed:12 + seed] /= 2.5
    kw = dict(window=4, min_duration=2, factor_threshold=1.5)
    want = [(r.start_idx, r.end_idx) for r in detect_regressions(ofu, **kw)]
    assert ref.regressions(ofu, **kw) == want
    assert want


def test_column_duty_divides_the_columns_inside_the_event():
    cfg = tiny.fleet_cell().config
    ev = {"slowdown": 2.5, "start_s": 1800.0, "end_s": 2400.0}
    cols = ref.column_duty(0.3, ev, 120, cfg)
    want = np.full(120, 0.3)
    want[60:80] = 0.3 / 2.5
    np.testing.assert_allclose(cols, want)
    np.testing.assert_array_equal(ref.column_duty(0.3, None, 120, cfg),
                                  np.full(120, 0.3))
    with pytest.raises(ValueError, match="cuts a hardware window"):
        ref.column_duty(0.3, dict(ev, start_s=1810.0), 120, cfg)


@pytest.mark.parametrize("duty", [0.05, 0.3, 0.9])
def test_clock_moments_are_those_of_clipped_draws(duty):
    cfg = tiny.fleet_cell().config
    mu, sd, lo, hi = ref._clock_law(duty, cfg)
    x = np.clip(mu + sd * np.random.default_rng(5).standard_normal(
        4_000_000), lo, hi)
    m, var, m4 = ref.clock_moments(duty, cfg)
    n = x.size
    assert abs(x.mean() - m) < 5 * np.sqrt(var / n)
    assert abs(x.var() - var) < 5 * np.sqrt((m4 - var * var) / n)


def test_the_plain_generator_reads_as_the_stated_model():
    cfg = tiny.fleet_cell().config
    ev = {"slowdown": 2.5, "start_s": 1800.0, "end_s": 2400.0}
    cols = ref.column_duty(0.31, ev, 120, cfg)
    rng = np.random.default_rng(7)
    tpa, clock = ref.generate_job(cols, 2048, cfg, rng)
    got = ref.generate_stats(tpa, clock, cols, cfg)
    lo, hi = cfg["jitter_rel_sd_range"]
    assert got["tpa_mean_gap"] < 1e-5
    assert got["clock_moment_z"] < 5
    assert lo <= got["jitter_rel_sd"] <= hi
    again = ref.noise(*ref.generate_job(cols, 2048, cfg, rng), cols)
    first = ref.noise(tpa, clock, cols)
    assert ref.noise_corr_z(first, again) < 5
    assert ref.noise_corr_z(first, first) == pytest.approx(
        np.sqrt(tpa.size))


def test_the_stated_model_is_the_engines_default():
    from repro.core.peaks import DEFAULT_CHIP
    from repro.telemetry.clock import ClockModel
    from repro.telemetry.counters import MAX_HW_AVG_WINDOW_S, StepProfile
    cfg = tiny.fleet_cell().config
    cm, c = ClockModel(), cfg["simulated_clock"]
    assert (cm.theta, cm.sigma_mhz, cm.throttle_frac, cm.f_min_frac) == (
        c["theta_per_s"], c["sigma_mhz"], c["throttle_frac"],
        c["f_min_frac"])
    assert DEFAULT_CHIP.f_max_mhz == cfg["simulated_chip"]["f_max_mhz"]
    assert StepProfile(1.0, 2.0).jitter == cfg["step_jitter"]
    assert MAX_HW_AVG_WINDOW_S == cfg["hw_window_s"]
