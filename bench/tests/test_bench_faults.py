"""A run of each cell with its timed path broken underneath comes out not
correct, once for each fault the cell can have; the same run unbroken
comes out correct.  The harness's look for a chip is skipped: the drivers
run on the CPU at a tiny size."""
import numpy as np
import pytest

from bench.tests import tiny


def test_sound_fleet_run_is_correct():
    run = tiny.run(tiny.fleet_cell())
    assert run.correct, run.compared
    assert run.attempted >= 1 and run.failed == 0


def test_fleet_answer_altered_where_produced(monkeypatch):
    from repro.kernels import fleet_hist
    real = fleet_hist.ofu_bucket_hist

    def moved_one(*a, **kw):
        hist, sums = real(*a, **kw)
        return hist.at[0, 0].add(-1).at[0, 1].add(1), sums
    monkeypatch.setattr(fleet_hist, "ofu_bucket_hist", moved_one)
    run = tiny.run(tiny.fleet_cell())
    assert not run.correct
    bad = {c.name for c in run.compared if not c.ok}
    assert "hist_cells_differ" in bad


def test_fleet_fold_that_leaves_the_rollup_unchanged(monkeypatch):
    from repro.fleet.streaming import StreamingRollup
    monkeypatch.setattr(StreamingRollup, "observe_hist",
                        lambda self, *a, **kw: None)
    run = tiny.run(tiny.fleet_cell())
    assert not run.correct


def test_fleet_verdict_altered(monkeypatch):
    from repro.fleet import regression
    monkeypatch.setattr(regression, "detect_regressions",
                        lambda ofu, **kw: [])
    run = tiny.run(tiny.fleet_cell())
    bad = {c.name for c in run.compared if not c.ok}
    assert {"planted_missed", "verdicts_differ"} <= bad


def _bad(run):
    assert not run.correct
    return {c.name for c in run.compared if not c.ok}


def test_fleet_engine_without_clock_noise(monkeypatch):
    from repro.telemetry.clock import ClockModel
    real = ClockModel.ou_step_constants
    monkeypatch.setattr(ClockModel, "ou_step_constants",
                        lambda self, dt: (real(self, dt)[0], 0.0))
    assert "clock_moment_z" in _bad(tiny.run(tiny.fleet_cell()))


def test_fleet_engine_without_step_jitter(monkeypatch):
    from repro.fleet import jobs
    real = jobs.build_profile

    def steady(spec):
        prof, app, app_exact = real(spec)
        prof.jitter = 0.0
        return prof, app, app_exact
    monkeypatch.setattr(jobs, "build_profile", steady)
    assert "jitter_off_jobs" in _bad(tiny.run(tiny.fleet_cell()))


def test_fleet_engine_that_repeats_its_draws(monkeypatch):
    import jax
    from repro.fleet import engine_jax
    real = engine_jax._group_inputs

    def same_keys(*a, **kw):
        args, static = real(*a, **kw)
        key = jax.random.PRNGKey(0)
        return args[:9] + (key, jax.random.PRNGKey(1)), static
    monkeypatch.setattr(engine_jax, "_group_inputs", same_keys)
    assert "round_noise_corr_z" in _bad(tiny.run(tiny.fleet_cell()))


def test_fleet_engine_that_ignores_duty(monkeypatch):
    import numpy as np
    from repro.fleet import engine_jax
    real = engine_jax._group_inputs

    def flat_duty(*a, **kw):
        args, static = real(*a, **kw)
        return (np.full_like(args[0], 0.3),) + args[1:], static
    monkeypatch.setattr(engine_jax, "_group_inputs", flat_duty)
    assert "tpa_mean_gap" in _bad(tiny.run(tiny.fleet_cell()))


def test_fleet_engine_in_bfloat16(monkeypatch):
    import jax.numpy as jnp
    from repro.fleet import engine_jax
    real = engine_jax._group_device_sim

    def bf16(*a, **kw):
        return tuple(x.astype(jnp.bfloat16).astype(jnp.float32)
                     for x in real(*a, **kw))
    monkeypatch.setattr(engine_jax, "_group_device_sim", bf16)
    assert _bad(tiny.run(tiny.fleet_cell())) & {"tpa_mean_gap",
                                                "jitter_off_jobs"}


def test_sound_train_run_is_correct():
    run = tiny.run(tiny.train_cell())
    assert run.correct, run.compared
    assert run.attempted >= 1 and run.failed == 0


def test_train_step_that_returns_its_state_unchanged(monkeypatch):
    from repro.train import trainer
    real = trainer.make_train_step

    def frozen(*a, **kw):
        step = real(*a, **kw)

        def same_state(params, opt_state, batch):
            _, _, metrics = step(params, opt_state, batch)
            return params, opt_state, metrics
        return same_state
    monkeypatch.setattr(trainer, "make_train_step", frozen)
    run = tiny.run(tiny.train_cell())
    got = {c.name: c.value for c in run.compared}
    assert not run.correct
    assert got["delta3_norm_gap"] == pytest.approx(1.0)
    assert got["grad1_norm_gap"] == pytest.approx(1.0)


def test_train_step_that_leaves_half_the_batch_out(monkeypatch):
    from repro.train import steps
    real = steps.loss_fn

    def half(cfg, params, batch, ctx=None):
        B = batch["tokens"].shape[0]
        return real(cfg, params, {k: v[:B // 2] for k, v in batch.items()},
                    ctx)
    monkeypatch.setattr(steps, "loss_fn", half)
    run = tiny.run(tiny.train_cell())
    assert not run.correct


def test_train_token_altered_where_produced(monkeypatch):
    from repro.data import pipeline
    real = pipeline.synthetic_batch

    def shifted(*a, **kw):
        b = real(*a, **kw)
        b["tokens"] = np.roll(b["tokens"], 1, axis=1)
        return b
    monkeypatch.setattr(pipeline, "synthetic_batch", shifted)
    run = tiny.run(tiny.train_cell())
    assert not run.correct
