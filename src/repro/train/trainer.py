"""Fault-tolerant trainer loop with OFU-driven recovery.

Closes the paper's §VI loop end-to-end:
  train step -> step timing -> telemetry (simulated counter backend here,
  TPU backend in deploy) -> scrape -> job OFU -> RecoveryService -> on
  sustained collapse, restart from the latest atomic checkpoint.

Also handles straight crash-recovery (resume from checkpoint + deterministic
data stream) and supports fault injection for the integration tests.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Optional

import jax
import numpy as np

from repro.configs.base import ModelConfig, ShapeSpec
from repro.core.ofu import ofu_point
from repro.core.peaks import ChipSpec, device_chip
from repro.data.pipeline import synthetic_batch
from repro.fleet.recovery import RecoveryService, StragglerMonitor
from repro.models import api as models
from repro.optim import adamw
from repro.train import checkpoint as ckpt
from repro.train.steps import make_train_step


@dataclass
class TrainConfig:
    total_steps: int = 100
    ckpt_every: int = 20
    ckpt_dir: str = "/tmp/repro_ckpt"
    keep: int = 3
    seed: int = 0
    log_every: int = 10
    #: the chip whose counters the monitor models; None = the device this
    #: runs on, looked up by its device_kind (an unknown kind raises)
    chip: Optional[ChipSpec] = None
    # OFU monitoring
    monitor: bool = True
    scrape_every_steps: int = 5
    # resilience
    max_restarts: int = 3


@dataclass
class StepTelemetry:
    """What the (real or simulated) counters say about recent steps."""

    step: int
    step_time_s: float
    tpa: float
    clock_mhz: float

    @property
    def ofu(self) -> float:
        return ofu_point(self.tpa, self.clock_mhz)


class Trainer:
    def __init__(self, cfg: ModelConfig, shape: ShapeSpec,
                 opt_cfg: Optional[adamw.OptConfig] = None,
                 train_cfg: Optional[TrainConfig] = None,
                 ctx=None,
                 fault_hook: Optional[Callable[[int], None]] = None,
                 flops_per_step: Optional[float] = None):
        self.cfg = cfg
        self.shape = shape
        self.opt_cfg = opt_cfg or adamw.OptConfig(warmup_steps=10,
                                                  decay_steps=1000)
        self.tc = train_cfg or TrainConfig()
        self.chip = self.tc.chip or device_chip()
        self.ctx = ctx
        self.fault_hook = fault_hook
        self.flops_per_step = flops_per_step
        self.step_fn = jax.jit(make_train_step(cfg, self.opt_cfg, ctx),
                               donate_argnums=(0, 1))
        self.recovery = RecoveryService(factor_threshold=2.0,
                                        sustain_samples=3,
                                        cooldown_samples=6)
        self.stragglers = StragglerMonitor()
        self.history: list[StepTelemetry] = []
        self.restarts = 0

    # ------------------------------------------------------------------
    @staticmethod
    def _device_put(tree):
        """Checkpoint restores give host numpy; donated jit args need
        committed jax.Arrays."""
        import jax.numpy as jnp
        return jax.tree.map(jnp.asarray, tree)

    def _init_state(self):
        params = models.init_params(self.cfg, jax.random.key(self.tc.seed))
        opt_state = adamw.init(self.opt_cfg, params)
        return params, opt_state

    def _telemetry(self, step: int, dt: float) -> StepTelemetry:
        """Model counter readings from the measured step time: the duty
        cycle the chip would show, mxu_time = flops / peak.  No hardware
        counter is read here yet, on any backend.
        """
        if self.flops_per_step:
            mxu_t = self.flops_per_step / (self.chip.peak_tflops() * 1e12)
        else:
            mxu_t = 0.35 * dt
        tpa = min(1.0, mxu_t / max(dt, 1e-9))
        clock = self.chip.f_max_mhz * (1 - 0.115 * tpa)
        return StepTelemetry(step, dt, tpa, clock)

    # ------------------------------------------------------------------
    def run(self, start_step: Optional[int] = None) -> dict:
        tc = self.tc
        params, opt_state = self._init_state()
        step = 0
        latest = ckpt.latest_step(tc.ckpt_dir)
        if start_step is None and latest is not None:
            params = self._device_put(
                ckpt.restore(tc.ckpt_dir, params, latest))
            opt_state = self._device_put(
                ckpt.restore(tc.ckpt_dir + "/opt", opt_state, latest))
            step = latest
        elif start_step:
            step = start_step

        metrics_log = []
        while step < tc.total_steps:
            try:
                if self.fault_hook is not None:
                    self.fault_hook(step)
                batch = synthetic_batch(self.cfg, self.shape, step,
                                        seed=tc.seed)
                batch = {k: jax.numpy.asarray(v) for k, v in batch.items()}
                t0 = time.perf_counter()
                params, opt_state, m = self.step_fn(params, opt_state, batch)
                jax.block_until_ready(m["loss"])
                dt = time.perf_counter() - t0
                step += 1

                tel = self._telemetry(step, dt)
                self.history.append(tel)
                if tc.monitor and step % tc.scrape_every_steps == 0:
                    action = self.recovery.observe("train", tel.ofu)
                    if action is not None:
                        raise _RecoveryRestart(action.reason)
                if step % tc.log_every == 0:
                    metrics_log.append(
                        {"step": step,
                         "loss": float(m["loss"]),
                         "ofu": tel.ofu,
                         "step_time_s": dt})
                if step % tc.ckpt_every == 0 or step == tc.total_steps:
                    ckpt.save(tc.ckpt_dir, step, params, keep=tc.keep)
                    ckpt.save(tc.ckpt_dir + "/opt", step, opt_state,
                              keep=tc.keep)
            except _RecoveryRestart as e:
                self.restarts += 1
                if self.restarts > tc.max_restarts:
                    raise RuntimeError("restart budget exhausted") from e
                latest = ckpt.latest_step(tc.ckpt_dir)
                params, opt_state = self._init_state()
                if latest is not None:
                    params = self._device_put(
                        ckpt.restore(tc.ckpt_dir, params, latest))
                    opt_state = self._device_put(
                        ckpt.restore(tc.ckpt_dir + "/opt", opt_state,
                                     latest))
                    step = latest
                else:
                    step = 0
            except KeyboardInterrupt:
                raise

        return {"final_step": step, "metrics": metrics_log,
                "restarts": self.restarts,
                "final_loss": metrics_log[-1]["loss"] if metrics_log
                else None}


class _RecoveryRestart(Exception):
    pass
