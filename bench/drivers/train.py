"""Train steps of a dense decoder through the program's `Trainer.step_fn`.

Set-up builds one `Trainer` and one state from the seed, then drives that
same object through its first three steps with the window's own call and
feed: a fresh `synthetic_batch`, put on the device, one step, a wait on
the loss.  Those steps also warm up every shape.  The window then keeps
stepping the same object.  After the window the program's state is freed
and the plain reference follows the first three steps; the losses, the
first gradient as the optimizer took it and the weights' change over the
three steps are compared, each leaf by its norm.
"""
from __future__ import annotations

import dataclasses
import math
import time

import numpy as np

from bench import flops, generate, window
from bench.record import Compared, Run
from bench.reference import dense_lm
from bench.spans import Spans

#: the configuration file's keys -> the program's ModelConfig fields
FIELDS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
          "num_attention_heads": "num_heads",
          "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
          "num_hidden_layers": "num_layers", "vocab_size": "vocab_size",
          "tie_word_embeddings": "tie_embeddings", "hidden_act": "activation",
          "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
          "torch_dtype": "dtype"}


def program_config(config: dict):
    """The program's ModelConfig for this configuration file."""
    from repro.configs.base import get_config
    base = get_config(config["program_arch"])
    return dataclasses.replace(
        base, **{f: config[k] for k, f in FIELDS.items()})


def norm_gap(program: dict, reference: dict) -> float:
    """Worst leaf's gap between the program's norm and the reference's,
    over the reference's norm of that leaf or of the median leaf,
    whichever is larger."""
    if set(program) != set(reference):
        return math.inf
    median = float(np.median(list(reference.values())))
    return max(abs(program[k] - r) / max(r, median)
               for k, r in reference.items())


def readings(prog: dict, ref: dict) -> dict:
    losses = [abs(p - r) / abs(r) for p, r in zip(prog["losses"],
                                                  ref["losses"])]
    return {"loss_rel_gap": max(losses) if losses else math.inf,
            "grad1_norm_gap": norm_gap(prog["grad1"], ref["grad1"]),
            "delta3_norm_gap": norm_gap(prog["delta3"], ref["delta3"])}


class Training:
    """One Trainer, its state, and the step the window drives."""

    def __init__(self, config: dict, steps, spans):
        import jax
        import jax.numpy as jnp
        from repro.configs.base import ShapeSpec
        from repro.core.peaks import TPU_V5E
        from repro.models import api as models
        from repro.optim import adamw
        from repro.train.trainer import TrainConfig, Trainer
        self.cfg = program_config(config)
        self.shape = ShapeSpec("bench", steps.seq, steps.batch, "train")
        self.opt_cfg = adamw.OptConfig(**config["optimizer"])
        # the Trainer's chip only feeds its own counter model, which the
        # window does not run; the benchmark's peaks are in bench/peaks.py
        self.trainer = Trainer(
            self.cfg, self.shape, opt_cfg=self.opt_cfg,
            train_cfg=TrainConfig(seed=steps.init_seed, chip=TPU_V5E))
        self.data_seed, self.spans, self.step_i = steps.data_seed, spans, 0
        init = jax.jit(lambda key: (lambda p: (p, adamw.init(
            self.opt_cfg, p)))(models.init_params(self.cfg, key)))
        self.params, self.opt = init(jax.random.key(steps.init_seed))
        self._jnp = jnp

    def step(self) -> float:
        """One step as Trainer.run takes it; returns the loss."""
        from repro.data.pipeline import synthetic_batch
        jnp = self._jnp
        with self.spans("data"):
            batch = synthetic_batch(self.cfg, self.shape, self.step_i,
                                    seed=self.data_seed)
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
        with self.spans("step"):
            self.params, self.opt, m = self.trainer.step_fn(
                self.params, self.opt, batch)
            loss = float(m["loss"])
        self.step_i += 1
        return loss

    def first_steps(self) -> dict:
        """Steps 1-3, with the readings the reference is compared on."""
        import jax
        b1 = self.opt_cfg.b1
        p0 = jax.tree.map(self._jnp.copy, self.params)
        losses = [self.step()]
        mu = jax.tree.map(lambda s: s["m"], self.opt["mu"],
                          is_leaf=lambda s: isinstance(s, dict) and "m" in s)
        grad1 = {k: n / (1 - b1) for k, n in dense_lm.leaf_norms(mu).items()}
        losses += [self.step(), self.step()]
        delta = jax.jit(lambda a, b: jax.tree.map(
            lambda x, y: x.astype(np.float32) - y.astype(np.float32), a, b))(
                self.params, p0)
        del p0
        return {"losses": losses, "grad1": grad1,
                "delta3": dense_lm.leaf_norms(delta)}


def run(cell, seed: int, seconds: float, measured, t_start: float,
        device) -> Run:
    """Set up, measure for `seconds` between `measured.start()` and
    `measured.stop()`, then compare; `t_start` is when the process began."""
    import gc
    config = cell.config
    steps = generate.make(cell.mix, seed)
    result = Run(peak=None)
    training = Training(config, steps, result.spans)
    prog = training.first_steps()

    result.spans.total_s.clear()
    result.spans.count.clear()
    losses = []
    measured.start()
    t0 = time.perf_counter()
    result.setup_s = t0 - t_start
    with result.spans("window"):
        while True:
            losses.append(training.step())
            te = time.perf_counter()
            if te - t0 >= seconds:
                break
    result.window_s = te - t0
    measured.stop()
    n = len(losses)
    tokens = steps.batch * steps.seq
    result.attempted = n
    result.failed = int(sum(not math.isfinite(x) for x in losses))
    result.end_to_end = {
        "train_tokens_per_s": window.rate(n * tokens, result.window_s)}
    result.counters = {
        "steps": n, "model_flops_per_step": flops.dense_train_flops(
            config, steps.batch, steps.seq)}
    result.memory_peak_bytes = device.peak_bytes()

    del training
    gc.collect()
    ref = dense_lm.three_steps(config, steps.batch, steps.seq,
                               steps.init_seed, steps.data_seed)
    got = readings(prog, ref)
    got["nonfinite_losses"] = result.failed + sum(
        not math.isfinite(x) for x in prog["losses"])
    result.compared = [Compared(k, float(v), float(config["limits"][k]))
                       for k, v in got.items()]
    return result


def control(cell, seeds, control_seeds, emit) -> None:
    """Program readings for each seed; for each control seed those of the
    control (the reference in fp8 in the program's place) and of a step
    that leaves half of its batch out."""
    import gc
    c = cell.config
    for seed in seeds:
        steps = generate.make(cell.mix, seed)
        training = Training(c, steps, Spans())
        prog = training.first_steps()
        del training
        gc.collect()
        args = (c, steps.batch, steps.seq, steps.init_seed, steps.data_seed)
        ref = dense_lm.three_steps(*args)
        emit("program", seed, readings(prog, ref))
        if seed in control_seeds:
            emit("control", seed, readings(
                dense_lm.three_steps(*args, precision="fp8"), ref))
            emit("half_batch", seed, readings(
                dense_lm.three_steps(*args, rows=steps.batch // 2), ref))
