"""Train steps of a DeepSeek-V3-block MoE decoder, one chip's share of an
expert-parallel deployment, through the program's `Trainer.step_fn`.

As `train.py` drives a dense decoder: set-up builds one `Trainer` and one
state from the seed and drives that same object through its first three
steps with the window's own feed (a fresh `synthetic_batch`, put on the
device, one step, one fetch of the loss and the pairs routed to the held
experts); the window keeps stepping it.  After the window the program's
state is freed and `bench/reference/mla_moe_lm.py` follows the first
three steps.  Compared, each where the configuration gives it a limit:
the losses; the first gradient as the optimizer took it and every leaf's
change over three steps (router bias included), each leaf by its norm;
and `dropped_pairs`: in step 1, the pairs the reference routes to held
experts that the program did not compute, where it falls shorter than
the reference's near-ties of routing (within the configuration's
`tie_margin` of score).  `control` prints every reading.
"""
from __future__ import annotations

import dataclasses
import math
import time

import numpy as np

from bench import generate, window
from bench.drivers.train import norm_gap
from bench.record import Compared, Run
from bench.reference import mla_moe_lm
from bench.spans import Spans

#: the configuration file's keys -> the program's ModelConfig fields
FIELDS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
          "num_attention_heads": "num_heads",
          "num_key_value_heads": "num_kv_heads",
          "num_hidden_layers": "num_layers", "vocab_size": "vocab_size",
          "tie_word_embeddings": "tie_embeddings", "hidden_act": "activation",
          "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
          "torch_dtype": "dtype", "moe_intermediate_size": "d_ff_expert",
          "n_shared_experts": "num_shared_experts",
          "num_experts_per_tok": "top_k",
          "first_k_dense_replace": "first_dense_layers",
          "kv_lora_rank": "kv_lora_rank", "qk_nope_head_dim": "qk_nope_dim",
          "qk_rope_head_dim": "qk_rope_dim", "v_head_dim": "v_head_dim",
          "routed_scaling_factor": "routed_scaling",
          "scoring_func": "router_score", "ep_size": "ep_size",
          "ep_rank": "ep_rank"}


def program_config(config: dict):
    """The program's ModelConfig for this configuration file: the router
    scores all n_routed_experts x ep_size experts, this chip holds
    n_routed_experts of them."""
    from repro.configs.base import get_config
    if not config["norm_topk_prob"]:
        raise ValueError("the program always renormalises the chosen "
                         "experts' weights")
    base = get_config(config["program_arch"])
    a = config["assumed"]
    return dataclasses.replace(
        base, **{f: config[k] for k, f in FIELDS.items()},
        num_experts=config["n_routed_experts"] * config["ep_size"],
        q_lora_rank=config["q_lora_rank"] or 0,
        head_dim=config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
        router_bias=config["topk_method"] == "noaux_tc",
        balance_alpha=a["balance_alpha"], bias_rate=a["bias_rate"])


def dropped_pairs(prog: dict, ref: dict, margin: float) -> float:
    """Step 1, where both sides route with the same weights and biases:
    the pairs the reference routes to held experts that the program did
    not compute.  A shortfall no larger than the reference's near-ties
    within `margin` (pairs the program's precision may route elsewhere)
    counts 0; a larger one counts whole.  Later steps route with weights
    updated in another precision and router biases whose +-gamma moves
    differ wherever an expert's load sits near the mean, so their routing
    parts by more than near-ties."""
    short = ref["held_pairs"][0] - prog["held_pairs"][0]
    return float(short if short > ref["held_ties"][0][margin] else 0)


def worst_leaves(prog: dict, ref: dict, n: int = 4) -> list:
    """The n leaves `norm_gap` reads worst, [path, gap, reference norm],
    and the median leaf's norm last."""
    median = float(np.median(list(ref.values())))
    gaps = sorted(((abs(prog[k] - r) / max(r, median), k, r)
                   for k, r in ref.items()), reverse=True)[:n]
    return [[k, g, r] for g, k, r in gaps] + [median]


def readings(prog: dict, ref: dict, config: dict) -> dict:
    losses = [abs(p - r) / abs(r) for p, r in zip(prog["losses"],
                                                  ref["losses"])]
    return {"loss_rel_gap": max(losses) if losses else math.inf,
            "grad1_norm_gap": norm_gap(prog["grad1"], ref["grad1"]),
            "delta3_norm_gap": norm_gap(prog["delta3"], ref["delta3"]),
            "dropped_pairs": dropped_pairs(prog, ref, config["tie_margin"])}


class Training:
    """One Trainer, its state, and the step the window drives."""

    def __init__(self, config: dict, steps, spans):
        import jax
        import jax.numpy as jnp
        from repro.configs.base import ShapeSpec
        from repro.core.peaks import TPU_V5E
        from repro.models import api as models
        from repro.optim import adamw
        from repro.train.steps import init_opt_state
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
        init = jax.jit(lambda key: (lambda p: (p, init_opt_state(
            self.opt_cfg, p)))(models.init_params(self.cfg, key)))
        self.params, self.opt = init(jax.random.key(steps.init_seed))
        self._jax, self._jnp = jax, jnp

    def step(self) -> tuple:
        """One step as Trainer.run takes it; returns (loss, pairs routed
        to the held experts), fetched together."""
        from repro.data.pipeline import synthetic_batch
        jnp = self._jnp
        with self.spans("data"):
            batch = synthetic_batch(self.cfg, self.shape, self.step_i,
                                    seed=self.data_seed)
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
        with self.spans("step"):
            self.params, self.opt, m = self.trainer.step_fn(
                self.params, self.opt, batch)
            loss, held = self._jax.device_get((m["loss"],
                                               m["moe_held_pairs"]))
        self.step_i += 1
        return float(loss), int(held)

    def first_steps(self) -> dict:
        """Steps 1-3, with the readings the reference is compared on.  The
        initial weights wait on the host, so the steps have the chip's
        memory they have in the window."""
        jax = self._jax
        b1 = self.opt_cfg.b1
        p0 = jax.device_get(self.params)
        out = [self.step()]
        mu = jax.tree.map(lambda s: s["m"], self.opt["mu"],
                          is_leaf=lambda s: isinstance(s, dict) and "m" in s)
        grad1 = {k: n / (1 - b1)
                 for k, n in mla_moe_lm.leaf_norms(mu).items()}
        out += [self.step(), self.step()]
        gap = jax.jit(lambda a, b: self._jnp.sqrt(self._jnp.sum(
            self._jnp.square(a.astype(np.float32) - b.astype(np.float32)))))
        flat, _ = jax.tree_util.tree_flatten_with_path(self.params)
        flat0 = jax.tree.leaves(p0)
        delta = {jax.tree_util.keystr(k): float(gap(x, x0))
                 for (k, x), x0 in zip(flat, flat0)}
        del p0, flat0
        return {"losses": [l for l, _ in out],
                "held_pairs": [h for _, h in out],
                "grad1": grad1, "delta3": delta}


def run(cell, seed: int, seconds: float, measured, t_start: float,
        device) -> Run:
    """Set up, measure for `seconds` between `measured.start()` and
    `measured.stop()`, then compare; `t_start` is when the process began."""
    import gc
    from bench import moe_flops
    config = cell.config
    steps = generate.make(cell.mix, seed)
    result = Run(peak=None)
    training = Training(config, steps, result.spans)
    prog = training.first_steps()

    result.spans.total_s.clear()
    result.spans.count.clear()
    out = []
    measured.start()
    t0 = time.perf_counter()
    result.setup_s = t0 - t_start
    with result.spans("window"):
        while True:
            out.append(training.step())
            te = time.perf_counter()
            if te - t0 >= seconds:
                break
    result.window_s = te - t0
    measured.stop()
    n = len(out)
    tokens = steps.batch * steps.seq
    result.attempted = n
    result.failed = int(sum(not math.isfinite(l) for l, _ in out))
    result.end_to_end = {
        "train_tokens_per_s": window.rate(n * tokens, result.window_s)}
    result.counters = {
        "steps": n, "moe_held_pairs": sum(h for _, h in out),
        "model_flops_per_step": moe_flops.train_flops(
            config, steps.batch, steps.seq),
        "gmm_flops_per_pair": moe_flops.gmm_flops_per_pair(config)}
    result.memory_peak_bytes = device.peak_bytes()

    del training
    gc.collect()
    ref = mla_moe_lm.three_steps(config, steps.batch, steps.seq,
                                 steps.init_seed, steps.data_seed)
    got = readings(prog, ref, config)
    got["nonfinite_losses"] = result.failed + sum(
        not math.isfinite(x) for x in prog["losses"])
    result.compared = [Compared(k, float(got[k]), float(limit))
                       for k, limit in config["limits"].items()]
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
        ref = mla_moe_lm.three_steps(*args)
        emit("program", seed, readings(prog, ref, c))
        emit("routing", seed, {
            "program_held": prog["held_pairs"],
            "reference_held": ref["held_pairs"],
            **{f"ties_at_{m:g}": [t[m] for t in ref["held_ties"]]
               for m in mla_moe_lm.TIE_MARGINS},
            **{f"dropped_at_{m:g}": dropped_pairs(prog, ref, m)
               for m in mla_moe_lm.TIE_MARGINS}})
        emit("leaves", seed, {k: worst_leaves(prog[k], ref[k])
                              for k in ("grad1", "delta3")})
        if seed in control_seeds:
            emit("control", seed, readings(
                mla_moe_lm.three_steps(*args, precision="fp8"), ref, c))
            emit("half_batch", seed, readings(mla_moe_lm.three_steps(
                *args, rows=steps.batch // 2), ref, c))
