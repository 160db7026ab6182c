"""The MoE router of the DeepSeek-V3 block (sigmoid scores, a correction
bias that only steers the choice, renormalised and scaled weights) and
the bias's life beside the optimizer: no moments, no decay, one step of
bias_rate toward balance after each train step."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, make_inputs
from repro.configs.base import ShapeSpec
from repro.models import forward, init_params
from repro.models import moe
from repro.optim import adamw
from repro.train.steps import (init_opt_state, make_train_step,
                               split_bias, with_bias)


def _cfg(**kw):
    return dataclasses.replace(get_config("moonlight-16b-a3b").smoke(), **kw)


def test_sigmoid_router_weights_are_the_chosen_scores_renormalised():
    cfg = _cfg()
    p = moe.moe_init(jax.random.key(0), cfg, jnp.float32)
    p = dict(p, router_bias=jnp.arange(cfg.num_experts, dtype=jnp.float32))
    x = jax.random.normal(jax.random.key(1), (2, 8, cfg.d_model))
    scores, idx, w = moe.route(cfg, p, x)
    want = jax.nn.sigmoid(x @ p["router"])
    np.testing.assert_allclose(np.asarray(scores), np.asarray(want),
                               rtol=1e-6)
    # the bias (largest on the last experts) picks them for every token
    assert sorted(np.unique(np.asarray(idx)).tolist()) == list(
        range(cfg.num_experts - cfg.top_k, cfg.num_experts))
    chosen = jnp.take_along_axis(want, idx, -1)
    np.testing.assert_allclose(
        np.asarray(w), np.asarray(chosen / chosen.sum(-1, keepdims=True)
                                  * cfg.routed_scaling), rtol=1e-6)


def test_the_bias_steps_toward_balance_outside_adamw():
    cfg = _cfg(balance_alpha=1e-4, bias_rate=1e-3)
    params = init_params(cfg, jax.random.key(0))
    opt_cfg = adamw.OptConfig(peak_lr=0.05, warmup_steps=1, decay_steps=10)
    opt = init_opt_state(opt_cfg, params)
    assert "router_bias" not in opt["mu"]["moe_layers"]["mlp"]
    batch = {k: jnp.asarray(v) for k, v in make_inputs(
        cfg, ShapeSpec("t", 32, 2, "train")).items()}
    step = jax.jit(make_train_step(cfg, opt_cfg))
    new, _, m = step(params, opt, batch)
    bias = np.asarray(new["moe_layers"]["mlp"]["router_bias"])
    # the step's loads, as the forward pass saw them
    _, stats = forward(cfg, params, batch, return_stats=True)
    load = np.asarray(stats["load"])
    want = 1e-3 * np.sign(load.mean(-1, keepdims=True) - load)
    np.testing.assert_allclose(bias, want, atol=1e-9)
    assert np.abs(bias).sum() > 0
    assert int(m["moe_held_pairs"]) == 2 * 32 * cfg.top_k  # ep_size 1
    assert float(m["balance_loss"]) > 0
    assert "moe_load" not in m


def test_split_bias_round_trips():
    tree = {"a": 1, "moe_layers": {"mlp": {"router_bias": 2, "w": 3},
                                   "attn": 4}}
    train, bias = split_bias(tree)
    assert train == {"a": 1, "moe_layers": {"mlp": {"w": 3}, "attn": 4}}
    assert bias == 2 and with_bias(train, bias) == tree
    assert split_bias({"a": 1}) == ({"a": 1}, None)
