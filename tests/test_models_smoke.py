"""Per-architecture smoke tests: REDUCED same-family config, one forward +
one train step + one decode step on CPU; asserts shapes + no NaNs.
(The FULL configs are exercised only via the dry-run.)"""
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config, list_configs, make_inputs
from repro.configs.base import ShapeSpec
from repro.models import decode_step, forward, init_params
from repro.optim import adamw
from repro.train.steps import init_opt_state, make_train_step

ARCHS = ["deepseek-moe-16b", "deepseek-v3-671b", "qwen3-4b",
         "nemotron-4-340b", "granite-3-2b", "llama3.2-3b", "whisper-small",
         "phi-3-vision-4.2b", "mamba2-780m", "zamba2-7b",
         "moonlight-16b-a3b"]


def test_all_assigned_archs_registered():
    assert sorted(ARCHS) == list_configs()


_CACHE: dict = {}


def _state(arch):
    if arch not in _CACHE:
        cfg = get_config(arch).smoke()
        _CACHE[arch] = (cfg, init_params(cfg, jax.random.key(0)))
    return _CACHE[arch]


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_smoke(arch):
    cfg, params = _state(arch)
    batch = make_inputs(cfg, ShapeSpec("t", 32, 2, "train"))
    logits = forward(cfg, params, batch)
    assert logits.shape == (2, 32, cfg.vocab_size)
    assert not jnp.isnan(logits.astype(jnp.float32)).any()


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_smoke(arch):
    cfg, params = _state(arch)
    batch = make_inputs(cfg, ShapeSpec("d", 16, 2, "decode"))
    logits, caches = decode_step(cfg, params, batch)
    assert logits.shape == (2, 1, cfg.vocab_size)
    assert not jnp.isnan(logits.astype(jnp.float32)).any()
    for k, v in caches.items():
        assert not jnp.isnan(v.astype(jnp.float32)).any(), k


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_smoke(arch):
    cfg, params = _state(arch)
    # lr large enough that one update survives bf16 weight quantization
    opt_cfg = adamw.OptConfig(peak_lr=0.05, warmup_steps=1, decay_steps=10)
    opt_state = init_opt_state(opt_cfg, params)
    step = jax.jit(make_train_step(cfg, opt_cfg))
    batch = {k: jnp.asarray(v)
             for k, v in make_inputs(cfg, ShapeSpec("t", 32, 2, "train")).items()}
    new_params, new_opt, m = step(params, opt_state, batch)
    assert jnp.isfinite(m["loss"])
    assert float(m["grad_norm"]) > 0
    # params must actually change
    moved = any(
        float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max()) > 0
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(new_params))
        if jnp.issubdtype(a.dtype, jnp.floating))  # note: bf16 kind is 'V'
    assert moved


def test_decode_matches_forward_incrementally():
    """Greedy decode over a cached prefix must agree with full forward
    logits at the same position (dense smoke config)."""
    cfg = get_config("granite-3-2b").smoke()
    params = init_params(cfg, jax.random.key(1))
    import numpy as np
    rng = np.random.default_rng(3)
    T = 8
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, T)), jnp.int32)

    from repro.configs.base import cache_specs
    caches = {k: jnp.zeros(v.shape, v.dtype)
              for k, v in cache_specs(cfg, 1, 16, jnp.float32).items()}
    dec_logits = []
    for t in range(T):
        batch = {"tokens": toks[:, t:t + 1],
                 "cache_index": jnp.asarray(t, jnp.int32), **caches}
        lg, caches = decode_step(cfg, params, batch)
        dec_logits.append(np.asarray(lg[:, 0].astype(jnp.float32)))
    full = forward(cfg, params, {"tokens": toks}).astype(jnp.float32)
    full = np.asarray(full)
    for t in range(T):
        np.testing.assert_allclose(dec_logits[t], full[:, t], rtol=2e-2,
                                   atol=2e-2)


@pytest.mark.parametrize("arch", ["qwen3-4b", "deepseek-moe-16b"])
def test_train_step_stages_are_named(arch):
    """Each stage of the step names its ops, so a device trace can split
    the step by stage; the backward pass carries its own scope."""
    cfg, params = _state(arch)
    opt_cfg = adamw.OptConfig()
    batch = make_inputs(cfg, ShapeSpec("t", 32, 2, "train"))
    text = jax.jit(make_train_step(cfg, opt_cfg)).lower(
        params, adamw.init(opt_cfg, params), batch).as_text(debug_info=True)
    names = re.findall(r'loc\("([^"]*)"', text)
    for stage in ("embed", "attention", "mlp", "logits", "loss", "adamw"):
        assert any(re.search(rf"[/(]{stage}[/)]", n) for n in names), stage
    backward = [n for n in names if "backward/" in n]
    assert backward and all("transpose(" in n for n in backward)
