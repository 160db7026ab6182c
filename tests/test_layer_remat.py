"""The layer remat keeps the attention core's output and log-sum-exp: the
rematted backward runs no online-softmax forward scan of its own, the
gradients are those of no remat, and stacks without attention are as
before."""
from __future__ import annotations

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.ad_checkpoint import print_saved_residuals
from jax.extend.core import ClosedJaxpr, Jaxpr

from repro.configs import get_config, make_inputs
from repro.configs.base import ShapeSpec
from repro.models import attention as attn
from repro.models import init_params
from repro.models import ssm_models
from repro.models import transformer as tfm
from repro.train.steps import loss_fn

B = 2


def _cfg(arch, remat="nothing"):
    return dataclasses.replace(get_config(arch).smoke(), dtype="float32",
                               remat=remat)


def _batch(cfg, S):
    return {k: jnp.asarray(v)
            for k, v in make_inputs(cfg, ShapeSpec("t", S, B, "train")).items()}


def _grad_jaxpr(cfg, params, batch):
    return jax.make_jaxpr(jax.grad(
        lambda p: loss_fn(cfg, p, batch)[0]))(params).jaxpr


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for x in v if isinstance(v, (list, tuple)) else (v,):
            if isinstance(x, ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, Jaxpr):
                yield x


def online_softmax_scans(jaxpr, rows) -> int:
    """Scans anywhere in `jaxpr` whose carry holds an f32 `rows` array:
    the flash forward's running max and sum (its backward carries dq)."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            lo = eqn.params["num_consts"]
            carry = eqn.invars[lo:lo + eqn.params["num_carry"]]
            n += any(v.aval.shape == rows and v.aval.dtype == jnp.float32
                     for v in carry)
        for sub in _sub_jaxprs(eqn):
            n += online_softmax_scans(sub, rows)
    return n


# arch, sequence, attention calls a layer body makes, queries a call
STACKS = {"gqa": ("qwen3-4b", 64, 1, 64),
          "mla_chunked": ("moonlight-16b-a3b", 2 * attn.Q_CHUNK, 2,
                          attn.Q_CHUNK)}


@pytest.mark.parametrize("remat", ["nothing", "dots"])
@pytest.mark.parametrize("stack", list(STACKS))
def test_rematted_backward_runs_one_forward_scan_per_attention_call(
        stack, remat):
    """One forward scan per attention call in each layer scan's body, as
    without remat: the backward layer scan recomputes none."""
    arch, S, calls, Sq = STACKS[stack]
    cfg = _cfg(arch, remat)
    params = init_params(cfg, jax.random.key(0))
    n_stacks = sum(k in params for k in ("dense_layers", "moe_layers"))
    rows = (B, cfg.num_heads, Sq)
    got = online_softmax_scans(_grad_jaxpr(cfg, params, _batch(cfg, S)),
                               rows)
    assert got == n_stacks * calls
    unrematted = dataclasses.replace(cfg, remat="none")
    assert got == online_softmax_scans(
        _grad_jaxpr(unrematted, params, _batch(cfg, S)), rows)


def test_mamba_stack_remat_is_nothing_saveable(monkeypatch):
    """No attention, no names: the Mamba stack's gradient is traced as it
    was under a plain `nothing_saveable` remat."""
    cfg = _cfg("mamba2-780m")
    params = init_params(cfg, jax.random.key(0))
    batch = _batch(cfg, 32)
    strip = lambda j: re.sub(r"policy=[^\n]*?(?=\s+\w+=|\])", "", str(j))
    got = strip(_grad_jaxpr(cfg, params, batch))
    monkeypatch.setattr(ssm_models, "_remat", lambda fn, cfg: jax.checkpoint(
        fn, policy=jax.checkpoint_policies.nothing_saveable))
    assert got == strip(_grad_jaxpr(cfg, params, batch))


# arch, sequence, queries a chunk (the MLA's chunked path runs at 32)
GRAD_CASES = {"dense_gqa": ("qwen3-4b", 32, None),
              "mla_moe": ("moonlight-16b-a3b", 32, 16)}


@pytest.mark.parametrize("case", list(GRAD_CASES))
def test_rematted_gradients_equal_unrematted(case, monkeypatch):
    arch, S, q_chunk = GRAD_CASES[case]
    if q_chunk:
        monkeypatch.setattr(attn, "Q_CHUNK", q_chunk)
    cfg = _cfg(arch)
    params = init_params(cfg, jax.random.key(3))
    batch = _batch(cfg, S)

    def value_and_grad(remat):
        c = dataclasses.replace(cfg, remat=remat)
        return jax.jit(jax.value_and_grad(
            lambda p: loss_fn(c, p, batch)[0]))(params)

    (loss, g), (loss0, g0) = value_and_grad("nothing"), value_and_grad("none")
    np.testing.assert_allclose(float(loss), float(loss0), rtol=1e-6)
    flat0 = dict(jax.tree_util.tree_flatten_with_path(g0)[0])
    for path, a in jax.tree_util.tree_flatten_with_path(g)[0]:
        want = np.asarray(flat0[path])  # f32 sums taken in another order
        np.testing.assert_allclose(np.asarray(a), want, rtol=1e-5,
                                   atol=1e-5 * np.abs(want).max(),
                                   err_msg=jax.tree_util.keystr(path))


# arch, sequence, the attention's output width per head
LAYERS = {"gqa": ("qwen3-4b", 2048), "mla_chunked": ("moonlight-16b-a3b",
                                                     2 * attn.Q_CHUNK)}


@pytest.mark.parametrize("layer", list(LAYERS))
def test_rematted_layer_saves_attention_output_and_lse(layer, capsys):
    """The residuals of one rematted layer: its inputs, each attention
    call's output and log-sum-exp, and nothing with one slice per kv block
    (the forward's blocks are 512 keys: 4 or more a call here)."""
    arch, S = LAYERS[layer]
    cfg = _cfg(arch)
    params = init_params(cfg, jax.random.key(0))
    p = jax.tree.map(lambda a: a[0], params["dense_layers"])
    x = jnp.ones((B, S, cfg.d_model), jnp.float32)
    positions = jnp.arange(S)
    body = tfm._remat(lambda x, p: tfm.block_apply(
        cfg, p, x, positions, None, moe=False)[0], cfg)
    print_saved_residuals(body, x, p)
    saved = capsys.readouterr().out.splitlines()

    H = cfg.num_heads
    hd_v = cfg.v_head_dim if cfg.kv_lora_rank else cfg.head_dim
    calls = S // attn.Q_CHUNK if cfg.kv_lora_rank else 1
    Sq = S // calls
    lse = [s for s in saved if "named 'attention.lse'" in s]
    assert len(lse) == calls and all(
        s.startswith(f"f32[{B},{H},{Sq}] ") for s in lse), saved
    assert len([s for s in saved
                if s.startswith(f"f32[{B},{Sq},{H},{hd_v}] ")]) == calls
    n_blocks = {(i + 1) * Sq // 512 for i in range(calls)}
    lead = [re.match(r"\w+\[(\d+),", s) for s in saved]
    assert not [m[0] for m in lead if m and int(m[1]) in n_blocks], saved
