"""The MoE cell's plain reference (`bench/reference/mla_moe_lm.py`) against
the program, layer by layer, in float32 at a small size: the same init
bit for bit, the same MLA without q-LoRA (whole and in query chunks), the
same routed layer under a normal routing and under one that sends every
token to one held expert, and expert shares that add up to the uncut
layer."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.drivers.moe_train import program_config
from bench.reference import mla_moe_lm as ref
from bench.tests.moe_tiny import moe_cell

F32 = jnp.float32


@pytest.fixture(scope="module")
def cfg_file():
    return moe_cell().config


def _cfg(cfg_file, **kw):
    return dataclasses.replace(program_config(cfg_file), dtype="float32",
                               **kw)


def _layer(cfg_file, seed=5):
    """The first MoE layer of the reference's init, in float32."""
    p = ref.init_params(cfg_file, seed)["moe_layers"]
    return jax.tree.map(lambda t: t[0].astype(F32), p)


def _x(seed=1, S=32, d=64):
    return jax.random.normal(jax.random.key(seed), (1, S, d), F32)


def test_init_matches_the_program_bit_for_bit(cfg_file):
    from repro.models import api as models
    prog = jax.jit(lambda k: models.init_params(program_config(cfg_file),
                                                k))(jax.random.key(99))
    ours = ref.init_params(cfg_file, 99)
    flat_p = jax.tree_util.tree_flatten_with_path(prog)[0]
    flat_o = {jax.tree_util.keystr(k): v for k, v in
              jax.tree_util.tree_flatten_with_path(ours)[0]}
    assert len(flat_p) == len(flat_o)
    for k, v in flat_p:
        mine = flat_o[jax.tree_util.keystr(k)]
        assert v.dtype == mine.dtype
        np.testing.assert_array_equal(np.asarray(v.astype(F32)),
                                      np.asarray(mine.astype(F32)))
    mlp = prog["moe_layers"]["mlp"]
    assert mlp["router"].shape[-1] == 8 and mlp["router"].dtype == F32
    assert mlp["experts"]["wi"].shape[1] == 4          # the 4 held of 8
    assert "wq" in prog["moe_layers"]["attn"]          # no q-LoRA
    assert "wq_a" not in prog["moe_layers"]["attn"]


@pytest.mark.parametrize("q_chunk", [2048, 8])
def test_mla_without_q_lora_matches_a_plain_float32_mla(cfg_file,
                                                        monkeypatch,
                                                        q_chunk):
    from repro.models import attention
    monkeypatch.setattr(attention, "Q_CHUNK", q_chunk)
    cfg, a = _cfg(cfg_file), _layer(cfg_file)["attn"]
    x = _x()
    with jax.default_matmul_precision("highest"):
        got = attention.mla_apply(cfg, a, x, positions=jnp.arange(32),
                                  causal=True, ctx=None)
        want = ref._attention(ref.shape(cfg_file), "f32", x[0], a)
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        r = jax.random.normal(jax.random.key(2), want.shape)
        g = jax.grad(lambda a: jnp.sum(attention.mla_apply(
            cfg, a, x, positions=jnp.arange(32), causal=True,
            ctx=None)[0] * r))(a)
        gr = jax.grad(lambda a: jnp.sum(ref._attention(
            ref.shape(cfg_file), "f32", x[0], a) * r))(a)
    for k in gr:
        np.testing.assert_allclose(np.asarray(g[k]), np.asarray(gr[k]),
                                   rtol=1e-4, atol=1e-5)


def _moe_both(cfg_file, p, x, **kw):
    from repro.models import moe
    with jax.default_matmul_precision("highest"):
        y, stats = moe.moe_apply(_cfg(cfg_file, **kw), p, x, None,
                                 router_stats=True)
        s = dict(ref.shape(cfg_file), ties=ref.TIE_MARGINS)
        yr, balance, load, counts = ref._moe(s, "f32", x[0], p)
    return (y[0], stats), (yr, balance, load, counts)


def test_moe_layer_matches_the_reference(cfg_file):
    p = _layer(cfg_file)["mlp"]
    (y, st), (yr, balance, load, counts) = _moe_both(cfg_file, p, _x())
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=1e-5,
                               atol=1e-5)
    assert int(st["held_pairs"]) == int(counts[0]) > 0
    np.testing.assert_array_equal(np.asarray(st["load"]), np.asarray(load))
    assert float(st["balance"]) == pytest.approx(float(balance), rel=1e-5)


def test_moe_layer_is_dropless_when_every_token_picks_one_held_expert(
        cfg_file):
    p = _layer(cfg_file)["mlp"]
    # expert 1 (held) first for every token: 32 pairs on one expert of 4,
    # where a 1.25 capacity factor would keep 32 * 2 / 8 * 1.25 = 10
    p = dict(p, router_bias=p["router_bias"].at[1].set(10.0))
    (y, st), (yr, _, load, counts) = _moe_both(cfg_file, p, _x(3))
    assert int(load[1]) == 32
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=1e-5,
                               atol=1e-5)
    assert int(st["held_pairs"]) == int(counts[0]) >= 32


def test_chunked_dispatch_equals_one_dispatch(cfg_file, monkeypatch):
    from repro.models import moe
    p, x = _layer(cfg_file)["mlp"], _x(4, S=64)
    cfg = _cfg(cfg_file)
    r = jax.random.normal(jax.random.key(6), x.shape)

    def run():
        y, st = moe.moe_apply(cfg, p, x, None, router_stats=True)
        g = jax.grad(lambda q: jnp.sum(moe.moe_apply(cfg, q, x, None) * r))(p)
        return y, st["held_pairs"], g

    whole = run()
    monkeypatch.setattr(moe, "MAX_PAIRS", 32)      # four chunks of 16 tokens
    parts = run()
    np.testing.assert_allclose(np.asarray(parts[0]), np.asarray(whole[0]),
                               rtol=1e-5, atol=1e-5)
    assert int(parts[1]) == int(whole[1])
    for a, b in zip(jax.tree.leaves(parts[2]), jax.tree.leaves(whole[2])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-5)


def test_expert_shares_add_up_to_the_uncut_layer(cfg_file):
    """Over 4 ranks of 2 experts each: the held experts' parts, with the
    shared experts counted once, give the uncut layer of the reference."""
    from repro.models import moe
    whole_file = dict(cfg_file, n_routed_experts=8, ep_size=1)
    p = _layer(whole_file, seed=7)["mlp"]
    x = _x(8)
    s = dict(ref.shape(whole_file), ties=ref.TIE_MARGINS)
    with jax.default_matmul_precision("highest"):
        want, _, _, counts = ref._moe(s, "f32", x[0], p)
        shared = moe.mlp_apply(_cfg(whole_file), p["shared"], x, None)[0]
        total, held = shared, 0
        for rank in range(4):
            cfg = _cfg(whole_file, ep_size=4, ep_rank=rank)
            share = moe.moe_init(jax.random.key(0), cfg, F32)
            part = dict(p, experts=jax.tree.map(
                lambda w: w[2 * rank:2 * rank + 2], p["experts"]))
            assert jax.tree.map(jnp.shape, share["experts"]) == \
                jax.tree.map(jnp.shape, part["experts"])
            y, st = moe.moe_apply(cfg, part, x, None, router_stats=True)
            total = total + (y[0] - shared)
            held += int(st["held_pairs"])
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert held == int(counts[0]) == 32 * 2


def test_a_share_holds_the_whole_layers_experts_key_for_key(cfg_file):
    from repro.models import moe
    whole = _cfg(dict(cfg_file, n_routed_experts=8, ep_size=1))
    full = moe.moe_init(jax.random.key(3), whole, F32)
    for rank in range(4):
        share = moe.moe_init(jax.random.key(3), dataclasses.replace(
            whole, ep_size=4, ep_rank=rank), F32)
        for a, b in zip(jax.tree.leaves(share["experts"]),
                        jax.tree.leaves(full["experts"])):
            np.testing.assert_array_equal(np.asarray(a),
                                          np.asarray(b[2 * rank:2 * rank + 2]))
        np.testing.assert_array_equal(np.asarray(share["router"]),
                                      np.asarray(full["router"]))
