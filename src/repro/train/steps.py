"""train_step / serve_step builders (the functions the dry-run lowers).

Loss is computed with vocab-sharded-friendly reductions (one-hot einsum +
logsumexp — no gather across the sharded vocab axis).
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeSpec
from repro.models import api as models
from repro.models import moe as moe_mod
from repro.models.common import ShardCtx
from repro.optim import adamw


def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean next-token CE; vocab axis may be sharded (einsum-reduced)."""
    with jax.named_scope("loss"):
        V = logits.shape[-1]
        lf = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(lf, axis=-1)
        onehot = jax.nn.one_hot(labels, V, dtype=jnp.float32)
        ll = jnp.einsum("...v,...v->...", lf, onehot)
        return jnp.mean(lse - ll)


def loss_fn(cfg: ModelConfig, params, batch,
            ctx: Optional[ShardCtx] = None) -> tuple[jax.Array, dict]:
    """Next-token cross entropy (+ 0.3 x the MTP loss), plus, with MoE
    layers, balance_alpha x their sequence-wise balance terms; aux carries
    `moe_load` (layers, E) and `moe_held_pairs`."""
    labels = batch["labels"]
    kw = {k: True for k, on in (("return_hidden", cfg.mtp_depth),
                                ("return_stats", cfg.num_experts)) if on}
    out = models.forward(cfg, params, batch, ctx, **kw)
    logits = out[0] if kw else out
    loss = cross_entropy(logits[:, :-1], labels[:, 1:])
    aux = {}
    if cfg.mtp_depth:
        from repro.models.transformer import mtp_logits
        aux["main_loss"] = loss
        mtp = mtp_logits(cfg, params, out[1], batch, ctx)
        aux["mtp_loss"] = cross_entropy(mtp[:, :-2], labels[:, 2:])
        loss = loss + 0.3 * aux["mtp_loss"]
    if cfg.num_experts and "moe_layers" in params:
        stats = out[-1]
        if cfg.balance_alpha:
            aux["balance_loss"] = cfg.balance_alpha * stats["balance"].sum()
            loss = loss + aux["balance_loss"]
        aux["moe_load"] = stats["load"]
        aux["moe_held_pairs"] = stats["held_pairs"].sum()
    aux["loss"] = loss
    return loss, aux


#: metrics of a step summed over its microbatches (the others: averaged)
SUMMED = ("moe_load", "moe_held_pairs")


def split_bias(params):
    """(params less the MoE layers' router bias, the bias or None).  The
    bias (DeepSeek-V3's noaux_tc) is no gradient-trained weight: it sits
    out of the gradient and AdamW and takes its own step after each
    update (`moe.step_router_bias`)."""
    mlp = params.get("moe_layers", {}).get("mlp", {})
    if "router_bias" not in mlp:
        return params, None
    mlp = dict(mlp)
    bias = mlp.pop("router_bias")
    return dict(params, moe_layers=dict(params["moe_layers"], mlp=mlp)), bias


def with_bias(params, bias):
    """`split_bias` undone."""
    if bias is None:
        return params
    moe = params["moe_layers"]
    return dict(params, moe_layers=dict(
        moe, mlp=dict(moe["mlp"], router_bias=bias)))


def init_opt_state(opt_cfg: adamw.OptConfig, params):
    """AdamW's state for the leaves the gradient trains."""
    return adamw.init(opt_cfg, split_bias(params)[0])


def make_train_step(cfg: ModelConfig, opt_cfg: adamw.OptConfig,
                    ctx: Optional[ShardCtx] = None, *,
                    accum_steps: int = 1):
    """(params, opt_state, batch) -> (params, opt_state, metrics).

    accum_steps > 1 enables gradient accumulation: the global batch is split
    into microbatches processed by a scanned, rematted inner loop — the
    standard activation-memory lever for 100B+ models (activations scale
    with the microbatch, grads accumulate in a single sharded fp32 buffer).

    The MoE router bias sits out of the gradient and AdamW
    (`split_bias`; the optimizer's state is `init_opt_state`'s); after
    the update it takes its step from the step's expert loads.
    """

    def grads_of(params, bias, batch):
        # value_and_grad spelled as vjp, so the transposed half of the
        # step carries its own name scope
        loss, pullback, aux = jax.vjp(
            lambda p: loss_fn(cfg, with_bias(p, bias), batch, ctx), params,
            has_aux=True)
        with jax.named_scope("backward"):
            (grads,) = pullback(jnp.ones_like(loss))
        return (loss, aux), grads

    def train_step(params, opt_state, batch):
        params, bias = split_bias(params)
        if accum_steps == 1:
            (loss, aux), grads = grads_of(params, bias, batch)
        else:
            def split(x):
                B = x.shape[0]
                assert B % accum_steps == 0, (B, accum_steps)
                return x.reshape(accum_steps, B // accum_steps, *x.shape[1:])

            micro = {k: split(v) for k, v in batch.items()}

            def body(acc, mb):
                (l, a), g = grads_of(params, bias, mb)
                acc = jax.tree.map(
                    lambda s, gi: s + gi.astype(s.dtype) / accum_steps,
                    acc, g)
                return acc, a

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            grads, auxs = jax.lax.scan(body, zeros, micro)
            aux = {k: v.sum(0) if k in SUMMED else v.mean(0)
                   for k, v in auxs.items()}
        with jax.named_scope("adamw"):
            params, opt_state, om = adamw.update(opt_cfg, grads, opt_state,
                                                 params)
        load = aux.pop("moe_load", None)
        if bias is not None:
            params = with_bias(
                params, moe_mod.step_router_bias(cfg, bias, load))
        aux.update(om)
        return params, opt_state, aux

    return train_step


def make_prefill_step(cfg: ModelConfig, ctx: Optional[ShardCtx] = None):
    """(params, batch) -> greedy next token (B,) — inference prefill."""

    def prefill_step(params, batch):
        logits = models.forward(cfg, params, batch, ctx)
        return jnp.argmax(logits[:, -1].astype(jnp.float32), axis=-1)

    return prefill_step


def make_serve_step(cfg: ModelConfig, ctx: Optional[ShardCtx] = None):
    """(params, batch) -> (next_token (B,1), updated caches) — one decode."""

    def serve_step(params, batch):
        logits, caches = models.decode_step(cfg, params, batch, ctx)
        nxt = jnp.argmax(logits[:, -1:].astype(jnp.float32), axis=-1)
        return nxt, caches

    return serve_step
