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
    labels = batch["labels"]
    if cfg.mtp_depth:
        logits, h = models.forward(cfg, params, batch, ctx,
                                   return_hidden=True)
        from repro.models.transformer import mtp_logits
        main = cross_entropy(logits[:, :-1], labels[:, 1:])
        mtp = mtp_logits(cfg, params, h, batch, ctx)
        mtp_loss = cross_entropy(mtp[:, :-2], labels[:, 2:])
        loss = main + 0.3 * mtp_loss
        return loss, {"loss": loss, "main_loss": main, "mtp_loss": mtp_loss}
    logits = models.forward(cfg, params, batch, ctx)
    loss = cross_entropy(logits[:, :-1], labels[:, 1:])
    return loss, {"loss": loss}


def make_train_step(cfg: ModelConfig, opt_cfg: adamw.OptConfig,
                    ctx: Optional[ShardCtx] = None, *,
                    accum_steps: int = 1):
    """(params, opt_state, batch) -> (params, opt_state, metrics).

    accum_steps > 1 enables gradient accumulation: the global batch is split
    into microbatches processed by a scanned, rematted inner loop — the
    standard activation-memory lever for 100B+ models (activations scale
    with the microbatch, grads accumulate in a single sharded fp32 buffer).
    """

    def grads_of(params, batch):
        # value_and_grad spelled as vjp, so the transposed half of the
        # step carries its own name scope
        loss, pullback, aux = jax.vjp(
            lambda p: loss_fn(cfg, p, batch, ctx), params, has_aux=True)
        with jax.named_scope("backward"):
            (grads,) = pullback(jnp.ones_like(loss))
        return (loss, aux), grads

    def train_step(params, opt_state, batch):
        if accum_steps == 1:
            (loss, aux), grads = grads_of(params, batch)
        else:
            def split(x):
                B = x.shape[0]
                assert B % accum_steps == 0, (B, accum_steps)
                return x.reshape(accum_steps, B // accum_steps, *x.shape[1:])

            micro = {k: split(v) for k, v in batch.items()}

            def body(acc, mb):
                (l, a), g = grads_of(params, mb)
                acc = jax.tree.map(
                    lambda s, gi: s + gi.astype(s.dtype) / accum_steps,
                    acc, g)
                return acc, a

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            grads, auxs = jax.lax.scan(body, zeros, micro)
            aux = jax.tree.map(lambda x: x.mean(), auxs)
        with jax.named_scope("adamw"):
            params, opt_state, om = adamw.update(opt_cfg, grads, opt_state,
                                                 params)
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
