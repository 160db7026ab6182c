"""Decoder-only transformer covering the dense / moe / mla_moe / vlm families.

Layers are stacked along a leading L axis and driven by jax.lax.scan (one
traced block regardless of depth — essential for 61/96-layer dry-run compile
times).  Heterogeneous stacks (deepseek first-k dense layers) are two scans.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models.common import ShardCtx, constrain, dense_init, rms_norm


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def _block_init(key, cfg: ModelConfig, dtype, moe: bool):
    ks = jax.random.split(key, 4)
    d = cfg.d_model
    if cfg.family == "mla_moe":
        a = attn.mla_init(ks[0], cfg, dtype)
    else:
        a = attn.gqa_init(ks[0], cfg, dtype)
    if moe:
        m = moe_mod.moe_init(ks[1], cfg, dtype)
    else:
        m = moe_mod.mlp_init(ks[1], cfg, dtype)
    return {"attn": a, "mlp": m,
            "norm1": jnp.ones((d,), dtype), "norm2": jnp.ones((d,), dtype)}


def init_params(cfg: ModelConfig, key, dtype=None):
    dtype = dtype or jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 8)
    d, V, L = cfg.d_model, cfg.vocab_size, cfg.num_layers
    n_dense = cfg.first_dense_layers if cfg.num_experts else L
    n_moe = L - n_dense

    params = {
        "embed": (jax.random.normal(ks[0], (V, d), jnp.float32) * 0.02
                  ).astype(dtype),
        "final_norm": jnp.ones((d,), dtype),
    }
    if n_dense:
        params["dense_layers"] = jax.vmap(
            lambda k: _block_init(k, cfg, dtype, moe=False))(
                jax.random.split(ks[1], n_dense))
    if n_moe:
        params["moe_layers"] = jax.vmap(
            lambda k: _block_init(k, cfg, dtype, moe=True))(
                jax.random.split(ks[2], n_moe))
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(ks[3], (d, V), dtype)
    if cfg.family == "vlm":
        params["mm_connector"] = dense_init(ks[4], (d, d), dtype)
    if cfg.mtp_depth:
        params["mtp"] = {
            "proj": dense_init(ks[5], (2 * d, d), dtype),
            "norm": jnp.ones((d,), dtype),
            "block": _block_init(ks[6], cfg, dtype, moe=bool(cfg.num_experts)),
        }
    return params


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------
def _sp(x, ctx):
    """Megatron-style sequence-parallel residual-stream constraint."""
    if ctx is None:
        return x
    S = x.shape[1]
    if ctx.tp is not None and S % ctx.mesh.shape[ctx.tp] == 0:
        return constrain(x, ctx, "dp", "tp", None)
    return constrain(x, ctx, "dp", None, None)


def block_apply(cfg: ModelConfig, p, x, positions, ctx, *, moe: bool,
                causal: bool = True):
    """-> (x, routing stats of an MoE layer, None for a dense one)."""
    # norm outputs pinned to SP: the attention/MLP full-sequence gather
    # then moves to the bf16 tensor instead of the f32 rms upcast
    with jax.named_scope("attention"):
        h = _sp(rms_norm(x, p["norm1"], cfg.norm_eps), ctx)
        if cfg.family == "mla_moe":
            a = attn.mla_apply(cfg, p["attn"], h, positions=positions,
                               causal=causal, ctx=ctx)
        else:
            a = attn.gqa_apply(cfg, p["attn"], h, positions=positions,
                               causal=causal, ctx=ctx)
        x = _sp(x + a, ctx)
    with jax.named_scope("mlp"):
        h = _sp(rms_norm(x, p["norm2"], cfg.norm_eps), ctx)
        stats = None
        if moe:
            m, stats = moe_mod.moe_apply(cfg, p["mlp"], h, ctx,
                                         router_stats=True)
        else:
            m = moe_mod.mlp_apply(cfg, p["mlp"], h, ctx)
        return _sp(x + m, ctx), stats


def _remat(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    policy = (jax.checkpoint_policies.nothing_saveable
              if cfg.remat == "nothing"
              else jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn, policy=policy)


def scan_stack(cfg: ModelConfig, stacked, x, positions, ctx, *, moe: bool):
    """-> (x, each layer's routing stats stacked, None for dense layers)."""
    def body(carry, p_layer):
        return block_apply(cfg, p_layer, carry, positions, ctx, moe=moe)

    body = _remat(body, cfg)
    return jax.lax.scan(body, x, stacked)


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------
def embed_inputs(cfg: ModelConfig, params, batch, ctx):
    tok = params["embed"][batch["tokens"]]  # gather
    if cfg.family == "vlm":
        img = batch["patch_embeds"] @ params["mm_connector"]
        x = jnp.concatenate([img, tok], axis=1)
    else:
        x = tok
    return _sp(x.astype(jnp.dtype(cfg.dtype)), ctx)


def forward(cfg: ModelConfig, params, batch, ctx: Optional[ShardCtx] = None,
            return_hidden: bool = False, return_stats: bool = False):
    """Full-sequence forward -> logits (B, S, V), then the final hidden
    state with `return_hidden`, then the MoE layers' routing stats stacked
    over layers (`moe.routing_stats`; None without MoE layers) with
    `return_stats`."""
    with jax.named_scope("embed"):
        x = embed_inputs(cfg, params, batch, ctx)
    S = x.shape[1]
    positions = jnp.arange(S)
    stats = None
    if "dense_layers" in params:
        x, _ = scan_stack(cfg, params["dense_layers"], x, positions, ctx,
                          moe=False)
    if "moe_layers" in params:
        x, stats = scan_stack(cfg, params["moe_layers"], x, positions, ctx,
                              moe=True)
    with jax.named_scope("logits"):
        h = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = lm_logits(cfg, params, h, ctx)
    out = (logits,) + ((h,) if return_hidden else ()) + (
        (stats,) if return_stats else ())
    return out if len(out) > 1 else logits


def lm_logits(cfg: ModelConfig, params, h, ctx):
    w = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = h @ w.astype(h.dtype)
    return constrain(logits, ctx, "dp", None, "tp")


def mtp_logits(cfg: ModelConfig, params, h, batch, ctx):
    """DeepSeek-V3 multi-token prediction: one extra block predicting t+2.

    h: main-model hidden states (B, S, d).  Combines h[t] with emb(tok[t+1]).
    """
    p = params["mtp"]
    tok = params["embed"][batch["tokens"]]
    if cfg.family == "vlm":
        raise NotImplementedError
    nxt = jnp.roll(tok, -1, axis=1).astype(h.dtype)
    z = jnp.concatenate([rms_norm(h, p["norm"], cfg.norm_eps), nxt], -1)
    z = _sp(z @ p["proj"], ctx)
    S = z.shape[1]
    z, _ = block_apply(cfg, p["block"], z, jnp.arange(S), ctx,
                       moe=bool(cfg.num_experts))
    return lm_logits(cfg, params, rms_norm(z, params["final_norm"],
                                           cfg.norm_eps), ctx)


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------
def decode_step(cfg: ModelConfig, params, batch,
                ctx: Optional[ShardCtx] = None):
    """One decode step.  batch: tokens (B,1), cache_index (), caches.

    Returns (logits (B, 1, V), new_caches dict).
    """
    idx = batch["cache_index"]
    x = params["embed"][batch["tokens"]].astype(jnp.dtype(cfg.dtype))
    x = constrain(x, ctx, "dp", None, None)

    n_dense = cfg.first_dense_layers if cfg.num_experts else cfg.num_layers

    def body(carry, layer):
        xx = carry
        p, kc, vc, cache = layer["p"], layer.get("kc"), layer.get("vc"), None
        h = rms_norm(xx, p["norm1"], cfg.norm_eps)
        if cfg.family == "mla_moe":
            a, new_kv = attn.mla_decode(cfg, p["attn"], h, layer["kv"], idx,
                                        ctx=ctx)
            upd = {"kv": new_kv}
        else:
            a, nk, nv = attn.gqa_decode(cfg, p["attn"], h, kc, vc, idx,
                                        ctx=ctx)
            upd = {"kc": nk, "vc": nv}
        xx = xx + a
        h = rms_norm(xx, p["norm2"], cfg.norm_eps)
        m = (moe_mod.moe_apply(cfg, p["mlp"], h, ctx) if layer["moe"]
             else moe_mod.mlp_apply(cfg, p["mlp"], h, ctx))
        return xx + m, upd

    new_caches = {}
    x_cur = x
    if cfg.family == "mla_moe":
        kv = batch["kv_cache"]
        parts = []
        if n_dense:
            def dbody(c, layer):
                out, upd = body(c, {"p": layer["p"], "kv": layer["kv"],
                                    "moe": False})
                return out, upd["kv"]
            x_cur, kv_d = jax.lax.scan(
                dbody, x_cur, {"p": params["dense_layers"],
                               "kv": kv[:n_dense]})
            parts.append(kv_d)
        def mbody(c, layer):
            out, upd = body(c, {"p": layer["p"], "kv": layer["kv"],
                                "moe": True})
            return out, upd["kv"]
        x_cur, kv_m = jax.lax.scan(
            mbody, x_cur, {"p": params["moe_layers"], "kv": kv[n_dense:]})
        parts.append(kv_m)
        new_caches["kv_cache"] = jnp.concatenate(parts, 0)
    else:
        kc, vc = batch["k_cache"], batch["v_cache"]
        kparts, vparts = [], []
        off = 0
        for name, moe in (("dense_layers", False), ("moe_layers", True)):
            if name not in params:
                continue
            n = jax.tree_util.tree_leaves(params[name])[0].shape[0]
            def sbody(c, layer, moe=moe):
                out, upd = body(c, {"p": layer["p"], "kc": layer["kc"],
                                    "vc": layer["vc"], "moe": moe})
                return out, (upd["kc"], upd["vc"])
            x_cur, (nk, nv) = jax.lax.scan(
                sbody, x_cur, {"p": params[name],
                               "kc": kc[off:off + n], "vc": vc[off:off + n]})
            kparts.append(nk)
            vparts.append(nv)
            off += n
        new_caches["k_cache"] = (jnp.concatenate(kparts, 0)
                                 if len(kparts) > 1 else kparts[0])
        new_caches["v_cache"] = (jnp.concatenate(vparts, 0)
                                 if len(vparts) > 1 else vparts[0])

    h = rms_norm(x_cur, params["final_norm"], cfg.norm_eps)
    logits = lm_logits(cfg, params, h, ctx)
    return logits, new_caches
