"""Attention layers: GQA (dense/moe/vlm/encdec/hybrid) and MLA (deepseek-v3).

Each layer exposes:
  init(key, cfg)                         -> params (unstacked; callers vmap)
  apply(cfg, p, x, ...)                  -> full-sequence forward
  decode(cfg, p, x, caches, idx, ...)    -> single-token forward + cache update
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import (ShardCtx, apply_rope, constrain,
                                 decode_attention, dense_init,
                                 flash_attention, head_shardable, rms_norm)


# ===========================================================================
# GQA
# ===========================================================================
def gqa_init(key, cfg: ModelConfig, dtype):
    H, KV, hd, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.d_model
    ks = jax.random.split(key, 6)
    p = {
        "wq": dense_init(ks[0], (d, H * hd), dtype),
        "wk": dense_init(ks[1], (d, KV * hd), dtype),
        "wv": dense_init(ks[2], (d, KV * hd), dtype),
        "wo": dense_init(ks[3], (H * hd, d), dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def _qkv(cfg: ModelConfig, p, x, positions, ctx):
    B, S, _ = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = (x @ p["wq"]).reshape(B, S, H, hd)
    k = (x @ p["wk"]).reshape(B, S, KV, hd)
    v = (x @ p["wv"]).reshape(B, S, KV, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    if head_shardable(H, ctx):
        q = constrain(q, ctx, "dp", None, "tp", None)
    if head_shardable(KV, ctx):
        k = constrain(k, ctx, "dp", None, "tp", None)
        v = constrain(v, ctx, "dp", None, "tp", None)
    return q, k, v


def gqa_apply(cfg: ModelConfig, p, x, *, positions, causal: bool,
              ctx: Optional[ShardCtx], kv_override=None):
    """Full-sequence attention.  kv_override: (k, v) for cross-attention."""
    B, S, _ = x.shape
    q, k, v = _qkv(cfg, p, x, positions, ctx)
    if kv_override is not None:
        k, v = kv_override
    o = flash_attention(q, k, v, causal=causal, ctx=ctx)
    o = o.reshape(B, S, cfg.num_heads * cfg.head_dim)
    out = o @ p["wo"]
    return constrain(out, ctx, "dp", "tp", None)


def gqa_decode(cfg: ModelConfig, p, x, k_cache, v_cache, cache_index, *,
               ctx: Optional[ShardCtx], cross: bool = False,
               kv_override=None):
    """x: (B, 1, d); caches: (B, S, KV, hd).  Returns (out, k_cache, v_cache)."""
    B = x.shape[0]
    positions = jnp.full((1,), cache_index, jnp.int32)
    q, k_new, v_new = _qkv(cfg, p, x, positions, ctx)
    if cross:
        # cross-attention: static KV from the encoder, no cache update
        k, v = kv_override
        o = flash_attention(q, k, v, causal=False, ctx=ctx)
    else:
        k_cache = jax.lax.dynamic_update_slice(
            k_cache, k_new.astype(k_cache.dtype), (0, cache_index, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(
            v_cache, v_new.astype(v_cache.dtype), (0, cache_index, 0, 0))
        o = decode_attention(q, k_cache, v_cache, cache_index)
    out = o.reshape(B, 1, cfg.num_heads * cfg.head_dim) @ p["wo"]
    return constrain(out, ctx, "dp", None, None), k_cache, v_cache


# ===========================================================================
# MLA (multi-head latent attention, deepseek-v3)
#
# q: d -> q_lora -> H*(nope+rope), or d -> H*(nope+rope) directly when
# q_lora_rank is 0 (Moonlight); kv: d -> (kv_lora + rope_shared);
# decode cache stores only the compressed latent + shared rope key.
# ===========================================================================
def mla_init(key, cfg: ModelConfig, dtype):
    d, H = cfg.d_model, cfg.num_heads
    qr, kvr = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    ks = jax.random.split(key, 6)
    if qr:
        q = {"wq_a": dense_init(ks[0], (d, qr), dtype),
             "q_norm": jnp.ones((qr,), dtype),
             "wq_b": dense_init(ks[1], (qr, H * (dn + dr)), dtype)}
    else:  # no q-LoRA: one direct projection
        q = {"wq": dense_init(ks[0], (d, H * (dn + dr)), dtype)}
    return {
        **q,
        "wkv_a": dense_init(ks[2], (d, kvr + dr), dtype),
        "kv_norm": jnp.ones((kvr,), dtype),
        "wkv_b": dense_init(ks[3], (kvr, H * (dn + dv)), dtype),
        "wo": dense_init(ks[4], (H * dv, d), dtype),
    }


def _mla_q(cfg, p, x, positions, ctx):
    B, S, _ = x.shape
    H = cfg.num_heads
    dn, dr = cfg.qk_nope_dim, cfg.qk_rope_dim
    if "wq" in p:
        q = x @ p["wq"]
    else:
        q = rms_norm(x @ p["wq_a"], p["q_norm"], cfg.norm_eps) @ p["wq_b"]
    q = q.reshape(B, S, H, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    q = jnp.concatenate([q_nope, q_rope], -1)
    if head_shardable(H, ctx):
        q = constrain(q, ctx, "dp", None, "tp", None)
    return q


def _mla_kv_from_latent(cfg, p, latent, ctx):
    """latent: (B, S, kv_lora + rope) -> per-head k (nope+rope), v."""
    B, S, _ = latent.shape
    H = cfg.num_heads
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    c_kv, k_rope = latent[..., :cfg.kv_lora_rank], latent[..., cfg.kv_lora_rank:]
    kv = rms_norm(c_kv, p["kv_norm"], cfg.norm_eps) @ p["wkv_b"]
    kv = kv.reshape(B, S, H, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (B, S, H, dr))], -1)
    if head_shardable(H, ctx):
        k = constrain(k, ctx, "dp", None, "tp", None)
        v = constrain(v, ctx, "dp", None, "tp", None)
    return k, v


def mla_apply(cfg: ModelConfig, p, x, *, positions, causal: bool,
              ctx: Optional[ShardCtx]):
    B, S, _ = x.shape
    dn, dr = cfg.qk_nope_dim, cfg.qk_rope_dim
    with jax.named_scope("mla.q"):
        q = _mla_q(cfg, p, x, positions, ctx)
    with jax.named_scope("mla.kv"):
        latent = x @ p["wkv_a"]  # (B, S, kv_lora + rope)
        k_rope = apply_rope(latent[..., cfg.kv_lora_rank:][:, :, None, :],
                            positions, cfg.rope_theta)[:, :, 0, :]
        latent = jnp.concatenate([latent[..., :cfg.kv_lora_rank], k_rope],
                                 -1)
        k, v = _mla_kv_from_latent(cfg, p, latent, ctx)
    with jax.named_scope("mla.core"):
        o = _mla_attend(q, k, v, causal=causal, scale=(dn + dr) ** -0.5,
                        ctx=ctx)
        out = o.reshape(B, S, cfg.num_heads * cfg.v_head_dim) @ p["wo"]
    return constrain(out, ctx, "dp", "tp", None)


#: queries a causal, unsharded MLA attention takes at a time
Q_CHUNK = 2048


def _mla_attend(q, k, v, *, causal: bool, scale: float,
                ctx: Optional[ShardCtx]):
    """flash_attention, in causal query chunks past Q_CHUNK: chunk i reads
    keys [0, (i + 1) Q_CHUNK) only, so the kv blocks past its diagonal are
    never computed.  Each chunk's backward keeps its output and log-sum-exp
    beside the q, k and v the chunks share, so they need no remat of their
    own."""
    S = q.shape[1]
    if ctx is not None or not causal or S <= Q_CHUNK or S % Q_CHUNK:
        return flash_attention(q, k, v, causal=causal, scale=scale, ctx=ctx)
    return jnp.concatenate(
        [flash_attention(q[:, lo:lo + Q_CHUNK], k, v, causal=True,
                         q_offset=lo, scale=scale)
         for lo in range(0, S, Q_CHUNK)], 1)


def mla_decode(cfg: ModelConfig, p, x, kv_cache, cache_index, *,
               ctx: Optional[ShardCtx]):
    """Absorbed MLA decode against the compressed latent cache.

    kv_cache: (B, S, kv_lora + rope) holding the *normalized* latent plus the
    shared roped key.  Per-head K/V are never expanded over S: wkv_b is
    absorbed into the query (scores) and the output (values), so attention
    runs directly in latent space — the whole point of MLA serving.
    """
    B = x.shape[0]
    H = cfg.num_heads
    dn, dr, dv, kvr = (cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim,
                       cfg.kv_lora_rank)
    positions = jnp.full((1,), cache_index, jnp.int32)
    q = _mla_q(cfg, p, x, positions, ctx)  # (B, 1, H, dn+dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]

    latent = x @ p["wkv_a"]
    c_kv = rms_norm(latent[..., :kvr], p["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(latent[..., kvr:][:, :, None, :],
                        positions, cfg.rope_theta)[:, :, 0, :]
    new_entry = jnp.concatenate([c_kv, k_rope], -1)
    kv_cache = jax.lax.dynamic_update_slice(
        kv_cache, new_entry.astype(kv_cache.dtype), (0, cache_index, 0))
    cached_c = kv_cache[..., :kvr]      # (B, S, kvr)
    cached_r = kv_cache[..., kvr:]      # (B, S, dr)

    w_kv = p["wkv_b"].reshape(kvr, H, dn + dv)
    w_k, w_v = w_kv[..., :dn], w_kv[..., dn:]
    # absorb w_k into the query: (B,H,kvr)
    q_lat = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], w_k,
                       preferred_element_type=jnp.float32)
    s = (jnp.einsum("bhr,bsr->bhs", q_lat,
                    cached_c.astype(jnp.float32)) +
         jnp.einsum("bhd,bsd->bhs", q_rope[:, 0].astype(jnp.float32),
                    cached_r.astype(jnp.float32))) * (dn + dr) ** -0.5
    S = kv_cache.shape[1]
    valid = jnp.arange(S) <= cache_index
    s = jnp.where(valid[None, None], s, -jnp.inf)
    prob = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhs,bsr->bhr", prob, cached_c.astype(jnp.float32))
    o = jnp.einsum("bhr,rhd->bhd", o_lat, w_v.astype(jnp.float32))
    out = o.reshape(B, 1, H * dv).astype(x.dtype) @ p["wo"]
    return constrain(out, ctx, "dp", None, None), kv_cache
