"""Shared model building blocks: norms, rope, activations, flash attention (jnp),
sharding-constraint plumbing, and parameter init helpers.

All forward code is pure-functional JAX.  Sharding is expressed through an
optional `ShardCtx`; when it is None every constraint is a no-op so the same
code runs un-meshed on CPU smoke tests and fully sharded in the dry-run.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P


# ---------------------------------------------------------------------------
# sharding context
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class ShardCtx:
    """Carries the mesh + logical axis bindings into pure model code.

    dp : axis name(s) carrying the batch (e.g. ("pod", "data") multi-pod)
    tp : tensor-parallel axis name ("model"), or None when the model runs
         pure-DP/FSDP (small dense models where TP boundary collectives
         would dominate — see EXPERIMENTS.md §Perf cell A)
    """

    mesh: Mesh
    dp: tuple[str, ...]
    tp: Optional[str]
    # expert-parallel axes; None -> tp.  Serving uses the FULL mesh (EP²,
    # e.g. one DeepSeek-V3 expert per chip) — see §Perf cell B.
    ep: Optional[tuple] = None

    @property
    def ep_axes(self):
        return self.ep if self.ep is not None else self.tp

    @property
    def ep_covers_dp(self) -> bool:
        if self.ep is None:
            return False
        return any(a in self.ep for a in self.dp)

    def spec(self, *axes) -> NamedSharding:
        def resolve(a):
            if a == "dp":
                return self.dp
            if a == "tp":
                return self.tp
            if a == "ep":
                return self.ep_axes
            return a
        return NamedSharding(self.mesh, P(*(resolve(a) for a in axes)))


def constrain(x: jax.Array, ctx: Optional[ShardCtx], *axes) -> jax.Array:
    """with_sharding_constraint that degrades to a no-op without a mesh.

    `axes` uses logical names: "dp" (batch), "tp" (model), None (replicated).
    """
    if ctx is None:
        return x
    return jax.lax.with_sharding_constraint(x, ctx.spec(*axes))


def head_shardable(n: int, ctx: Optional[ShardCtx]) -> bool:
    """True if a head-count dimension divides the tensor-parallel axis size."""
    if ctx is None or ctx.tp is None:
        return False
    return n % ctx.mesh.shape[ctx.tp] == 0


# ---------------------------------------------------------------------------
# numerics
# ---------------------------------------------------------------------------
def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    """RMSNorm with fp32 *statistics* but bf16 tensor math.

    Upcasting the whole tensor (x.astype(f32) * rsqrt * scale) makes every
    downstream TP-boundary collective and its cotangent fp32 — measured
    ~2x wire bytes on the 81-layer hybrid (§Perf cell C).  Only the
    variance reduction needs fp32.
    """
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                   keepdims=True)
    inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
    return x * inv * scale.astype(x.dtype)


def activation_fn(name: str):
    if name == "silu":
        return jax.nn.silu
    if name == "gelu":
        return jax.nn.gelu
    if name == "relu2":  # squared ReLU (nemotron)
        return lambda x: jnp.square(jax.nn.relu(x))
    raise ValueError(name)


def gated(name: str) -> bool:
    """Gated (SwiGLU-style) MLPs use wi+wg; relu2/gelu archs use a plain wi."""
    return name == "silu"


# ---------------------------------------------------------------------------
# rotary embeddings (llama-style rotate-half)
# ---------------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, hd); positions: (..., S) or (S,)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)  # (hd/2,)
    angles = positions.astype(jnp.float32)[..., None] * freqs  # (..., S, hd/2)
    angles = angles[..., None, :]  # broadcast over heads
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# flash attention, pure-jnp (blockwise online softmax, no S×S materialization)
#
# Every model path runs this: train, prefill and the dry-run.  The forward is
# one scan over kv blocks; the backward is FlashAttention-2's, one scan over
# the same blocks that recomputes each block's probabilities from the saved
# per-row log-sum-exp.  The Pallas forward in repro.kernels is validated
# against repro.kernels.ref, which shares this math.
# ---------------------------------------------------------------------------
#: checkpoint names of the attention core's output and per-row log-sum-exp,
#: the residuals its backward reads: a layer remat that saves them does not
#: run the online-softmax forward a second time
ATTN_OUT = "attention.out"
ATTN_LSE = "attention.lse"


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool, q_offset=0,
                    block: int = 512, scale: Optional[float] = None,
                    kv_len: Optional[jax.Array] = None,
                    ctx: Optional[ShardCtx] = None) -> jax.Array:
    """q: (B, Sq, H, hd); k/v: (B, Sk, KV, hd) with H % KV == 0.

    q_offset: global position of q[:, 0] (for causal masking during decode /
    chunked prefill); a causal call with an int offset reads only the keys
    its last query sees.  kv_len: optional valid-length of k/v (decode
    caches).  Returns (B, Sq, H, hd_v).

    The gradient is a custom VJP: the forward keeps q, k, v as given, the
    output and the per-row log-sum-exp (O(S) beyond what the caller holds,
    no per-block state), and the backward recomputes each block's
    probabilities from them once.

    Layout note: internally runs head-major (B, H, S, hd) with GQA KV heads
    repeated, and pins every scan carry to the head-sharded layout — without
    the explicit constraints XLA's SPMD partitioner oscillates between
    head- and sequence-sharded layouts across the online-softmax carries and
    inserts "involuntary full rematerialization" collectives (measured:
    ~160 GB/device of phantom all-gathers on a 2.5B dense model).
    """
    B, Sq, H, hd = q.shape
    _, Sk, KV, hd_v = v.shape
    G = H // KV
    scale = hd ** -0.5 if scale is None else scale
    Sr = Sk  # keys read
    if causal and isinstance(q_offset, int):
        Sr = min(Sk, max(q_offset + Sq, 1))
    block = min(block, Sr)
    nb = -(-Sr // block)
    pad = nb * block - Sr

    shard_heads = head_shardable(H, ctx)

    def pin(x):  # (B, H, ...) head-sharded pin
        if not shard_heads:
            return x
        return constrain(x, ctx, *(("dp", "tp") + (None,) * (x.ndim - 2)))

    def head_major(q, k, v):
        """-> q (B, H, Sq, hd); k, v as (nb, B, H, block, hd) blocks."""
        qh = pin(q.transpose(0, 2, 1, 3))
        k, v = k[:, :Sr], v[:, :Sr]
        if G > 1:  # repeat KV heads -> clean head sharding
            k = jnp.repeat(k, G, axis=2)
            v = jnp.repeat(v, G, axis=2)
        kh = pin(k.transpose(0, 2, 1, 3))                  # (B, H, Sr, hd)
        vh = pin(v.transpose(0, 2, 1, 3))
        if pad:
            kh = jnp.pad(kh, ((0, 0), (0, 0), (0, pad), (0, 0)))
            vh = jnp.pad(vh, ((0, 0), (0, 0), (0, pad), (0, 0)))
        blocks = lambda x: jnp.moveaxis(
            x.reshape(B, H, nb, block, x.shape[-1]), 2, 0)
        return qh, blocks(kh), blocks(vh)

    def kv_major(xb, dtype):
        """The gradient of head_major's k or v blocks -> (B, Sk, KV, d)."""
        x = jnp.moveaxis(xb, 0, 2).reshape(B, H, nb * block, xb.shape[-1])
        x = x[:, :, :Sr].transpose(0, 2, 1, 3)
        if G > 1:  # the repeat's gradient: sum over each KV head's G copies
            x = x.reshape(B, Sr, KV, G, x.shape[-1]).astype(
                jnp.float32).sum(3)
        return jnp.pad(x.astype(dtype),
                       ((0, 0), (0, Sk - Sr), (0, 0), (0, 0)))

    def scores(qh, kj, j, q_pos, kv_len):
        """One block's masked f32 scores, (B, H, Sq, block)."""
        s = pin(jnp.einsum("bhqd,bhjd->bhqj", qh, kj,
                           preferred_element_type=jnp.float32) * scale)
        kv_pos = j * block + jnp.arange(block)
        mask = jnp.ones((Sq, block), bool)
        if causal:
            mask &= q_pos[:, None] >= kv_pos[None, :]
        if kv_len is not None:
            mask &= kv_pos[None, :] < kv_len
        if pad:
            mask &= kv_pos[None, :] < Sr
        return jnp.where(mask[None, None], s, -jnp.inf)

    def forward(q, k, v, q_offset, kv_len):
        """-> (out (B, Sq, H, hd_v) in q's dtype, lse (B, H, Sq) f32)."""
        qh, kb, vb = head_major(q, k, v)
        q_pos = q_offset + jnp.arange(Sq)

        def body(carry, inputs):
            m, l, acc = carry
            kj, vj, j = inputs
            s = scores(qh, kj, j, q_pos, kv_len)
            m_new = jnp.maximum(m, s.max(-1))
            # guard fully-masked rows (m_new == -inf)
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            # no second mask on p: exp(-inf - finite) is already 0, and each
            # avoided (B,H,Sq,block) write is ~160 GiB/step on a 40-layer
            # train
            p = jnp.exp(s - m_safe[..., None])
            corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
            l_new = pin(l * corr + p.sum(-1))
            acc_new = pin(acc * corr[..., None] + jnp.einsum(
                "bhqj,bhjd->bhqd", p.astype(vj.dtype), vj,
                preferred_element_type=jnp.float32))
            return (pin(m_new), l_new, acc_new), None

        m0 = pin(jnp.full((B, H, Sq), -jnp.inf, jnp.float32))
        l0 = pin(jnp.zeros((B, H, Sq), jnp.float32))
        a0 = pin(jnp.zeros((B, H, Sq, hd_v), jnp.float32))
        # nothing differentiates this scan, but an enclosing rematted layer
        # scan partially evaluates it: without the checkpoint it hoists each
        # block's mask, broadcast to (B, H, Sq, block), out of the loop as
        # one stacked residual (1 GiB a 2048-query chunk of an 8K MLA)
        body = jax.checkpoint(body,
                              policy=jax.checkpoint_policies.nothing_saveable)
        (m, l, acc), _ = jax.lax.scan(
            body, (m0, l0, a0), (kb, vb, jnp.arange(nb)))
        out = acc / jnp.maximum(l, 1e-37)[..., None]       # (B, H, Sq, hd_v)
        return out.transpose(0, 2, 1, 3).astype(q.dtype), pin(m + jnp.log(l))

    @jax.custom_vjp
    def attend(q, k, v, q_offset, kv_len):
        with jax.named_scope("attention.flash_fwd"):
            return forward(q, k, v, q_offset, kv_len)[0]

    def attend_fwd(q, k, v, q_offset, kv_len):
        with jax.named_scope("attention.flash_fwd"):
            out, lse = forward(q, k, v, q_offset, kv_len)
        out = checkpoint_name(out, ATTN_OUT)
        lse = checkpoint_name(lse, ATTN_LSE)
        return out, (q, k, v, out, lse, q_offset, kv_len)

    def attend_bwd(res, dout):
        q, k, v, out, lse, q_offset, kv_len = res
        with jax.named_scope("attention.flash_bwd"):
            # the barrier keeps XLA from sharing the forward's head-major
            # copies, which would then live through the rest of the layer's
            # backward: the residuals are q, k and v as the caller holds them
            qh, kb, vb = head_major(*jax.lax.optimization_barrier((q, k, v)))
            q_pos = q_offset + jnp.arange(Sq)
            do = pin(dout.transpose(0, 2, 1, 3))            # (B, H, Sq, hd_v)
            delta = pin(jnp.sum(dout.astype(jnp.float32)
                                * out.astype(jnp.float32), -1)
                        .transpose(0, 2, 1))                # (B, H, Sq)
            # fully-masked rows (lse == -inf) get p = exp(-inf) = 0
            lse = jnp.where(jnp.isfinite(lse), lse, jnp.inf)

            def body(dq, inputs):
                kj, vj, j = inputs
                p = jnp.exp(scores(qh, kj, j, q_pos, kv_len) - lse[..., None])
                dv = jnp.einsum("bhqj,bhqd->bhjd", p.astype(vj.dtype), do,
                                preferred_element_type=jnp.float32)
                dp = pin(jnp.einsum("bhqd,bhjd->bhqj", do, vj,
                                    preferred_element_type=jnp.float32))
                ds = p * (dp - delta[..., None]) * scale
                dq = pin(dq + jnp.einsum(
                    "bhqj,bhjd->bhqd", ds.astype(kj.dtype), kj,
                    preferred_element_type=jnp.float32))
                dk = jnp.einsum("bhqj,bhqd->bhjd", ds.astype(qh.dtype), qh,
                                preferred_element_type=jnp.float32)
                return dq, (pin(dk.astype(kj.dtype)),
                            pin(dv.astype(vj.dtype)))

            dq0 = pin(jnp.zeros((B, H, Sq, hd), jnp.float32))
            dq, (dkb, dvb) = jax.lax.scan(body, dq0, (kb, vb, jnp.arange(nb)))
            return (dq.transpose(0, 2, 1, 3).astype(q.dtype),
                    kv_major(dkb, k.dtype), kv_major(dvb, v.dtype),
                    None, None)

    attend.defvjp(attend_fwd, attend_bwd)
    return attend(q, k, v, q_offset, kv_len)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     cache_index: jax.Array, *,
                     scale: Optional[float] = None) -> jax.Array:
    """Single-position attention against a (possibly longer) cache.

    q: (B, 1, H, hd); caches: (B, S, KV, hd).  Positions > cache_index masked.
    """
    B, _, H, hd = q.shape
    S, KV = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    scale = hd ** -0.5 if scale is None else scale
    qg = q.reshape(B, KV, G, hd)
    s = jnp.einsum("bkgd,bjkd->bkgj", qg, k_cache,
                   preferred_element_type=jnp.float32) * scale
    valid = jnp.arange(S) <= cache_index
    s = jnp.where(valid[None, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgj,bjkd->bkgd", p.astype(v_cache.dtype), v_cache,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, 1, H, v_cache.shape[-1]).astype(q.dtype)


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------
def dense_init(key, shape, dtype, scale: float = 1.0):
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    std = scale / np.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def stack_init(key, n: int, init_fn):
    """vmap an init over a leading layer dimension."""
    return jax.vmap(init_fn)(jax.random.split(key, n))
