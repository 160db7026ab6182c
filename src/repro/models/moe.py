"""MLP layers: dense (gated / plain) and mixture-of-experts.

The router scores every expert of the layer (softmax, or sigmoid with a
per-expert correction bias that only steers the choice, DeepSeek-V3's
noaux_tc) and picks each token's top-k.  A chip holds `experts_held` of
them (expert parallelism, `ep_size` shares) and computes only their part
of the result, plus the shared experts once.

One device (ctx None): dropless.  The (token, k) pairs are sorted by
expert, the held experts' rows go through a grouped matmul
(`kernels/expert_matmul`), and each token's rows are gathered back and
summed by their weights.  No capacity, no dropped pair.

Under a mesh (the sharded dry-run) the GShard dense one-hot dispatch
stays: the dispatch/combine tensors shard over the expert axis (= "model"
mesh axis), the combine einsum contracts it and lowers to one all-reduce,
and pairs past an expert's capacity are dropped.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.expert_matmul import expert_matmul, row_tile
from repro.models.common import (ShardCtx, activation_fn, constrain,
                                 dense_init, gated)


# ---------------------------------------------------------------------------
# dense MLP
# ---------------------------------------------------------------------------
def mlp_init(key, cfg: ModelConfig, dtype, d_ff: Optional[int] = None):
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    p = {"wi": dense_init(ks[0], (d, ff), dtype),
         "wo": dense_init(ks[1], (ff, d), dtype)}
    if gated(cfg.activation):
        p["wg"] = dense_init(ks[2], (d, ff), dtype)
    return p


def mlp_apply(cfg: ModelConfig, p, x, ctx: Optional[ShardCtx]):
    act = activation_fn(cfg.activation)
    h = x @ p["wi"]
    h = constrain(h, ctx, "dp", None, "tp")
    if "wg" in p:
        h = act(x @ p["wg"]) * h
    else:
        h = act(h)
    out = h @ p["wo"]
    return constrain(out, ctx, "dp", "tp", None)


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------
def moe_init(key, cfg: ModelConfig, dtype):
    """Router over all experts; the held experts' weights are those of
    experts [first, first + G) of the whole layer, key for key."""
    d, E, ffe = cfg.d_model, cfg.num_experts, cfg.d_ff_expert
    G, first = cfg.experts_held, cfg.ep_rank * cfg.experts_held
    ks = jax.random.split(key, 6)

    def one_expert(k):
        kk = jax.random.split(k, 3)
        p = {"wi": dense_init(kk[0], (d, ffe), dtype),
             "wo": dense_init(kk[1], (ffe, d), dtype)}
        if gated(cfg.activation):
            p["wg"] = dense_init(kk[2], (d, ffe), dtype)
        return p

    p = {"router": dense_init(ks[0], (d, E), jnp.float32),
         "experts": jax.vmap(one_expert)(
             jax.random.split(ks[1], E)[first:first + G])}
    if cfg.router_bias:
        p["router_bias"] = jnp.zeros((E,), jnp.float32)
    if cfg.num_shared_experts:
        p["shared"] = mlp_init(ks[2], cfg, dtype,
                               d_ff=cfg.d_ff_expert * cfg.num_shared_experts)
    return p


def capacity(cfg: ModelConfig, tokens_per_group: int) -> int:
    c = int(tokens_per_group * cfg.top_k / cfg.num_experts
            * cfg.capacity_factor)
    # round to an MXU-friendly multiple where it matters, keep >= top_k
    c = max(c, cfg.top_k)
    return -(-c // 8) * 8


def route(cfg: ModelConfig, p, x):
    """x (B, S, d) -> scores (B, S, E) f32, chosen experts (B, S, K) and
    their weights (B, S, K) f32.  The bias moves the choice only; the
    weights are the chosen scores, renormalised and scaled."""
    logits = x.astype(jnp.float32) @ p["router"]
    if cfg.router_score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, -1)
    choice = scores
    if "router_bias" in p:
        choice = scores + jax.lax.stop_gradient(p["router_bias"])
    _, idx = jax.lax.top_k(choice, cfg.top_k)
    w = jnp.take_along_axis(scores, idx, -1)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    return scores, idx, w * cfg.routed_scaling


def routing_stats(cfg: ModelConfig, scores, idx, held_pairs) -> dict:
    """load: pairs per expert (E,); balance: DeepSeek-V3's sequence-wise
    sum_i f_i P_i, f_i = E/(K S) * the sequence's pairs on expert i, P_i =
    the mean over its tokens of the scores normalised to sum 1, averaged
    over sequences (1 when balanced); held_pairs: pairs this chip
    computed."""
    E, K = cfg.num_experts, cfg.top_k
    S = idx.shape[1]
    per_seq = jax.nn.one_hot(idx, E, dtype=jnp.float32).sum((1, 2))  # (B, E)
    f = per_seq * (E / (K * S))
    prob = scores / jnp.maximum(scores.sum(-1, keepdims=True), 1e-9)
    balance = jnp.mean(jnp.sum(f * prob.mean(1), -1))
    return {"load": per_seq.sum(0), "balance": balance,
            "held_pairs": held_pairs.astype(jnp.int32)}


def moe_apply(cfg: ModelConfig, p, x, ctx: Optional[ShardCtx],
              router_stats: bool = False):
    """x: (B, S, d) -> y, or (y, stats) with `router_stats` (see
    `routing_stats`).  Routing groups = batch rows."""
    B, S, d = x.shape
    if S == 1 and B > 1:
        # decode: route the whole batch as ONE group — per-row groups pad
        # every expert's capacity to top_k PER TOKEN (measured ~250x slot
        # waste on deepseek-v3 decode_32k; §Perf cell B iteration 2)
        y, stats = moe_apply(cfg, p, x.reshape(1, B, d), ctx, True)
        y = y.reshape(B, S, d)
        return (y, stats) if router_stats else y
    with jax.named_scope("moe.route"):
        scores, idx, w = route(cfg, p, x)
    if ctx is None:
        y, held = _sorted_experts(cfg, p, x, idx, w)
    else:
        y, held = _gshard_experts(cfg, p, x, idx, w, ctx)
    if cfg.num_shared_experts:
        with jax.named_scope("moe.shared"):
            y = y + mlp_apply(cfg, p["shared"], x, ctx)
    if router_stats:
        return y, routing_stats(cfg, scores, idx, held)
    return y


#: pairs one sorted dispatch takes at most; a layer with more goes
#: through in equal chunks of tokens, one after another, so the backward
#: pass holds one chunk's buffers (16,384 tokens x 6: the v5e compile of
#: Moonlight's 4 x 8192 step plans a 14.18 GB peak so, 14.41 GB unchunked)
MAX_PAIRS = 98_304


def _sorted_experts(cfg: ModelConfig, p, x, idx, w):
    """Dropless: sum over each token's chosen held experts e of
    w_e * SwiGLU_e(x).  Pairs are sorted held experts first (by expert),
    then the rest; the static row buffer holds all T*K pairs (all of a
    token's experts may be held), and only the held experts' row tiles are
    multiplied (the grouped matmul's groups are the G held experts)."""
    B, S, d = x.shape
    T, K = B * S, cfg.top_k
    n = 1
    while T * K // n > MAX_PAIRS and T % (2 * n) == 0:
        n *= 2
    if n > 1:
        parts = [_sorted_experts(cfg, p, xc[None], ic[None], wc[None])
                 for xc, ic, wc in zip(x.reshape(n, T // n, d),
                                       idx.reshape(n, T // n, K),
                                       w.reshape(n, T // n, K))]
        return (jnp.concatenate([y for y, _ in parts], 1).reshape(B, S, d),
                sum(h for _, h in parts))
    G, first = cfg.experts_held, cfg.ep_rank * cfg.experts_held
    act = activation_fn(cfg.activation)
    ex = p["experts"]
    P = T * K
    m = -(-P // row_tile(P)) * row_tile(P)
    with jax.named_scope("moe.dispatch"):
        local = idx.reshape(P) - first
        key = jnp.where((local >= 0) & (local < G), local, G)
        order = jnp.argsort(key, stable=True)
        sizes = jnp.zeros((G + 1,), jnp.int32).at[key].add(1)[:G]
        live = sizes.sum()
        inv = jnp.zeros((P,), jnp.int32).at[order].set(
            jnp.arange(P, dtype=jnp.int32))
        src = jnp.pad(order // K, (0, m - P))
        pairs = Pairs(src, inv, live, K)
        xs = pairs.gather(x.reshape(T, d))
        ws = jnp.where(jnp.arange(m) < live,
                       jnp.pad(w.reshape(P)[order], (0, m - P)), 0.0)
    with jax.named_scope("moe.experts"):
        h = expert_matmul(xs, ex["wi"], sizes)
        if "wg" in ex:
            h = act(expert_matmul(xs, ex["wg"], sizes)) * h
        else:
            h = act(h)
        # the routing weight on each row before the (linear) down matmul
        h = (h.astype(jnp.float32) * ws[:, None]).astype(h.dtype)
        ys = expert_matmul(h, ex["wo"], sizes)
    with jax.named_scope("moe.combine"):
        y = pairs.scatter(ys)
    return y.reshape(B, S, d), live


class Pairs:
    """The pairs' row order.  `gather`: token rows to the sorted buffer
    (m, d); `scatter`: each token's sum of its live rows (T, d), float32
    accumulation.  Each is the other's transpose, so each backward pass is
    a gather too; rows past `live` (not held, pad, or left uninitialized
    by the grouped matmul) are never summed."""

    def __init__(self, src, inv, live, k):
        self.src, self.inv, self.live, self.k = src, inv, live, k

    def gather(self, x):
        return _gather(x, self.src, self.inv, self.live, self.k)

    def scatter(self, ys):
        return _scatter(ys, self.src, self.inv, self.live, self.k)


def _gather_fwd_impl(x, src):
    return x[src]


def _scatter_impl(ys, inv, live, k):
    rows = jnp.where((inv < live)[:, None], ys[inv], 0).astype(jnp.float32)
    return rows.reshape(-1, k, ys.shape[-1]).sum(1).astype(ys.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(4,))
def _gather(x, src, inv, live, k):
    return _gather_fwd_impl(x, src)


def _gather_fwd(x, src, inv, live, k):
    return _gather_fwd_impl(x, src), (src, inv, live)


def _gather_bwd(k, res, g):
    src, inv, live = res
    return _scatter_impl(g, inv, live, k), None, None, None


_gather.defvjp(_gather_fwd, _gather_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(4,))
def _scatter(ys, src, inv, live, k):
    return _scatter_impl(ys, inv, live, k)


def _scatter_fwd(ys, src, inv, live, k):
    return _scatter_impl(ys, inv, live, k), (src, inv, live)


def _scatter_bwd(k, res, g):
    src, inv, live = res
    return _gather_fwd_impl(g, src), None, None, None


_scatter.defvjp(_scatter_fwd, _scatter_bwd)


def _gshard_experts(cfg: ModelConfig, p, x, idx, w, ctx: ShardCtx):
    """GShard one-hot dispatch over all experts (every expert held),
    capacity C a routing group; pairs past it are dropped."""
    assert cfg.ep_size == 1, "the sharded path holds every expert"
    B, S, d = x.shape
    E, K = cfg.num_experts, cfg.top_k
    C = capacity(cfg, S)
    act = activation_fn(cfg.activation)
    # batch sharding of routing tensors: drop when EP spans the data axes
    bsp = None if (ctx is not None and ctx.ep_covers_dp) else "dp"

    # position of each (token, k) assignment within its expert's capacity
    khot = jax.nn.one_hot(idx, E, dtype=jnp.int32)         # (B, S, K, E)
    flat = khot.reshape(B, S * K, E)
    pos = jnp.cumsum(flat, axis=1) - flat                  # (B, S*K, E)
    pos = pos.reshape(B, S, K, E)
    in_cap = (pos < C) & (khot > 0)

    # dispatch: (B, S, E, C) one-hot over capacity slots, sharded on E
    pos_in_e = (pos * khot).sum(-1)                        # (B, S, K)
    slot_hot = jax.nn.one_hot(pos_in_e, C, dtype=x.dtype)  # (B, S, K, C)
    keep = in_cap.any(-1).astype(x.dtype)                  # (B, S, K)

    def accum(carry, k):
        disp, comb = carry
        ek = jax.nn.one_hot(idx[:, :, k], E, dtype=x.dtype)
        contrib = (ek[..., None] * slot_hot[:, :, k, None, :]
                   * keep[:, :, k, None, None])            # (B, S, E, C)
        return (disp + contrib,
                comb + contrib * w[:, :, k, None, None].astype(x.dtype)), None

    z = jnp.zeros((B, S, E, C), x.dtype)
    z = constrain(z, ctx, bsp, None, "ep", None)
    (dispatch, combine), _ = jax.lax.scan(accum, (z, z), jnp.arange(K))
    dispatch = constrain(dispatch, ctx, bsp, None, "ep", None)
    combine = constrain(combine, ctx, bsp, None, "ep", None)

    xe = jnp.einsum("bsd,bsec->becd", x, dispatch)         # (B, E, C, d)
    xe = constrain(xe, ctx, bsp, "ep", None, None)
    h = jnp.einsum("becd,edf->becf", xe, p["experts"]["wi"])
    if "wg" in p["experts"]:
        h = act(jnp.einsum("becd,edf->becf", xe, p["experts"]["wg"])) * h
    else:
        h = act(h)
    ye = jnp.einsum("becf,efd->becd", h, p["experts"]["wo"])
    ye = constrain(ye, ctx, bsp, "ep", None, None)
    y = jnp.einsum("becd,bsec->bsd", ye, combine)          # all-reduce over E
    y = constrain(y, ctx, bsp, "tp" if bsp else None, None)
    return y, keep.sum()


def step_router_bias(cfg: ModelConfig, bias, load):
    """DeepSeek-V3's bias rule after a step: each expert's bias moves by
    bias_rate toward balance, sign(mean load - load), over all experts."""
    mean = load.mean(-1, keepdims=True)
    return bias + cfg.bias_rate * jnp.sign(mean - load)
