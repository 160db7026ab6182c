"""Plain float32 reference of a DeepSeek-V3-block decoder's first train
steps, at one chip's share of an expert-parallel layer: MLA attention
without q-LoRA, a leading dense SwiGLU layer, then MoE layers whose router
scores every expert of the layer and whose held experts are computed here.

It imports nothing of the program.  From the configuration file and the
seeds it makes the same initial weights (the stated init, drawn from
`jax.random.key(init seed)`; router and its bias in float32, the rest
stored in bfloat16) and the same batches (`dense_lm.batch`), and follows
three AdamW steps:

  * MLA: q = h wq (H heads of nope + rope), latent = h wkv_a, the rope
    part of the latent roped and shared by every head, the rest RMS-normed
    and expanded by wkv_b into each head's k_nope and v; a plain per-head
    causal softmax scaled by (nope + rope) ** -0.5.
  * Router: sigmoid scores of h w_r over all E experts in float32; each
    token's top-k of scores + bias; weights = the chosen scores over their
    sum, times the routed scaling.  The held experts [first, first + G)
    are computed densely over every token and weighted by the token's
    routing weight for each (zero where not chosen): no sort, no kernel.
    The shared experts are one SwiGLU.
  * Loss: mean next-token cross entropy plus balance_alpha times each MoE
    layer's sequence-wise sum_i f_i P_i (DeepSeek-V3 eq. 17-20).
  * After the AdamW update (in float32, every leaf but the bias, decay on
    leaves of two or more dims), each layer's bias moves by bias_rate *
    sign(mean load - load) over all E experts.

Rows of the batch go through one at a time and each layer is recomputed in
the backward pass.  `precision="fp8"` and `rows` are the controls of
`dense_lm`.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.dense_lm import (F32, _ein, _normal, _rms, _rope,
                                      _update, leaf_norms, lr_at)

#: leaves kept in float32 as the program keeps them
FLOAT32 = ("router", "router_bias")
#: score margins under which a near-tie of routing is counted
TIE_MARGINS = (1e-4, 3e-4, 1e-3, 1e-2)


def shape(cfg: dict) -> dict:
    """The sizes the reference reads from a configuration file."""
    E = cfg["n_routed_experts"] * cfg["ep_size"]
    return {"d": cfg["hidden_size"], "H": cfg["num_attention_heads"],
            "dn": cfg["qk_nope_head_dim"], "dr": cfg["qk_rope_head_dim"],
            "dv": cfg["v_head_dim"], "kvr": cfg["kv_lora_rank"],
            "ff": cfg["intermediate_size"],
            "ffe": cfg["moe_intermediate_size"],
            "shared": cfg["n_shared_experts"], "E": E,
            "G": cfg["n_routed_experts"], "first":
            cfg["ep_rank"] * cfg["n_routed_experts"],
            "K": cfg["num_experts_per_tok"], "V": cfg["vocab_size"],
            "L": cfg["num_hidden_layers"],
            "dense": cfg["first_k_dense_replace"],
            "eps": cfg["rms_norm_eps"], "theta": float(cfg["rope_theta"]),
            "scale": float(cfg["routed_scaling_factor"]),
            "alpha": cfg["assumed"]["balance_alpha"],
            "gamma": cfg["assumed"]["bias_rate"]}


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------
def init_params(cfg: dict, init_seed: int):
    """normal(0, 0.02) embedding, normal(0, 1/sqrt(fan_in)) matrices, unit
    norm scales, a zero bias; the key is split as the stated init splits
    it, and the held experts are the whole layer's experts first..first+G
    drawn from their own keys."""
    s = shape(cfg)
    d, H, dn, dr, dv, kvr = (s[k] for k in ("d", "H", "dn", "dr", "dv",
                                            "kvr"))

    def dense(key, shp):
        return _normal(key, shp, 1.0 / np.sqrt(shp[-2]))

    def swiglu(key, ff):
        m = jax.random.split(key, 3)
        return {"wi": dense(m[0], (d, ff)), "wo": dense(m[1], (ff, d)),
                "wg": dense(m[2], (d, ff))}

    def attention(key):
        a = jax.random.split(key, 6)
        return {"wq": dense(a[0], (d, H * (dn + dr))),
                "wkv_a": dense(a[2], (d, kvr + dr)),
                "kv_norm": jnp.ones((kvr,), F32),
                "wkv_b": dense(a[3], (kvr, H * (dn + dv))),
                "wo": dense(a[4], (H * dv, d))}

    def experts(key):
        m = jax.random.split(key, 6)
        keys = jax.random.split(m[1], s["E"])[s["first"]:s["first"] + s["G"]]
        return {"router": dense(m[0], (d, s["E"])),
                "experts": jax.vmap(lambda k: swiglu(k, s["ffe"]))(keys),
                "router_bias": jnp.zeros((s["E"],), F32),
                "shared": swiglu(m[2], s["ffe"] * s["shared"])}

    def layer(key, moe):
        ka, km, _, _ = jax.random.split(key, 4)
        return {"attn": attention(ka),
                "mlp": experts(km) if moe else swiglu(km, s["ff"]),
                "norm1": jnp.ones((d,), F32), "norm2": jnp.ones((d,), F32)}

    @jax.jit
    def make(key):
        ks = jax.random.split(key, 8)
        p = {"embed": _normal(ks[0], (s["V"], d), 0.02),
             "final_norm": jnp.ones((d,), F32),
             "dense_layers": jax.vmap(partial(layer, moe=False))(
                 jax.random.split(ks[1], s["dense"])),
             "moe_layers": jax.vmap(partial(layer, moe=True))(
                 jax.random.split(ks[2], s["L"] - s["dense"])),
             "lm_head": dense(ks[3], (d, s["V"]))}
        dt = jnp.dtype(cfg["torch_dtype"])
        return jax.tree_util.tree_map_with_path(
            lambda path, x: x if path[-1].key in FLOAT32 else x.astype(dt),
            p)

    return make(jax.random.key(init_seed))


# ---------------------------------------------------------------------------
# forward and loss of one row
# ---------------------------------------------------------------------------
def _swiglu(p, x, precision):
    mm = partial(_ein, "si,io->so", precision=precision)
    return mm(jax.nn.silu(mm(x, p["wg"])) * mm(x, p["wi"]), p["wo"])


def _attention(s, precision, x, a):
    """x (S, d) -> (S, d): MLA without q-LoRA, one head at a time."""
    S = x.shape[0]
    H, dn, dr, dv, kvr = s["H"], s["dn"], s["dr"], s["dv"], s["kvr"]
    mm = partial(_ein, "si,io->so", precision=precision)
    q = mm(x, a["wq"]).reshape(1, S, H, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], s["theta"])], -1)
    latent = mm(x, a["wkv_a"])
    k_rope = _rope(latent[None, :, None, kvr:], s["theta"])[0, :, 0]
    kv = mm(_rms(latent[:, :kvr], a["kv_norm"], s["eps"]), a["wkv_b"])
    kv = kv.reshape(S, H, dn + dv)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
        k_rope[:, None], (S, H, dr))], -1)
    causal = jnp.tril(jnp.ones((S, S), bool))

    def head(qkv):
        qh, kh, vh = qkv
        sc = _ein("qd,kd->qk", qh, kh, precision) * (dn + dr) ** -0.5
        sc = jnp.where(causal, sc, -jnp.inf)
        return _ein("qk,kd->qd", jax.nn.softmax(sc, -1), vh, precision)

    o = jax.lax.map(jax.checkpoint(head), (jnp.moveaxis(q[0], 1, 0),
                                           jnp.moveaxis(k, 1, 0),
                                           jnp.moveaxis(kv[..., dn:], 1, 0)))
    return mm(jnp.moveaxis(o, 0, 1).reshape(S, H * dv), a["wo"])


def route(s, x, p, precision):
    """-> scores (S, E), chosen experts (S, K), their weights (S, K)."""
    scores = jax.nn.sigmoid(_ein("si,io->so", x, p["router"], precision))
    _, idx = jax.lax.top_k(scores + p["router_bias"], s["K"])
    w = jnp.take_along_axis(scores, idx, -1)
    return scores, idx, w / w.sum(-1, keepdims=True) * s["scale"]


def held_ties(s, x, p, precision):
    """For each margin m of s["ties"]: the pairs on held experts that a
    swap of a token's K-th and (K+1)-th choice, closer than m in score +
    bias, would take off the held experts (the program may route such a
    near-tie the other way at its precision)."""
    choice = jax.nn.sigmoid(_ein("si,io->so", x, p["router"], precision)) \
        + p["router_bias"]
    vals, ids = jax.lax.top_k(choice, s["K"] + 1)
    held = (ids >= s["first"]) & (ids < s["first"] + s["G"])
    out_of_held = held[:, -2] & ~held[:, -1]
    gap = vals[:, -2] - vals[:, -1]
    return jnp.stack([jnp.sum(out_of_held & (gap < m)) for m in s["ties"]])


def _moe(s, precision, x, p):
    """x (S, d) -> (out, balance, load (E,), held pairs and their ties)."""
    S, E, K, G = x.shape[0], s["E"], s["K"], s["G"]
    scores, idx, w = route(s, x, p, precision)
    chosen = jax.nn.one_hot(idx, E, dtype=F32)                 # (S, K, E)
    weight = jnp.einsum("sk,ske->se", w, chosen)               # (S, E)
    held = weight[:, s["first"]:s["first"] + G]                # (S, G)

    def expert(pw):
        pe, we = pw
        return _swiglu(pe, x, precision) * we[:, None]

    y = jax.lax.map(jax.checkpoint(expert),
                    (p["experts"], jnp.moveaxis(held, 1, 0))).sum(0)
    load = chosen.sum((0, 1))
    f = load * E / (K * S)
    prob = (scores / scores.sum(-1, keepdims=True)).mean(0)
    n_held = chosen[:, :, s["first"]:s["first"] + G].sum()
    counts = jnp.concatenate([n_held[None],
                              held_ties(s, x, p, precision).astype(F32)])
    return (y + _swiglu(p["shared"], x, precision), jnp.sum(f * prob),
            load, counts)


def _layer(s, precision, moe, x, p):
    p = jax.tree.map(lambda t: t.astype(F32), p)
    x = x + _attention(s, precision, _rms(x, p["norm1"], s["eps"]),
                       p["attn"])
    h = _rms(x, p["norm2"], s["eps"])
    if not moe:
        return x + _swiglu(p["mlp"], h, precision), None
    out, balance, load, counts = _moe(s, precision, h, p["mlp"])
    return x + out, (balance, load, counts)


def loss(s, precision, params, tokens, labels):
    """One row: (cross entropy + alpha * sum of the MoE layers' balance
    terms, (per-layer loads (L, E), [held pairs, their ties...]))."""
    x = params["embed"].astype(F32)[tokens[0]]
    dense = jax.checkpoint(partial(_layer, s, precision, False))
    moe = jax.checkpoint(partial(_layer, s, precision, True))
    x, _ = jax.lax.scan(dense, x, params["dense_layers"])
    x, (balance, load, counts) = jax.lax.scan(moe, x, params["moe_layers"])
    h = _rms(x, params["final_norm"].astype(F32), s["eps"])
    logits = _ein("sd,dv->sv", h, params["lm_head"].astype(F32),
                  precision)[:-1]
    ll = jnp.take_along_axis(logits, labels[0, 1:, None], -1)[:, 0]
    ce = jnp.mean(jax.nn.logsumexp(logits, -1) - ll)
    return ce + s["alpha"] * balance.sum(), (load, counts.sum(0))


@partial(jax.jit, static_argnums=(0, 1), donate_argnums=(2,))
def _accumulate(s_items, precision, acc, params, tokens, labels, w):
    s = dict(s_items)
    with jax.default_matmul_precision("highest"):
        (l, (load, held)), g = jax.value_and_grad(
            partial(loss, s, precision), has_aux=True)(params, tokens,
                                                       labels)
    return jax.tree.map(lambda a, b: a + w * b, acc, g), l, load, held


def loss_and_grads(cfg, precision, params, tokens, labels,
                   ties=TIE_MARGINS):
    """Loss, gradients, per-layer expert loads, and the pairs routed to
    held experts with, for each margin of `ties`, those of them on a
    near-tie (`held_ties`), of the whole batch, one row at a time."""
    items = tuple(sorted(dict(shape(cfg), ties=tuple(ties)).items()))
    B = tokens.shape[0]
    acc = jax.tree.map(lambda p: jnp.zeros(p.shape, F32), params)
    total, load, counts = 0.0, 0.0, 0
    for r in range(B):
        acc, l, lo, c = _accumulate(items, precision, acc, params,
                                    tokens[r:r + 1], labels[r:r + 1],
                                    1.0 / B)
        total += float(l) / B
        load = load + lo
        counts = counts + np.asarray(c, np.int64)
    return total, acc, load, [int(c) for c in counts]


# ---------------------------------------------------------------------------
# three steps
# ---------------------------------------------------------------------------
def _split_bias(tree):
    """(every leaf but the router bias, the biases of the MoE layers)."""
    mlp = dict(tree["moe_layers"]["mlp"])
    bias = mlp.pop("router_bias")
    return dict(tree, moe_layers=dict(tree["moe_layers"], mlp=mlp)), bias


def _with_bias(tree, bias):
    mlp = dict(tree["moe_layers"]["mlp"], router_bias=bias)
    return dict(tree, moe_layers=dict(tree["moe_layers"], mlp=mlp))


def three_steps(cfg: dict, batch_size: int, seq: int, init_seed: int,
                data_seed: int, precision: str = "f32",
                rows: int | None = None) -> dict:
    """Losses of steps 1-3; pairs routed to held experts in each and, for
    each margin of TIE_MARGINS, those of them on a near-tie; per-leaf norms
    of the first gradient as the optimizer takes it (after clipping; every
    leaf but the bias), and of every leaf's change over the three steps.
    `rows` keeps only the batch's first rows."""
    from bench.reference.dense_lm import batch
    opt = cfg["optimizer"]
    hyper = (opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"],
             opt["clip_norm"])
    gamma = shape(cfg)["gamma"]
    params, bias = _split_bias(init_params(cfg, init_seed))
    m = jax.tree.map(lambda p: np.zeros(p.shape, np.float32), params)
    v = jax.tree.map(lambda p: np.zeros(p.shape, np.float32), params)
    losses, held, ties, g1 = [], [], [], None
    for step in (1, 2, 3):
        tokens, labels = batch(cfg, batch_size, seq, step - 1, data_seed)
        tokens, labels = tokens[:rows], labels[:rows]
        l, grads, load, counts = loss_and_grads(
            cfg, precision, _with_bias(params, bias), tokens, labels)
        grads, _ = _split_bias(grads)
        losses.append(l)
        held.append(counts[0])
        ties.append(dict(zip(TIE_MARGINS, counts[1:])))
        if step == 1:
            norms = leaf_norms(grads)
            gn = np.sqrt(sum(n * n for n in norms.values()))
            scale = min(1.0, opt["clip_norm"] / (gn + 1e-9))
            g1 = {k: n * scale for k, n in norms.items()}
        params, m, v = _update(hyper, params, grads, jax.device_put(m),
                               jax.device_put(v), lr_at(opt, step),
                               1 - opt["b1"] ** step, 1 - opt["b2"] ** step)
        del grads
        bias = bias + gamma * jnp.sign(load.mean(-1, keepdims=True) - load)
        m, v = jax.device_get(m), jax.device_get(v)
    del m, v
    p0 = init_params(cfg, init_seed)
    delta = jax.jit(lambda a, b: jax.tree.map(
        lambda x, y: x.astype(F32) - y.astype(F32), a, b))(
            _with_bias(params, bias), p0)
    del params, p0
    return {"losses": losses, "held_pairs": held, "held_ties": ties,
            "grad1": g1,
            "delta3": leaf_norms(delta)}
