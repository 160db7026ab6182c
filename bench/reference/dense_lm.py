"""Plain float32 reference of a dense GQA decoder's first train steps.

It imports nothing of the program.  From the configuration file and the
seeds it makes the same initial weights (the stated init, drawn from
`jax.random.key(init seed)` and stored in bfloat16), the same batches (the
stated synthetic-token rule), and follows three AdamW steps: the forward
pass and its gradients in float32 at `highest` matmul precision, a plain
softmax attention scaled by head_dim ** -0.5 (with an RMSNorm over each
head's q and k where the configuration has `qk_norm`), the update in
float32, the weights stored back in the type the configuration states.
Rows of the batch go through one at a time and each layer is recomputed
in the backward pass, so it fits one chip once the program's state is
freed.

`precision="fp8"` is the control: every matmul operand is rounded to an
8-bit float (e4m3 forward, e5m2 backward, each tensor scaled to its
largest magnitude), the step that would tempt a later change.  Every
rounding is a reduce-precision op, which the compiler keeps.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# inputs: weights and batches from the seeds
# ---------------------------------------------------------------------------
def _normal(key, shape, std):
    return (jax.random.normal(key, shape, F32) * std)


def _stored(x, cfg):
    """Stored in the configuration's type, as the weights are served."""
    return x.astype(jnp.dtype(cfg["torch_dtype"]))


def init_params(cfg: dict, init_seed: int):
    """normal(0, 0.02) embedding, normal(0, 1/sqrt(fan_in)) matrices, unit
    norm scales; the key is split as the stated init splits it."""
    d, H, KV = (cfg["hidden_size"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"])
    hd, ff, V, L = (cfg["head_dim"], cfg["intermediate_size"],
                    cfg["vocab_size"], cfg["num_hidden_layers"])

    def dense(key, shape):
        return _normal(key, shape, 1.0 / np.sqrt(shape[-2]))

    def layer(key):
        ka, km, _, _ = jax.random.split(key, 4)
        a = jax.random.split(ka, 6)
        m = jax.random.split(km, 3)
        attn = {"wq": dense(a[0], (d, H * hd)),
                "wk": dense(a[1], (d, KV * hd)),
                "wv": dense(a[2], (d, KV * hd)),
                "wo": dense(a[3], (H * hd, d))}
        if cfg.get("qk_norm"):
            attn["q_norm"] = jnp.ones((hd,), F32)
            attn["k_norm"] = jnp.ones((hd,), F32)
        return {"attn": attn,
                "mlp": {"wi": dense(m[0], (d, ff)),
                        "wo": dense(m[1], (ff, d)),
                        "wg": dense(m[2], (d, ff))},
                "norm1": jnp.ones((d,), F32), "norm2": jnp.ones((d,), F32)}

    @jax.jit
    def make(key):
        ks = jax.random.split(key, 8)
        p = {"embed": _normal(ks[0], (V, d), 0.02),
             "final_norm": jnp.ones((d,), F32),
             "dense_layers": jax.vmap(layer)(jax.random.split(ks[1], L))}
        return jax.tree.map(lambda x: _stored(x, cfg), p)

    return make(jax.random.key(init_seed))


def batch(cfg: dict, batch_size: int, seq: int, step: int, data_seed: int):
    """(tokens, labels), each (batch, seq) int32, uniform over the
    vocabulary from SeedSequence([data_seed, step, 0])."""
    rng = np.random.default_rng(np.random.SeedSequence([data_seed, step, 0]))
    V = cfg["vocab_size"]
    tokens = rng.integers(0, V, (batch_size, seq)).astype(np.int32)
    labels = rng.integers(0, V, (batch_size, seq)).astype(np.int32)
    return tokens, labels


# ---------------------------------------------------------------------------
# precision of the matmuls
# ---------------------------------------------------------------------------
def _round8(x, exponent_bits, mantissa_bits, top):
    """x scaled so its largest magnitude is `top`, rounded to an 8-bit
    float of the given exponent and mantissa bits, and scaled back."""
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, amax / top, 1.0)
    return jax.lax.reduce_precision(x / s, exponent_bits=exponent_bits,
                                    mantissa_bits=mantissa_bits) * s


@jax.custom_vjp
def _fp8(x):
    return _round8(x, 4, 3, 240.0)          # e4m3, its largest finite 240


def _fp8_fwd(x):
    return _fp8(x), None


def _fp8_bwd(_, g):
    return (_round8(g, 5, 2, 57344.0),)     # e5m2


_fp8.defvjp(_fp8_fwd, _fp8_bwd)


def _ein(spec, a, b, precision):
    if precision == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


# ---------------------------------------------------------------------------
# forward and loss
# ---------------------------------------------------------------------------
def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, theta):
    S, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(S, dtype=F32)[:, None] * freqs
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(cfg, precision, x, p):
    B, S, d = x.shape
    H, KV, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    mm = partial(_ein, "bsi,io->bso", precision=precision)
    p = jax.tree.map(lambda w: w.astype(F32), p)
    a, m = p["attn"], p["mlp"]
    h = _rms(x, p["norm1"], eps)
    q = mm(h, a["wq"]).reshape(B, S, H, hd)
    k = mm(h, a["wk"]).reshape(B, S, KV, hd)
    if cfg["qk_norm"]:
        q, k = _rms(q, a["q_norm"], eps), _rms(k, a["k_norm"], eps)
    q, k = _rope(q, theta), _rope(k, theta)
    v = mm(h, a["wv"]).reshape(B, S, KV, hd)
    causal = jnp.tril(jnp.ones((S, S), bool))

    def attend(qkv):
        """Softmax attention of one kv head's group of query heads."""
        qg, kg, vg = qkv
        s = _ein("bqgd,bkd->bgqk", qg, kg, precision) * hd ** -0.5
        s = jnp.where(causal, s, -jnp.inf)
        return _ein("bgqk,bkd->bqgd", jax.nn.softmax(s, axis=-1), vg,
                    precision)

    # query head h reads kv head h // (H // KV); one kv head at a time
    groups = (jnp.moveaxis(q.reshape(B, S, KV, H // KV, hd), 2, 0),
              jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0))
    o = jax.lax.map(jax.checkpoint(attend), groups)       # (KV, B, S, G, hd)
    x = x + mm(jnp.moveaxis(o, 0, 2).reshape(B, S, H * hd), a["wo"])
    h = _rms(x, p["norm2"], eps)
    return x + mm(jax.nn.silu(mm(h, m["wg"])) * mm(h, m["wi"]), m["wo"])


def loss(cfg, precision, params, tokens, labels):
    """Mean next-token cross entropy of (tokens, labels)."""
    embed = params["embed"].astype(F32)
    x = embed[tokens]
    body = jax.checkpoint(lambda x, p: (_layer(cfg, precision, x, p), None))
    x, _ = jax.lax.scan(body, x, params["dense_layers"])
    h = _rms(x, params["final_norm"].astype(F32), cfg["rms_norm_eps"])
    logits = _ein("bsd,vd->bsv", h, embed, precision)[:, :-1]
    ll = jnp.take_along_axis(logits, labels[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - ll)


@partial(jax.jit, static_argnums=(0, 1), donate_argnums=(2,))
def _accumulate(cfg_items, precision, acc, params, tokens, labels, w):
    cfg = dict(cfg_items)
    with jax.default_matmul_precision("highest"):
        l, g = jax.value_and_grad(partial(loss, cfg, precision))(
            params, tokens, labels)
    return jax.tree.map(lambda a, b: a + w * b, acc, g), l


def loss_and_grads(cfg, precision, params, tokens, labels):
    """Loss and gradients of the whole batch, one row at a time."""
    items = _items(cfg)
    B = tokens.shape[0]
    acc = jax.tree.map(lambda p: jnp.zeros(p.shape, F32), params)
    total = 0.0
    for r in range(B):
        acc, l = _accumulate(items, precision, acc, params,
                             tokens[r:r + 1], labels[r:r + 1], 1.0 / B)
        total += float(l) / B
    return total, acc


def _items(cfg: dict) -> tuple:
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "rms_norm_eps", "rope_theta")
    return tuple((k, cfg[k]) for k in keys) + (
        ("qk_norm", bool(cfg.get("qk_norm"))),)


# ---------------------------------------------------------------------------
# AdamW
# ---------------------------------------------------------------------------
def lr_at(opt: dict, step: int) -> float:
    if step < opt["warmup_steps"]:
        return opt["peak_lr"] * step / max(opt["warmup_steps"], 1)
    prog = min(max((step - opt["warmup_steps"])
                   / max(opt["decay_steps"] - opt["warmup_steps"], 1), 0.0),
               1.0)
    return opt["min_lr"] + 0.5 * (opt["peak_lr"] - opt["min_lr"]) \
        * (1 + np.cos(np.pi * prog))


@partial(jax.jit, static_argnums=(0,), donate_argnums=(1, 3, 4))
def _update(hyper, params, grads, m, v, lr, c1, c2):
    b1, b2, eps, wd, clip = hyper
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, clip / (gn + 1e-9))

    def leaf(p, g, m, v):
        g = g * scale
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * jnp.square(g)
        upd = (m / c1) / (jnp.sqrt(v / c2) + eps)
        p32 = p.astype(F32)
        if p.ndim >= 2:
            upd = upd + wd * p32
        return (p32 - lr * upd).astype(p.dtype), m, v

    out = jax.tree.map(leaf, params, grads, m, v)
    pick = lambda i: jax.tree.map(lambda t: t[i], out,
                                  is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1), pick(2)


def leaf_norms(tree) -> dict:
    """{leaf path: float32 norm}."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    norms = jax.jit(lambda xs: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(F32)))) for x in xs])([x for _, x in flat])
    return {jax.tree_util.keystr(k): float(n)
            for (k, _), n in zip(flat, norms)}


def three_steps(cfg: dict, batch_size: int, seq: int, init_seed: int,
                data_seed: int, precision: str = "f32",
                rows: int | None = None) -> dict:
    """Losses of steps 1-3, per-leaf norms of the first gradient as the
    optimizer takes it (after clipping), and of the weights' change over
    the three steps.  `rows` keeps only the batch's first rows: the fault
    of a step that leaves half of its batch out.  The moments wait on the
    host while the gradients are taken, so both fit one chip."""
    opt = cfg["optimizer"]
    hyper = (opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"],
             opt["clip_norm"])
    params = init_params(cfg, init_seed)
    m = jax.tree.map(lambda p: np.zeros(p.shape, np.float32), params)
    v = jax.tree.map(lambda p: np.zeros(p.shape, np.float32), params)
    losses, g1 = [], None
    for step in (1, 2, 3):
        tokens, labels = batch(cfg, batch_size, seq, step - 1, data_seed)
        tokens, labels = tokens[:rows], labels[:rows]
        l, grads = loss_and_grads(cfg, precision, params, tokens, labels)
        losses.append(l)
        if step == 1:
            norms = leaf_norms(grads)
            gn = np.sqrt(sum(n * n for n in norms.values()))
            scale = min(1.0, opt["clip_norm"] / (gn + 1e-9))
            g1 = {k: n * scale for k, n in norms.items()}
        params, m, v = _update(hyper, params, grads, jax.device_put(m),
                               jax.device_put(v), lr_at(opt, step),
                               1 - opt["b1"] ** step, 1 - opt["b2"] ** step)
        del grads
        m, v = jax.device_get(m), jax.device_get(v)
    del m, v
    p0 = init_params(cfg, init_seed)
    delta = jax.jit(lambda a, b: jax.tree.map(
        lambda x, y: x.astype(F32) - y.astype(F32), a, b))(params, p0)
    del params, p0
    return {"losses": losses, "grad1": g1, "delta3": leaf_norms(delta)}
