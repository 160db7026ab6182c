"""`models/common.flash_attention`: value and gradient against a plain
masked-softmax attention, and the residuals its backward keeps."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.common import flash_attention

F32 = jnp.float32


def plain_attention(q, k, v, *, causal, q_offset=0, kv_len=None):
    """Softmax over the whole masked score matrix, in f32; a row that sees
    no key gives zeros."""
    B, Sq, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    q_pos = q_offset + jnp.arange(Sq)
    k_pos = jnp.arange(Sk)
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= q_pos[:, None] >= k_pos[None, :]
    if kv_len is not None:
        mask &= k_pos[None, :] < kv_len
    s = jnp.where(mask, s, -jnp.inf)
    m = s.max(-1, keepdims=True)
    e = jnp.where(mask, jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0)), 0.0)
    p = e / jnp.maximum(e.sum(-1, keepdims=True), 1e-30)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


# (H, KV, qk width, v width, Sq, Sk, causal, q_offset, kv_len)
CASES = {
    "causal": (4, 4, 16, 16, 32, 32, True, 0, None),
    "non_causal": (4, 4, 16, 16, 32, 32, False, 0, None),
    "gqa": (4, 2, 16, 16, 32, 32, True, 0, None),
    "qk_wider_than_v": (4, 4, 24, 16, 32, 32, True, 0, None),
    "q_offset_chunk": (4, 4, 24, 16, 16, 48, True, 32, None),
    "chunk_before_the_last_keys": (4, 2, 24, 16, 16, 48, True, 16, None),
    "padding_causal": (4, 2, 16, 16, 36, 36, True, 0, None),
    "padding_non_causal": (4, 4, 16, 16, 20, 36, False, 0, None),
    "kv_len": (4, 2, 16, 16, 16, 32, False, 0, 19),
    "rows_that_see_no_key": (4, 4, 16, 16, 16, 16, True, -4, None),
}


@pytest.mark.parametrize("case", list(CASES))
def test_flash_attention_value_and_grad_match_plain_attention(case):
    H, KV, hd, hd_v, Sq, Sk, causal, q_offset, kv_len = CASES[case]
    ks = jax.random.split(jax.random.key(7), 4)
    q = jax.random.normal(ks[0], (2, Sq, H, hd), F32)
    k = jax.random.normal(ks[1], (2, Sk, KV, hd), F32)
    v = jax.random.normal(ks[2], (2, Sk, KV, hd_v), F32)
    r = jax.random.normal(ks[3], (2, Sq, H, hd_v), F32)
    kv_len = None if kv_len is None else jnp.asarray(kv_len, jnp.int32)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, q_offset=q_offset,
                               block=8, kv_len=kv_len)

    def plain(q, k, v):
        return plain_attention(q, k, v, causal=causal, q_offset=q_offset,
                               kv_len=kv_len)

    with jax.default_matmul_precision("highest"):
        got, want = flash(q, k, v), plain(q, k, v)
        g = jax.grad(lambda *a: jnp.sum(flash(*a) * r), (0, 1, 2))(q, k, v)
        gr = jax.grad(lambda *a: jnp.sum(plain(*a) * r), (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    for name, a, b in zip("qkv", g, gr):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5, err_msg=name)
    if q_offset < 0:  # the rows before every key: zero output, zero dq
        np.testing.assert_array_equal(np.asarray(got[:, :-q_offset]), 0.0)
        np.testing.assert_array_equal(np.asarray(g[0][:, :-q_offset]), 0.0)


def test_flash_attention_backward_keeps_no_per_block_state():
    """The backward's residuals are q, k, v, the output and the per-row
    log-sum-exp: nothing with one slice per kv block, and O(S) beyond the
    inputs, so a long sequence needs no remat of its own."""
    B, S, H, hd, hd_v, block = 2, 96, 4, 24, 16, 16
    nb = S // block
    q = jnp.ones((B, S, H, hd), jnp.bfloat16)
    k = jnp.ones((B, S, H, hd), jnp.bfloat16)
    v = jnp.ones((B, S, H, hd_v), jnp.bfloat16)
    out, backward = jax.vjp(
        lambda q, k, v: flash_attention(q, k, v, causal=True, block=block),
        q, k, v)
    saved = [x for x in jax.tree.leaves(backward) if hasattr(x, "shape")]
    assert saved
    assert not [x.shape for x in saved if x.ndim and x.shape[0] == nb]
    nbytes = lambda xs: sum(x.size * x.dtype.itemsize for x in xs)
    assert nbytes(saved) <= 2 * nbytes((q, k, v, out)), [
        (x.shape, x.dtype) for x in saved]
