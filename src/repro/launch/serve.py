"""Serving driver: batched greedy decode with KV/SSM caches.

  PYTHONPATH=src python -m repro.launch.serve --arch mamba2-780m --smoke \
      --tokens 32 --batch 4
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import SHAPES, ShapeSpec, cache_specs, get_config
from repro.launch.cache import enable_compile_cache
from repro.models import api as models
from repro.train.steps import make_serve_step


def init_caches(cfg, B, S):
    specs = cache_specs(cfg, B, S, jnp.dtype(cfg.dtype))
    return {k: jnp.zeros(v.shape, v.dtype) for k, v in specs.items()}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--ctx-len", type=int, default=64)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()

    B, S = args.batch, args.ctx_len
    params = models.init_params(cfg, jax.random.key(0))
    serve = jax.jit(make_serve_step(cfg), donate_argnums=(1,))

    batch = {"tokens": jnp.zeros((B, 1), jnp.int32),
             "cache_index": jnp.asarray(0, jnp.int32)}
    batch.update(init_caches(cfg, B, S))
    if cfg.family == "encdec":
        batch["encoder_out"] = jnp.zeros((B, cfg.encoder_seq, cfg.d_model),
                                         jnp.dtype(cfg.dtype))

    toks = []
    t0 = time.perf_counter()
    for i in range(args.tokens):
        nxt, caches = serve(params, batch)
        toks.append(np.asarray(nxt)[:, 0])
        batch = {"tokens": nxt.astype(jnp.int32),
                 "cache_index": jnp.asarray(i + 1, jnp.int32), **caches}
        if cfg.family == "encdec":
            batch["encoder_out"] = jnp.zeros(
                (B, cfg.encoder_seq, cfg.d_model), jnp.dtype(cfg.dtype))
    dt = time.perf_counter() - t0
    print(f"decoded {args.tokens} tokens x {B} seqs in {dt:.2f}s "
          f"({args.tokens * B / dt:.1f} tok/s)")
    print("sample:", np.stack(toks, 1)[0][:16])


if __name__ == "__main__":
    main()
