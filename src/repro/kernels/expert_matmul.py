"""Grouped matmul over the experts one chip holds: the Pallas megablox
`gmm` (its custom VJP takes the weights' gradient by `tgmm`).

Rows of `x` are sorted by expert, the G experts this chip holds first;
`group_sizes` (G,) counts each held expert's rows and `w` stacks their
weights.  Only the row tiles of the held experts are computed.  Rows past
sum(group_sizes), the pairs of experts held elsewhere, come back
uninitialized: the caller never reads them.  (Megablox's `group_offset`
over all E experts would zero them instead, one more pass over the
buffer for each product.)
"""
from __future__ import annotations

import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import gmm

from repro.kernels.ops import _interpret

#: rows a tile takes, and the largest tile of a matrix dim past 1536
#: (a v5e's 16 MiB of scoped VMEM refuses tgmm at 512 rows x 1024)
ROW_TILE = 256
MAX_TILE = 1024


def row_tile(m: int) -> int:
    """Rows a tile takes for m rows; the caller pads m to a multiple."""
    return min(ROW_TILE, -(-m // 8) * 8)


def _tile(x: int) -> int:
    """A matrix dim's tile divides it exactly (a ragged one would be
    padded whole by the kernel call): the dim itself up to 1536 (1408 at
    Moonlight's expert width), else the largest of MAX_TILE ... 128 that
    divides it."""
    if x <= 1536 or x % 128:
        return x
    return next(t for t in (MAX_TILE, 512, 256, 128) if x % t == 0)


def _tiling(m: int, k: int, n: int) -> tuple:
    """(m, k, n) -> the kernel's (rows, contraction, output) tile."""
    return row_tile(m), _tile(k), _tile(n)


def expert_matmul(x, w, group_sizes):
    """x (m, k) sorted by expert, m a multiple of row_tile(m); w (G, k, n);
    group_sizes (G,) int32.  Returns (m, n) in x's dtype, accumulated in
    float32.  The forward and both backward products (`gmm` with the
    weights transposed, `tgmm`) look their tiles up by their own dims."""
    return gmm(x, w, group_sizes.astype(jnp.int32), x.dtype, _tiling,
               None, None, False, _interpret())
