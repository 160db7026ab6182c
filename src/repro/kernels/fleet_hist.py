"""Fused OFU histogram-accumulate — the device side of rollup ingest.

`StreamingRollup.add_grid` over a NumPy grid computes the per-device OFU
series on the host and scatter-adds it into per-bucket histograms.  For a
jax engine grid that round-trip is the bottleneck: a 1M-device day of
30 s scrapes is ~23 GB of per-device OFU that exists only to be reduced
into a few kilobytes of (bucket, bin) weights.  This module keeps the
reduction on the device:

    ofu = tpa * clock / f_max          (Eq. 1, elementwise)
    k   = bucketize(ofu, edges)        (comparison-based — see below)
    hist[b, k] += 1 ; sums[b] += ofu   (per time-bucket accumulate)

fused into one pass, so only the (n_buckets, bins) histogram and the
(n_buckets,) weighted sums ever reach the host.

Bin assignment is COMPARISON-based (count of edges ≤ value — digitize's
definition), never arithmetic on the value: XLA is free to contract or
reorder a `floor((v - lo) * inv_width)` chain at different intermediate
precision than the host, which flips samples sitting one ulp from a bin
edge.  Comparisons on identical f32 bits are exact, so the kernel, the
XLA fallback, and the NumPy oracle agree bin-for-bin by construction.

Two implementations share the arithmetic:

  * `pallas` — a `pl.pallas_call` over row tiles that span the whole
    time axis, (rows, S).  Each tile counts, per column, the samples at
    or above each bin edge, and adds them into a (bins, S) int32 block
    that stays resident across the one 'arbitrary' grid axis; per-column
    sums ride along.  A bin's count is the difference of two adjacent
    edge counts, and columns fold into time buckets outside the kernel,
    so it needs bucket-aligned columns (every bucket spans the same
    number of scrape columns; the last may run short).  Compiled on a
    TPU, interpreted elsewhere, with the same tiling.  A row-sharded
    grid runs it on each chip's rows under `shard_map`.
  * `xla` — a jnp searchsorted + scatter-add over (bucket, bin) keys;
    the path for ragged column->bucket maps, and the default off-TPU.

Counts are int32, exact to 2^31 per (bucket, bin).  `ofu_bucket_hist`
picks the path; `bucket_hist_ref` is the NumPy oracle the equivalence
tests pin both against.  NaN samples (the engine never makes one) are
not counted by the kernel.
"""
from __future__ import annotations

import collections
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from repro.kernels.ops import _interpret


def _edges_f32(edges: np.ndarray) -> np.ndarray:
    """Edge grid in the comparison dtype (f32, matching the engine's
    telemetry); must be strictly increasing."""
    edges = np.asarray(edges, np.float32)
    if edges.ndim != 1 or len(edges) < 2 or not (np.diff(edges) > 0).all():
        raise ValueError("edges must be a 1-D strictly-increasing grid")
    return edges


def _aligned_spb(col_bucket: np.ndarray, n_buckets: int) -> Optional[int]:
    """Samples-per-bucket when every bucket spans an equal run of columns
    (the last may run short); None when the map is ragged."""
    S = len(col_bucket)
    if S == 0 or n_buckets <= 0:
        return None
    spb = int(np.searchsorted(col_bucket, 1)) if n_buckets > 1 else S
    if spb <= 0:
        return None
    if np.array_equal(col_bucket, np.arange(S) // spb):
        return spb
    return None


# ---------------------------------------------------------------------------
# pallas kernel: device-row tiles spanning the whole time axis
# ---------------------------------------------------------------------------
#: f32 samples per (rows, S) input tile: 64 vregs, a few hundred KB of
#: VMEM per double-buffered input, whatever the series length
_TILE_ELEMS = 64 * 1024


def _block_rows(D: int, S: int) -> int:
    """Tile height: ~_TILE_ELEMS samples in whole 8-row sublane tiles (S
    rounds up to the 128-lane tile), or all D rows when fewer — a block
    dim must be a multiple of 8 or the full array dim."""
    lanes = -(-max(S, 1) // 128) * 128
    return min(D, max(8, _TILE_ELEMS // lanes // 8 * 8))


def _hist_kernel(edges_ref, tpa_ref, clock_ref, ge_ref, sum_ref, ofu_ref, *,
                 n_rows: int, block_d: int, bins: int, inv_fmax: float):
    """One (block_d, S) tile into the resident per-column outputs.

    ge_ref[j, s] counts samples of column s with OFU >= edges[j] (j >= 1;
    row 0 counts every sample), so a bin's count is a difference of two
    rows — `_fold_buckets` takes them.  Rows past n_rows (the ragged last
    tile) become NaN, which counts nowhere.
    """
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        ge_ref[...] = jnp.zeros_like(ge_ref)
        sum_ref[...] = jnp.zeros_like(sum_ref)

    ofu = tpa_ref[...] * clock_ref[...] * jnp.float32(inv_fmax)
    rows = jax.lax.broadcasted_iota(jnp.int32, ofu.shape, 0) + i * block_d
    ofu = jnp.where(rows < n_rows, ofu, jnp.nan)
    ofu_ref[...] = ofu
    seen = ofu == ofu
    ge_ref[0:1, :] += jnp.sum(seen.astype(jnp.int32), axis=0, keepdims=True)
    sum_ref[...] += jnp.sum(jnp.where(seen, ofu, 0.0), axis=0,
                            keepdims=True)

    def count_ge(j, carry):
        hit = (ofu_ref[...] >= edges_ref[j]).astype(jnp.int32)
        ge_ref[pl.ds(j, 1), :] += jnp.sum(hit, axis=0, keepdims=True)
        return carry

    jax.lax.fori_loop(1, bins, count_ge, 0)


def _hist_tiles(edges, tpa, clock, *, inv_fmax, interpret):
    """pallas_call over row tiles: ((bins, S) int32 cumulative counts,
    (1, S) f32 column sums).  The grid is one 'arbitrary' axis so the
    whole-array outputs stay resident in VMEM across every tile."""
    D, S = tpa.shape
    bins = edges.shape[0] - 1
    block_d = _block_rows(D, S)
    tile = pl.BlockSpec((block_d, S), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_hist_kernel, n_rows=D, block_d=block_d,
                          bins=bins, inv_fmax=inv_fmax),
        grid=(pl.cdiv(D, block_d),),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), tile, tile],
        out_specs=[pl.BlockSpec((bins, S), lambda i: (0, 0)),
                   pl.BlockSpec((1, S), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((bins, S), jnp.int32),
                   jax.ShapeDtypeStruct((1, S), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_d, S), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(edges, tpa, clock)


def _fold_buckets(ge, colsum, *, spb, n_buckets):
    """Per-column cumulative counts and sums -> (hist (B, bins) int32,
    sums (B,) f32), for bucket-aligned columns (the last may run short)."""
    bins, S = ge.shape
    pad = n_buckets * spb - S
    ge = jnp.pad(ge, ((0, 0), (0, pad))).reshape(bins, n_buckets, spb) \
        .sum(axis=2)
    hist = ge - jnp.concatenate([ge[1:], jnp.zeros_like(ge[:1])])
    sums = jnp.pad(colsum[0], (0, pad)).reshape(n_buckets, spb).sum(axis=1)
    return hist.T, sums


@functools.partial(jax.jit, static_argnames=(
    "spb", "n_buckets", "inv_fmax", "interpret"))
def _hist_pallas(tpa, clock, edges, *, spb, n_buckets, inv_fmax, interpret):
    ge, colsum = _hist_tiles(edges, tpa, clock, inv_fmax=inv_fmax,
                             interpret=interpret)
    return _fold_buckets(ge, colsum, spb=spb, n_buckets=n_buckets)


@functools.partial(jax.jit, static_argnames=(
    "mesh", "axis", "spb", "n_buckets", "inv_fmax", "interpret"))
def _hist_pallas_sharded(tpa, clock, edges, *, mesh, axis, spb, n_buckets,
                         inv_fmax, interpret):
    """The kernel on each chip's rows of a row-sharded grid, per-chip
    counts summed by a psum: the grid itself never moves."""
    def local(e, t, c):
        ge, colsum = _hist_tiles(e, t, c, inv_fmax=inv_fmax,
                                 interpret=interpret)
        return jax.lax.psum(ge, axis), jax.lax.psum(colsum, axis)

    rows = P(axis, None)
    ge, colsum = jax.shard_map(
        local, mesh=mesh, in_specs=(P(), rows, rows), out_specs=(P(), P()),
        check_vma=False)(edges, tpa, clock)
    return _fold_buckets(ge, colsum, spb=spb, n_buckets=n_buckets)


def _row_axis(x):
    """(mesh, axis) when x's rows are split over more than one device,
    else None (one device, or a replicated copy on each)."""
    sh = getattr(x, "sharding", None)
    if not isinstance(sh, jax.sharding.NamedSharding) or not sh.spec:
        return None
    axis = sh.spec[0]
    if axis is None or sh.mesh.shape[axis] <= 1:
        return None
    return sh.mesh, axis


# ---------------------------------------------------------------------------
# XLA path for ragged column maps: searchsorted + scatter-add
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("n_buckets", "inv_fmax"))
def _hist_xla(tpa, clock, edges, col_bucket, *, n_buckets, inv_fmax):
    bins = edges.shape[0] - 1
    ofu = tpa * clock * jnp.float32(inv_fmax)
    k = jnp.clip(jnp.searchsorted(edges, ofu.ravel(), side="right")
                 .astype(jnp.int32) - 1, 0, bins - 1)
    seg = jnp.broadcast_to(col_bucket[None, :], ofu.shape).ravel()
    hist = jnp.zeros(n_buckets * bins, jnp.int32) \
        .at[seg * bins + k].add(1).reshape(n_buckets, bins)
    sums = jnp.zeros(n_buckets, ofu.dtype).at[seg].add(ofu.ravel())
    return hist, sums


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------
#: calls per route ("pallas", "pallas-interpret", "xla"): how a caller
#: tells which path a fold took
ROUTES: collections.Counter = collections.Counter()


def ofu_bucket_hist(tpa, clock, *, inv_fmax: float, edges: np.ndarray,
                    col_bucket: np.ndarray, n_buckets: int,
                    use_pallas: Optional[bool] = None):
    """Device-side fused ingest: (hist (B, bins) int32, sums (B,) f32).

    col_bucket: (S,) 0-based LOCAL bucket row per scrape column (the
    caller rebases absolute bucket indices).  use_pallas=None routes to
    the pallas kernel on TPU and to XLA elsewhere; pass True to force the
    kernel (interpreted off-TPU, compiled on it).  A ragged column map
    always takes the XLA scatter.  A grid whose rows are sharded over a
    mesh runs the kernel on each chip's rows.
    """
    edges = _edges_f32(edges)
    col_bucket = np.asarray(col_bucket, np.int32)
    spb = _aligned_spb(col_bucket, n_buckets)
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    tpa, clock = jnp.asarray(tpa), jnp.asarray(clock)
    if use_pallas and spb is not None:
        interpret = _interpret()
        ROUTES["pallas-interpret" if interpret else "pallas"] += 1
        kw = dict(spb=spb, n_buckets=n_buckets, inv_fmax=float(inv_fmax),
                  interpret=interpret)
        sharded = _row_axis(tpa)
        if sharded is not None:
            mesh, axis = sharded
            return _hist_pallas_sharded(tpa, clock, jnp.asarray(edges),
                                        mesh=mesh, axis=axis, **kw)
        return _hist_pallas(tpa, clock, jnp.asarray(edges), **kw)
    ROUTES["xla"] += 1
    return _hist_xla(tpa, clock, jnp.asarray(edges), jnp.asarray(col_bucket),
                     n_buckets=n_buckets, inv_fmax=float(inv_fmax))


def bucket_hist_ref(tpa, clock, *, inv_fmax: float, edges: np.ndarray,
                    col_bucket: np.ndarray, n_buckets: int):
    """NumPy oracle: OFU and bin comparisons in the device paths' exact
    f32, exact integer counts, sums accumulated in f64."""
    edges = _edges_f32(edges)
    bins = len(edges) - 1
    tpa = np.asarray(tpa, np.float32)
    clock = np.asarray(clock, np.float32)
    ofu = (tpa * clock * np.float32(inv_fmax)).ravel()
    k = np.clip(np.searchsorted(edges, ofu, side="right") - 1, 0, bins - 1)
    seg = np.broadcast_to(np.asarray(col_bucket, np.int64)[None, :],
                          tpa.shape).ravel()
    hist = np.bincount(seg * bins + k, minlength=n_buckets * bins) \
        .reshape(n_buckets, bins)
    sums = np.bincount(seg, weights=ofu, minlength=n_buckets)
    return hist, sums.astype(np.float32)
