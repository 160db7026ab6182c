"""jax backend for the fused fleet engine (fleet-scale what-if sweeps).

Reproduces `simulate_jobs_fused`'s generative model on jax so scenario
sweeps scale past what a NumPy grid affords (ROADMAP: "as fast as the
hardware allows"; the MegaScale-class fleets in PAPERS.md are 10k+
accelerators).  Same structure, device arrays instead of ndarrays:

  * jobs grouped by `engine.group_slots` — one padded (D, S_max) grid,
    one jitter draw, and one OU recurrence per (interval, clock-model)
    group, exactly like the NumPy path, from the same host plan
    (`engine.group_plan`);
  * evented duty averages the per-window sub-samples with a `lax.scan`
    over the n_sub axis, so resident memory stays O(D·S) however finely
    the hardware window is sub-sampled;
  * the clock is `ClockModel.simulate_batch`'s exact one-step-per-
    interval discretization — `(a, sd) = cm.ou_step_constants(dt)` — as
    a `lax.scan` over time with a (D,) carry;
  * grids carry a `with_sharding_constraint` over a 1-D device mesh
    (rows = devices axis), so on multi-chip hosts XLA partitions the
    whole pipeline; on a single device it is a no-op;
  * one more jitted program, `_split_group`, cuts the group's grids
    into every member's (rows, S_j) grids in a single dispatch.  It is
    specialised on the members' sizes only (row starts are traced), so
    a fleet that keeps its job sizes compiles it once.

Equivalence to the NumPy reference is statistical, not bitwise (jax
threefry vs NumPy philox draws), frozen by the same-tolerance property
suite in tests/test_engine_jax.py.  The grids come back as device
arrays: `StreamingRollup.add_grid` recognizes them and reduces OFU
histograms on-device (`repro.kernels.fleet_hist`) instead of pulling
per-device telemetry to host — pass materialize=True to opt out.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import spans
from repro.fleet.engine import (EngineParams, JobSlot, empty_grids,
                                group_layout, group_plan, group_slots)
from repro.telemetry.scrape import DeviceGrid


def default_mesh() -> Optional[jax.sharding.Mesh]:
    """1-D mesh over every visible accelerator; None on single-device
    hosts (a sharding constraint there is pure overhead)."""
    devs = jax.devices()
    if len(devs) <= 1:
        return None
    return jax.sharding.Mesh(np.array(devs), ("devices",))


def _shard(x, mesh):
    """Constrain rows (devices) across the mesh.  Rows must divide the
    mesh: `_simulate_group_jax` pads them so they do."""
    if mesh is None:
        return x
    if x.shape[0] % mesh.size:
        raise ValueError(f"{x.shape[0]} grid rows do not divide the "
                         f"{mesh.size}-device mesh")
    spec = jax.sharding.PartitionSpec("devices",
                                      *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, spec))


@functools.partial(jax.jit, static_argnames=("S", "n_sub", "consts", "mesh"))
def _group_device_sim(ratio, strag, dev_job, sig, ev_base, ev_rows,
                      ev_job_of_row, strag_e, base_end, k_jit, k_clk, *,
                      S: int, n_sub: int, consts: tuple, mesh):
    """Device half of one fused group: (tpa, clock), both (D, S) f32."""
    f32 = jnp.float32
    D = strag.shape[0]

    # --- duty -> tpa: constant rows for event-free jobs, lax.scan mean
    # over the window sub-samples for evented rows ------------------------
    with jax.named_scope("duty"):
        duty_p = jnp.minimum(1.0, jnp.take(ratio, dev_job) / strag)
        tpa_det = jnp.broadcast_to(duty_p[:, None], (D, S))
        if ev_rows.shape[0]:
            def sub_step(acc, base_k):               # base_k: (J_e, S)
                d = jnp.minimum(1.0, jnp.take(base_k, ev_job_of_row, axis=0)
                                / strag_e[:, None])
                return acc + d, None
            acc, _ = jax.lax.scan(
                sub_step, jnp.zeros((ev_rows.shape[0], S), f32), ev_base)
            tpa_det = tpa_det.at[ev_rows].set(acc * (1.0 / n_sub))
        tpa_det = _shard(tpa_det, mesh)
    # single lognormal jitter draw, σ ≈ jitter / n_eff (NumPy path's
    # mean-of-n-jittered-subsamples dispersion)
    with jax.named_scope("jitter"):
        z = jax.random.normal(k_jit, (D, S), dtype=f32)
        tpa = jnp.clip(tpa_det * jnp.exp(z * sig[:, None]), 0.0, 1.0)

    # --- clock: exact OU discretization, one lax.scan step per sample ----
    with jax.named_scope("clock_ou"):
        a, sd, f_min, f_max, throttle = consts
        duty_end = jnp.minimum(1.0, jnp.take(base_end, dev_job, axis=0)
                               / strag[:, None])
        # drive = μ(duty)·(1−a) + σ·dW, time-major like simulate_batch
        drive = (f_max * (1.0 - a)) * (1.0 - throttle * duty_end.T) \
            + sd * jax.random.normal(k_clk, (S, D), dtype=f32)

        def ou_step(cur, dr):
            cur = jnp.clip(cur * a + dr, f_min, f_max)
            return cur, cur

        cur0 = f_max * (1.0 - throttle * duty_end[:, 0])  # mean_clock(duty₀)
        _, f = jax.lax.scan(ou_step, cur0, drive)
        f = _shard(f.T, mesh)
    return tpa, f


def _simulate_group_jax(members, out, rng, params, mesh, materialize):
    """One fused group: host prep, one jitted device call, then one
    jitted split of its rows into a DeviceGrid per member."""
    interval, strag_list, S = group_layout(members)
    if int(S.max()) <= 0:
        return empty_grids(members, out, interval, strag_list)
    with spans.span("engine.inputs"):
        args, static = _group_inputs(members, rng, params, mesh)
        args = [jnp.asarray(a) for a in args]
    tpa, clock = _group_device_sim(*args, **static)
    # free the inputs' device copies with the call; they live until it
    # ends, so they overlap the split's outputs (12 bytes a device row)
    del args
    with spans.span("engine.slice"):
        # sizes in a canonical order, so a reordered layout of the same
        # sizes reuses the compile; the padded mesh rows are left out
        n_dev = np.array([len(st) for st in strag_list])
        order = np.lexsort((S, n_dev))
        starts = (np.cumsum(n_dev) - n_dev)[order].astype(np.int32)
        sizes = tuple((int(n_dev[k]), int(S[k])) for k in order)
        cached = _split_group._cache_size()
        parts = _split_group(tpa, clock, starts, sizes=sizes)
        spans.count("engine.split.calls")
        spans.count("engine.split.jobs", len(sizes))
        spans.count("engine.split.compiles",
                    _split_group._cache_size() - cached)
        if materialize:
            parts = jax.device_get(parts)
        for m, k in enumerate(order):
            out[members[k][0]] = DeviceGrid(interval, parts[2 * m],
                                            parts[2 * m + 1])


@functools.partial(jax.jit, static_argnames=("sizes",))
def _split_group(tpa, clock, starts, *, sizes: tuple):
    """Every member's rows and columns of a group's (tpa, clock), in one
    program: `(tpa_0, clock_0, tpa_1, ...)`, block m of shape `sizes[m]`
    = (rows, S_j) starting at row `starts[m]` and column 0.  Only the
    sizes are static, so one compile serves any placement of them."""
    return tuple(jax.lax.dynamic_slice(x, (starts[m], 0), shape,
                                       allow_negative_indices=False)
                 for m, shape in enumerate(sizes) for x in (tpa, clock))


def _group_inputs(members, rng, params, mesh):
    """Host half: packs the group's `engine.group_plan` for one
    `_group_device_sim` call — rows padded to the mesh, the evented
    bases stacked, the OU constants and two keys.  Returns the
    positional host arrays and the static keywords."""
    p = group_plan(members, params)
    cm = members[0][2]
    dev_job, strag, sig = p.dev_job, p.strag, p.sig
    # rows pad to a multiple of the mesh with copies of row 0, which the
    # caller slices off, so every chip holds an equal share
    pad = -len(strag) % mesh.size if mesh is not None else 0
    if pad:
        dev_job, strag, sig = (np.concatenate([x, np.repeat(x[:1], pad)])
                               for x in (dev_job, strag, sig))

    # per-window sub-sample base grids for evented jobs, (n_sub, J_e, S)
    ev_rows = ev_job_of_row = np.empty(0, np.int32)
    ev_base = np.empty((1, 0, p.S_max), np.float32)
    if p.ev_base:
        ev_base = np.stack([b.T for b in p.ev_base], axis=1)
        ev_rows = np.flatnonzero(p.has_ev[dev_job]).astype(np.int32)
        ev_job_of_row = (np.cumsum(p.has_ev) - 1)[dev_job[ev_rows]] \
            .astype(np.int32)

    a, sd = cm.ou_step_constants(p.interval)
    consts = (a, sd, cm.chip.f_max_mhz * cm.f_min_frac,
              float(cm.chip.f_max_mhz), cm.throttle_frac)
    k_jit, k_clk = (jax.random.PRNGKey(int(rng.integers(0, 2 ** 31)))
                    for _ in range(2))
    args = (p.ratio, strag, dev_job, sig, ev_base, ev_rows, ev_job_of_row,
            strag[ev_rows], p.base_end, k_jit, k_clk)
    return args, dict(S=p.S_max, n_sub=p.n_sub, consts=consts, mesh=mesh)


def simulate_jobs_jax(slots: Sequence[JobSlot], *, seed: int = 0,
                      params: Optional[EngineParams] = None,
                      mesh="auto", materialize: bool = False
                      ) -> list[DeviceGrid]:
    """jax twin of `simulate_jobs_fused`; one DeviceGrid per slot.

    mesh: "auto" shards grid rows over every visible accelerator (no-op
    on one device); pass a 1-D `jax.sharding.Mesh` with a "devices"
    axis, or None to disable.  materialize=False (default) leaves the
    grids as device arrays so `StreamingRollup.add_grid` can reduce
    them on-device; True copies back to NumPy.
    """
    params = params or EngineParams()
    rng = np.random.default_rng(seed)
    if isinstance(mesh, str):
        if mesh != "auto":
            raise ValueError(f"unknown mesh spec {mesh!r} "
                             "(expected 'auto', a Mesh, or None)")
        mesh = default_mesh()
    out: list = [None] * len(slots)
    for members in group_slots(slots).values():
        _simulate_group_jax(members, out, rng, params, mesh, materialize)
    return out
