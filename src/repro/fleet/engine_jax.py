"""jax backend for the fused fleet engine (fleet-scale what-if sweeps).

Reproduces `simulate_jobs_fused`'s generative model on jax so scenario
sweeps scale past what a NumPy grid affords (ROADMAP: "as fast as the
hardware allows"; the MegaScale-class fleets in PAPERS.md are 10k+
accelerators).  Same structure, device arrays instead of ndarrays:

  * jobs grouped by `engine.group_slots` — one padded (D, S_max) grid,
    one jitter draw, and one OU recurrence per (interval, clock-model)
    group, exactly like the NumPy path;
  * evented duty averages the per-window sub-samples with a `lax.scan`
    over the n_sub axis, so resident memory stays O(D·S) however finely
    the hardware window is sub-sampled;
  * the clock is `ClockModel.simulate_batch`'s exact one-step-per-
    interval discretization — `(a, sd) = cm.ou_step_constants(dt)` — as
    a `lax.scan` over time with a (D,) carry;
  * grids carry a `with_sharding_constraint` over a 1-D device mesh
    (rows = devices axis), so on multi-chip hosts XLA partitions the
    whole pipeline; on a single device it is a no-op.

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

from repro.fleet.engine import EngineParams, JobSlot, group_slots
from repro.telemetry.counters import check_scrape_interval, event_factors
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
    z = jax.random.normal(k_jit, (D, S), dtype=f32)
    tpa = jnp.clip(tpa_det * jnp.exp(z * sig[:, None]), 0.0, 1.0)

    # --- clock: exact OU discretization, one lax.scan step per sample ----
    a, sd, f_min, f_max, throttle = consts
    duty_end = jnp.minimum(1.0, jnp.take(base_end, dev_job, axis=0)
                           / strag[:, None])
    # drive = μ(duty)·(1−a) + σ·dW, time-major like simulate_batch
    drive = (f_max * (1.0 - a)) * (1.0 - throttle * duty_end.T) \
        + sd * jax.random.normal(k_clk, (S, D), dtype=f32)

    def ou_step(cur, dr):
        cur = jnp.clip(cur * a + dr, f_min, f_max)
        return cur, cur

    cur0 = f_max * (1.0 - throttle * duty_end[:, 0])   # mean_clock(duty₀)
    _, f = jax.lax.scan(ou_step, cur0, drive)
    return tpa, _shard(f.T, mesh)


def _group_dims(members):
    """(interval, per-member straggler factors, per-member sample counts)."""
    interval = float(members[0][1].interval_s)
    strag_list = [np.ones(1) if sl.stragglers is None
                  else np.atleast_1d(np.asarray(sl.stragglers, float))
                  for _, sl, _ in members]
    S = np.array([max(int(sl.duration_s / interval), 0)
                  for _, sl, _ in members])
    return interval, strag_list, S


def _simulate_group_jax(members, out, rng, params, mesh, materialize):
    """One fused group: host prep, one jitted device call, then a
    DeviceGrid per member sliced from the group's rows."""
    interval, strag_list, S = _group_dims(members)
    if int(S.max()) <= 0:
        for (i, _, _), st in zip(members, strag_list):
            out[i] = DeviceGrid(interval, np.empty((len(st), 0)),
                                np.empty((len(st), 0)))
        return
    args, static = _group_inputs(members, rng, params, mesh)
    tpa, clock = _group_device_sim(*(jnp.asarray(a) for a in args),
                                   **static)
    row0 = 0
    for (i, _, _), st, Sj in zip(members, strag_list, S):
        nd = len(st)
        t, c = tpa[row0:row0 + nd, :Sj], clock[row0:row0 + nd, :Sj]
        if materialize:
            t, c = np.asarray(t), np.asarray(c)
        out[i] = DeviceGrid(interval, t, c)
        row0 += nd


def _group_inputs(members, rng, params, mesh):
    """Host half: mirrors `engine._simulate_group`'s prep (same event
    factors, same n_eff/n_sub policy).  Returns the positional host
    arrays and the static keywords of one `_group_device_sim` call."""
    interval, strag_list, S = _group_dims(members)
    S_max = int(S.max())
    cm = members[0][2]
    n_dev = np.array([len(s) for s in strag_list])
    avg_w = check_scrape_interval(interval, strict=False)

    J = len(members)
    step = np.array([sl.profile.step_time_s for _, sl, _ in members])
    mxu = np.array([sl.profile.mxu_time_s for _, sl, _ in members])
    jit = np.array([sl.profile.jitter for _, sl, _ in members])
    n_eff = np.clip(avg_w / np.maximum(step / 4, 1e-3), 8, 4096).astype(int)
    has_ev = np.array([bool(sl.events) for _, sl, _ in members])
    dev_job = np.repeat(np.arange(J), n_dev).astype(np.int32)
    strag = np.concatenate(strag_list).astype(np.float32)
    # rows pad to a multiple of the mesh (copies of job 0, which the
    # caller slices off) so every chip holds an equal share
    pad = -len(strag) % mesh.size if mesh is not None else 0
    dev_job = np.concatenate([dev_job, np.zeros(pad, np.int32)])
    strag = np.concatenate([strag, np.ones(pad, np.float32)])
    t_end = (np.arange(S_max) + 1.0) * interval

    ratio = (mxu / step).astype(np.float32)
    sig = (jit / n_eff).astype(np.float32)[dev_job]

    # per-window sub-sample base grids for evented jobs, (n_sub, J_e, S)
    n_sub = 1
    ev_rows = np.empty(0, np.int32)
    ev_job_of_row = np.empty(0, np.int32)
    ev_base = np.empty((1, 0, S_max), np.float32)
    if has_ev.any():
        ev_jobs = np.flatnonzero(has_ev)
        n_sub = int(min(params.n_sub_max, n_eff[ev_jobs].max()))
        offs = (np.arange(n_sub) / n_sub) * avg_w
        ts = (t_end[:, None] - avg_w) + offs[None, :]   # (S_max, n_sub)
        bases = []
        for j in ev_jobs:
            slow, scale = event_factors(members[j][1].events, ts)
            bases.append(((mxu[j] * scale)
                          / (step[j] * slow)).astype(np.float32).T)
        ev_base = np.stack(bases, axis=1)               # (n_sub, J_e, S)
        ev_rows = np.flatnonzero(has_ev[dev_job]).astype(np.int32)
        job_to_e = np.cumsum(has_ev) - 1
        ev_job_of_row = job_to_e[dev_job[ev_rows]].astype(np.int32)

    base_end = np.broadcast_to(ratio[:, None], (J, S_max)).copy()
    for j in np.flatnonzero(has_ev):
        slow_e, scale_e = event_factors(members[j][1].events, t_end - 1e-6)
        base_end[j] = ((mxu[j] * scale_e) / (step[j] * slow_e)) \
            .astype(np.float32)

    a, sd = cm.ou_step_constants(interval)
    consts = (a, sd, cm.chip.f_max_mhz * cm.f_min_frac,
              float(cm.chip.f_max_mhz), cm.throttle_frac)
    k_jit, k_clk = (jax.random.PRNGKey(int(rng.integers(0, 2 ** 31)))
                    for _ in range(2))
    args = (ratio, strag, dev_job, sig, ev_base, ev_rows, ev_job_of_row,
            strag[ev_rows], base_end, k_jit, k_clk)
    return args, dict(S=S_max, n_sub=n_sub, consts=consts, mesh=mesh)


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
