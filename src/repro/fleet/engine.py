"""Vectorized fleet telemetry engine (fleet-scale §V-B/§VI simulation).

The scalar `SimulatedDeviceBackend` advances one device one poll at a time
— Python loops over sub-step duty samples and OU clock sub-steps — which
tops out at a few hundred device-minutes per wall-second.  The paper's
fleet scenarios (608 jobs, thousands of GPUs, hours of scrapes) need four
orders of magnitude more.  This engine simulates the SAME generative model
as batched NumPy array ops, at two fusion levels:

  * `simulate_devices` — one device group (one job) as an
    (n_devices, n_samples) grid: duty via `telemetry.counters.duty_grid`
    (vectorized event masks) averaged over the hardware window, clock via
    one batched OU pass (`ClockModel.simulate_batch`), per-step jitter as
    a single lognormal draw.
  * `simulate_jobs_fused` — a whole MULTI-JOB fleet stacked into one
    padded (total_devices, S_max) grid.  Ragged job durations pad to the
    longest job and are sliced back on output (OU padding sits at the tail
    of each row, so valid samples are untouched); jobs are grouped by
    (scrape interval, clock-model constants) so each group shares one time
    grid, one jitter draw, and ONE batched OU recurrence — the per-group
    Python cost is O(S_max × K) regardless of job count.  Event-free jobs
    skip the duty sub-sample grid entirely (their deterministic duty is
    constant in time); evented jobs evaluate it device-batched.

Each group's host half (layout, event factors, n_eff/n_sub, duty bases,
OU drive) is one `GroupPlan`, shared with the jax backend
(`engine_jax._group_inputs`).  The per-device `SimulatedDeviceBackend`
remains the semantic oracle; equivalence is statistical (same
seed/profile ⇒ matching tpa/clock statistics within tolerance), covered
by tests/test_fleet_engine.py.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence

import numpy as np

from repro.core.peaks import DEFAULT_CHIP, ChipSpec
from repro.telemetry.clock import ClockModel
from repro.telemetry.counters import (Event, StepProfile,
                                      check_scrape_interval, event_factors)
from repro.telemetry.scrape import DeviceGrid  # noqa: F401  (re-export)


@dataclass
class EngineParams:
    """Fidelity knobs for the vectorized path.

    (A clock_substeps knob existed through PR 1; it is gone because the OU
    drive — duty at window ends — is piecewise-constant within a scrape
    interval, so `simulate_batch`'s exact discretization takes ONE step
    per scrape sample and sub-steps only ever added intermediate clipping
    >10σ from the clip bounds.)
    """

    n_sub_max: int = 64          # duty sub-samples per averaging window


@dataclass
class JobSlot:
    """One job's slot in a fused multi-job grid (the engine-level view:
    no configs, no FLOPs — just the step profile and its timeline)."""

    profile: StepProfile
    duration_s: float
    interval_s: float
    events: Sequence[Event] = ()
    stragglers: Optional[np.ndarray] = None   # (n_devices,); default: [1.0]
    chip: ChipSpec = DEFAULT_CHIP
    clock_model: Optional[ClockModel] = None


# ---------------------------------------------------------------------------
# Fault injection: post-hoc counter perturbation (scenario ground truth)
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class CounterFault:
    """A declarative counter-stream perturbation with a known timeline.

    `Event` feeds the GENERATIVE model (it changes what the simulated
    hardware does, sample statistics and OU drive included).  A
    CounterFault instead perturbs the OBSERVED counters after the engine
    pass — multiplicative masks over the (device, sample) grid — which is
    what the scenario library needs for ground-truth labels: the
    perturbation applies identically on both engines (fused, jax), so a
    detector scorecard measures the detector, never engine-equivalence
    noise.

    Timeline: active on samples with start_s <= t < end_s.  period_s > 0
    gates that window into repeating bursts (active for the first
    `active_frac` of each period — preemption waves, MoE imbalance
    bursts).  diurnal_amp adds a sinusoidal duty modulation with period
    diurnal_period_s (multi-tenant inference load shapes).

    Scope: all devices by default; `devices` pins an explicit row subset,
    else `device_frac` takes the leading ceil(frac × D) rows (stable and
    seed-free — straggler-host scenarios stay reproducible).
    """

    start_s: float = 0.0
    end_s: float = float("inf")
    duty_scale: float = 1.0          # multiplies tpa while active
    clock_scale: float = 1.0         # multiplies clock_mhz while active
    device_frac: float = 1.0
    devices: Optional[tuple] = None  # explicit device rows (wins over frac)
    period_s: float = 0.0
    active_frac: float = 1.0
    diurnal_amp: float = 0.0
    diurnal_period_s: float = 86400.0
    kind: str = "fault"

    def __post_init__(self):
        if self.end_s < self.start_s:
            raise ValueError(f"fault window [{self.start_s}, {self.end_s}) "
                             "is reversed")
        if not 0.0 < self.device_frac <= 1.0:
            raise ValueError(f"device_frac={self.device_frac} must be in "
                             "(0, 1]")
        if self.period_s < 0 or not 0.0 < self.active_frac <= 1.0:
            raise ValueError(f"need period_s >= 0 (got {self.period_s}) "
                             f"and active_frac in (0, 1] "
                             f"(got {self.active_frac})")
        if abs(self.diurnal_amp) > 1.0:
            raise ValueError(f"diurnal_amp={self.diurnal_amp} must stay "
                             "within ±1 (duty cannot go negative)")


def fault_factors(faults: Sequence[CounterFault], times_s: np.ndarray,
                  n_devices: int) -> tuple[np.ndarray, np.ndarray]:
    """(duty, clock) multiplicative factor grids, shape (D, S) float32.

    Later faults compound multiplicatively with earlier ones on samples
    where both are active (a throttled straggler is both slow AND hot).
    """
    t = np.asarray(times_s, float).ravel()
    duty = np.ones((n_devices, t.size), dtype=np.float32)
    clock = np.ones((n_devices, t.size), dtype=np.float32)
    for f in faults:
        on = (f.start_s <= t) & (t < f.end_s)
        if f.period_s > 0:
            phase = np.mod(t - f.start_s, f.period_s)
            on &= phase < f.active_frac * f.period_s
        if not on.any():
            continue
        if f.devices is not None:
            rows = np.asarray(f.devices, int)
            if rows.size and (rows.min() < 0 or rows.max() >= n_devices):
                raise ValueError(f"fault devices {list(rows)} out of range "
                                 f"for {n_devices} device(s)")
        else:
            rows = np.arange(int(np.ceil(f.device_frac * n_devices)))
        d = np.full(t.size, 1.0, dtype=np.float32)
        d[on] = f.duty_scale
        if f.diurnal_amp:
            wave = 1.0 + f.diurnal_amp * np.sin(
                2.0 * np.pi * t / f.diurnal_period_s)
            d[on] = (d * wave.astype(np.float32))[on]
        duty[rows] *= d[None, :]
        if f.clock_scale != 1.0:
            c = np.full(t.size, 1.0, dtype=np.float32)
            c[on] = f.clock_scale
            clock[rows] *= c[None, :]
    return duty, clock


def apply_faults(grid: DeviceGrid,
                 faults: Sequence[CounterFault]) -> DeviceGrid:
    """Perturb a simulated grid's counters per the fault timeline.

    Pure post-processing: multiplies tpa/clock by `fault_factors` masks
    (duty clipped back into [0, 1]) and returns a NEW DeviceGrid with the
    same interval/t0.  Works on host numpy grids and jax device grids
    alike — the arithmetic goes through the grid arrays' own operators,
    so a device-resident grid stays on device.
    """
    if not faults:
        return grid
    if grid.tpa.size == 0:
        return DeviceGrid(grid.interval_s, grid.tpa, grid.clock_mhz,
                          t0_s=grid.t0_s)
    duty_f, clock_f = fault_factors(faults, grid.times_s, grid.n_devices)
    tpa = (grid.tpa * duty_f).clip(0.0, 1.0)
    clk = (grid.clock_mhz * clock_f).clip(0.0, None)
    return DeviceGrid(grid.interval_s, tpa, clk, t0_s=grid.t0_s)


def simulate_devices(profile: StepProfile, *, duration_s: float,
                     interval_s: float,
                     chip: ChipSpec = DEFAULT_CHIP,
                     clock_model: Optional[ClockModel] = None,
                     events: Sequence[Event] = (),
                     stragglers=None, n_devices: Optional[int] = None,
                     seed: int = 0,
                     params: Optional[EngineParams] = None) -> DeviceGrid:
    """Simulate a whole device group's counter streams in one shot.

    stragglers: optional (n_devices,) per-device step-time multipliers;
    defaults to 1.0 everywhere.  All devices share the step profile and
    event timeline (the per-job model `simulate_job` uses); straggler
    spread is the per-device degree of freedom.  n_devices defaults to
    len(stragglers) (or 1); passing BOTH requires them to agree — the
    old behaviour quietly simulated len(stragglers) devices whatever
    n_devices said.

    Implemented as a single-slot fused pass — `simulate_jobs_fused` is the
    one grid evaluator, whether one job or six hundred.
    """
    if stragglers is None:
        stragglers = np.ones(1 if n_devices is None else n_devices)
    stragglers = np.asarray(stragglers, float)
    if n_devices is not None and n_devices != len(stragglers):
        raise ValueError(f"n_devices={n_devices} conflicts with "
                         f"len(stragglers)={len(stragglers)}")
    slot = JobSlot(profile, duration_s, interval_s, events=events,
                   stragglers=stragglers, chip=chip, clock_model=clock_model)
    return simulate_jobs_fused([slot], seed=seed, params=params)[0]


def simulate_jobs_fused(slots: Sequence[JobSlot], *, seed: int = 0,
                        params: Optional[EngineParams] = None
                        ) -> list[DeviceGrid]:
    """Simulate many jobs as fused multi-job grids; one DeviceGrid per slot.

    Jobs sharing (scrape interval, clock-model constants) fuse into one
    padded (total_devices, S_max) grid with shared RNG streams; the result
    list is aligned with `slots` regardless of grouping.
    """
    params = params or EngineParams()
    rng = np.random.default_rng(seed)
    out: list = [None] * len(slots)
    for members in group_slots(slots).values():
        _simulate_group(members, out, rng, params)
    return out


def group_slots(slots: Sequence[JobSlot]) -> dict:
    """Group slots by (scrape interval, clock-model constants) — the
    fusion key every batched backend (NumPy here, jax in `engine_jax`)
    shares, so each group gets one time grid and one OU recurrence.
    Values are [(slot index, slot, resolved ClockModel), ...]."""
    groups: dict = {}
    for i, sl in enumerate(slots):
        cm = sl.clock_model or ClockModel(chip=sl.chip)
        key = (float(sl.interval_s), cm.theta, cm.sigma_mhz,
               cm.throttle_frac, cm.f_min_frac, cm.chip.f_max_mhz)
        groups.setdefault(key, []).append((i, sl, cm))
    return groups


def group_layout(members) -> tuple[float, list, np.ndarray]:
    """(scrape interval, each member's straggler factors, each member's
    sample count) of one group from `group_slots`."""
    interval = float(members[0][1].interval_s)
    strag_list = [np.ones(1) if sl.stragglers is None
                  else np.atleast_1d(np.asarray(sl.stragglers, float))
                  for _, sl, _ in members]
    S = np.array([max(int(sl.duration_s / interval), 0)
                  for _, sl, _ in members])
    return interval, strag_list, S


def empty_grids(members, out, interval: float, strag_list) -> None:
    """A (rows, 0) grid for every member of a group with no whole sample."""
    for (i, _, _), st in zip(members, strag_list):
        out[i] = DeviceGrid(interval, np.empty((len(st), 0)),
                            np.empty((len(st), 0)))


@dataclass
class GroupPlan:
    """The host half of one fused group, computed once by `group_plan`
    and read by both evaluators: `_simulate_group` (NumPy) and
    `engine_jax._group_inputs` (packing for the device program).  Draws
    nothing; rows are device rows, members in group order."""

    interval: float
    n_dev: np.ndarray        # (J,) device rows per member
    S: np.ndarray            # (J,) samples per member
    dev_job: np.ndarray      # (D,) int32 row -> member
    strag: np.ndarray        # (D,) f32 straggler factors
    sig: np.ndarray          # (D,) f32 jitter sigma, jitter / n_eff
    ratio: np.ndarray        # (J,) f32 full-rate duty, mxu / step
    has_ev: np.ndarray       # (J,) member has events
    n_sub: int               # window sub-samples of evented members (1: none)
    ev_base: list            # per evented member: f32 (S_max, n_sub) duty base
    base_end: np.ndarray     # (J, S_max) f32 window-end duty: the OU drive

    @property
    def S_max(self) -> int:
        return self.base_end.shape[1]


def group_plan(members, params: EngineParams) -> GroupPlan:
    """Lay out one group (`group_slots` value, at least one sample) and
    evaluate its deterministic duty: every event factor, the n_eff/n_sub
    policy and the per-row factors, in f32 where the grids are f32."""
    interval, strag_list, S = group_layout(members)
    S_max = int(S.max())
    avg_w = check_scrape_interval(interval, strict=False, stacklevel=4)
    J = len(members)
    n_dev = np.array([len(s) for s in strag_list])
    step = np.array([sl.profile.step_time_s for _, sl, _ in members])
    mxu = np.array([sl.profile.mxu_time_s for _, sl, _ in members])
    jit = np.array([sl.profile.jitter for _, sl, _ in members])
    # same effective sub-sample count as the scalar backend (per job)
    n_eff = np.clip(avg_w / np.maximum(step / 4, 1e-3), 8, 4096).astype(int)
    has_ev = np.array([bool(sl.events) for _, sl, _ in members])
    dev_job = np.repeat(np.arange(J, dtype=np.int32), n_dev)
    t_end = (np.arange(S_max) + 1.0) * interval
    # the whole tpa pipeline runs float32: counters are duty fractions in
    # [0, 1], so 1e-7 relative granularity is noise-free headroom, and the
    # grid passes move half the bytes
    ratio = (mxu / step).astype(np.float32)
    base_end = np.broadcast_to(ratio[:, None], (J, S_max)).copy()
    ev_jobs = np.flatnonzero(has_ev)
    n_sub, ev_base = 1, []
    if ev_jobs.size:
        n_sub = int(min(params.n_sub_max, n_eff[ev_jobs].max()))
        offs = (np.arange(n_sub) / n_sub) * avg_w
        ts = (t_end[:, None] - avg_w) + offs[None, :]  # (S_max, n_sub)
    for j in ev_jobs:
        events = members[j][1].events
        slow, scale = event_factors(events, ts)
        ev_base.append(((mxu[j] * scale)
                        / (step[j] * slow)).astype(np.float32))
        slow_e, scale_e = event_factors(events, t_end - 1e-6)
        base_end[j] = (mxu[j] * scale_e) / (step[j] * slow_e)
    return GroupPlan(interval, n_dev, S, dev_job,
                     np.concatenate(strag_list).astype(np.float32),
                     (jit / n_eff).astype(np.float32)[dev_job], ratio,
                     has_ev, n_sub, ev_base, base_end)


def _simulate_group(members, out, rng, params: EngineParams) -> None:
    """One fused pass over all jobs sharing an interval + clock model."""
    interval, strag_list, S = group_layout(members)
    if int(S.max()) <= 0:
        return empty_grids(members, out, interval, strag_list)
    p = group_plan(members, params)
    cm = members[0][2]
    D, S_max = len(p.strag), p.S_max

    # --- duty: hardware-averaged over the trailing window -----------------
    tpa = np.empty((D, S_max), dtype=np.float32)
    plain = ~p.has_ev[p.dev_job]
    # no events -> deterministic duty is constant in time: skip the sub grid
    tpa[plain] = np.minimum(np.float32(1.0), p.ratio[p.dev_job][plain]
                            / p.strag[plain])[:, None]
    # one bounded (S_max, n_sub) base grid per evented job, device rows in
    # bounded blocks — resident memory scales with neither job count nor
    # device count
    row_off = np.concatenate([[0], np.cumsum(p.n_dev)])
    block = max(1, 2 ** 24 // (S_max * p.n_sub))
    for j, base_j in zip(np.flatnonzero(p.has_ev), p.ev_base):
        for b0 in range(row_off[j], row_off[j + 1], block):
            rb = slice(b0, min(b0 + block, row_off[j + 1]))
            duty = base_j[None, :, :] / p.strag[rb, None, None]
            np.minimum(duty, np.float32(1.0), out=duty)
            tpa[rb] = duty.mean(axis=2, dtype=np.float32)
    # one lognormal draw per (device, sample) with the scalar path's
    # mean-of-n-jittered-subsamples dispersion (σ ≈ jitter / n_eff) —
    # a single shared stream for the whole group
    jitter = rng.standard_normal((D, S_max), dtype=np.float32)
    jitter *= p.sig[:, None]
    np.exp(jitter, out=jitter)
    tpa *= jitter
    np.clip(tpa, 0.0, 1.0, out=tpa)

    # --- clock: ONE batched OU pass for every device of every job ---------
    duty_end = p.base_end[p.dev_job]
    duty_end /= p.strag[:, None]
    np.minimum(duty_end, np.float32(1.0), out=duty_end)             # (D, S)
    # exact OU discretization: one step per scrape sample (the drive is
    # constant within each interval, so no sub-stepping is needed)
    clock = cm.simulate_batch(duty_end, dt_s=interval,
                              seed=int(rng.integers(0, 2 ** 31)))

    row0 = 0
    for (i, _, _), nd, Sj in zip(members, p.n_dev, p.S):
        # copies (cheap vs the simulation) so holding one job's telemetry
        # never pins the whole group's padded arrays in memory
        out[i] = DeviceGrid(interval,
                            np.ascontiguousarray(tpa[row0:row0 + nd, :Sj]),
                            np.ascontiguousarray(clock[row0:row0 + nd, :Sj]))
        row0 += nd
