"""Fleet rounds: simulate a fleet-hour on the device, fold every job
through the rollup's device ingest, and judge it with the detectors.

One round is `simulate_fleet(engine="jax")` -> `StreamingRollup.add_job`
for each job -> `scan_rollup` + `analyze_rollup`, its verdict reaching the
host.  Rounds run in a closed loop for the window.  Afterwards a sample of
rounds, drawn from the seed, is simulated again: every job's counters are
held to the stated generative model, two rounds' draws to independence,
and every job's rollup and the verdict to the plain reference's.
"""
from __future__ import annotations

import time
import traceback

import numpy as np

from bench import generate, window
from bench.record import Compared, Run
from bench.reference import fleet as ref
from bench.spans import Spans


class Fleet:
    """The program's fleet path for one configuration and traffic."""

    def __init__(self, config: dict, rounds):
        from repro.fleet.jobs import JobSpec
        from repro.telemetry.counters import Event
        self.config, self.rounds = config, rounds
        p = rounds.planted
        self._event = [Event(start_s=p["start_s"], end_s=p["end_s"],
                             slowdown=p["slowdown"])]
        self._spec = JobSpec

    def specs(self, r: int) -> list:
        c, t = self.config, self.rounds
        return [self._spec(job_id=j.job_id, arch=j.arch, shape=t.shape,
                           chips=t.devices_per_job, true_duty=j.duty,
                           duration_s=c["round_s"],
                           scrape_interval_s=c["scrape_interval_s"],
                           events=self._event if j.planted else (),
                           seed=s)
                for j, s in zip(t.jobs, t.round_seeds(r))]

    def simulate(self, r: int) -> list:
        from repro.fleet.jobs import simulate_fleet
        return simulate_fleet(self.specs(r),
                              max_devices=self.rounds.devices_per_job,
                              engine="jax")

    def round(self, r: int, spans: Spans):
        """(rollup, ({job: [(start, end)]}, [flagged jobs])) of round r."""
        from repro.fleet.divergence import analyze_rollup
        from repro.fleet.regression import scan_rollup
        from repro.fleet.streaming import StreamingRollup
        c = self.config
        with spans("simulate"):
            tels = self.simulate(r)
        roll = StreamingRollup(c["bucket_s"], bins=c["bins"],
                               lo=c["ofu_lo"], hi=c["ofu_hi"])
        with spans("ingest"):
            for tel in tels:
                roll.add_job(tel)
        with spans("detect"):
            regs = scan_rollup(roll, **c["regression"])
            div = analyze_rollup(roll, **c["divergence"])
        verdict = ({j: [(g.start_idx, g.end_idx) for g in gs]
                    for j, gs in regs.items()},
                   sorted(p.job_id for p in div.flagged))
        return roll, verdict


def _job_rows(roll) -> dict:
    """{job: (hist, sums)} of a rollup, read from its public wire form."""
    from repro.fleet import wire
    snap = wire.decode(roll.to_bytes_v2())
    out = {}
    for (kind, name), idx, hist, sums in snap.scopes:
        if kind == "job":
            h = np.zeros((snap.n_buckets, snap.bins))
            s = np.zeros(snap.n_buckets)
            h[idx], s[idx] = hist, sums
            out[name] = (h, s)
    return out


#: device rows of each job kept for the check that rounds draw afresh
NOISE_ROWS = 1024


def compare_round(fleet: Fleet, r: int, roll, verdict,
                  precision: str = "float32") -> tuple:
    """Simulate round r again and read how far the program's counters lie
    from the stated model, and its rollup and verdict from the reference
    fed the same counters.  With `precision="bfloat16"` the reference in
    that precision is put in the program's place: the plain generator
    makes the counters and the plain fold the rollup.  Returns (readings,
    {job: draws}) for the check across rounds."""
    cfg, rounds = fleet.config, fleet.rounds
    program = _job_rows(roll)
    duty = {j.job_id: j.duty for j in rounds.jobs}
    rng = np.random.default_rng(np.random.SeedSequence([rounds.seed, 3, r]))
    hists, draws = {}, {}
    cells, sums_err, gen = 0, 0.0, []
    for tel in fleet.simulate(r):
        job = tel.spec.job_id
        tpa, clock = np.asarray(tel.grid.tpa), np.asarray(tel.grid.clock_mhz)
        cols = ref.column_duty(
            duty[job], rounds.planted if job == rounds.planted_job else None,
            tpa.shape[1], cfg)
        h_ref, s_ref = ref.job_hist(tpa, clock, cfg)
        hists[job] = (h_ref, s_ref)
        if precision == "float32":
            h_p, s_p = program.get(job, (np.zeros_like(h_ref),
                                         np.zeros_like(s_ref)))
        else:
            h_p, s_p = ref.job_hist(tpa, clock, cfg, precision)
            tpa, clock = ref.generate_job(cols, tpa.shape[0], cfg, rng,
                                          precision)
        gen.append(ref.generate_stats(tpa, clock, cols, cfg))
        draws[job] = ref.noise(tpa[:NOISE_ROWS], clock[:NOISE_ROWS], cols)
        if h_p.shape != h_ref.shape:
            cells += h_ref.size
            sums_err = np.inf
            continue
        cells += int(np.count_nonzero(h_p != h_ref))
        sums_err = max(sums_err, ref.sums_rel_err(s_p, s_ref))
    # an exact FLOPs counter reports the job's true duty as its MFU
    want = ref.verdict(hists, duty, cfg)
    differ = (sum(verdict[0].get(j) != want[0].get(j)
                  for j in set(verdict[0]) | set(want[0]))
              + len(set(verdict[1]) ^ set(want[1])))
    lo, hi = cfg["jitter_rel_sd_range"]
    return {"tpa_mean_gap": max(g["tpa_mean_gap"] for g in gen),
            "clock_moment_z": max(g["clock_moment_z"] for g in gen),
            "jitter_off_jobs": sum(not lo <= g["jitter_rel_sd"] <= hi
                                   for g in gen),
            "hist_cells_differ": cells, "sums_rel_err": sums_err,
            "verdicts_differ": differ}, draws


def fresh_draws(draws: list) -> float:
    """round_noise_corr_z: worst correlation of one job's draws between
    consecutive rounds compared, in standard errors."""
    return max((ref.noise_corr_z(a[j], b[j])
                for a, b in zip(draws, draws[1:]) for j in a), default=0.0)


def compare_rounds(fleet: Fleet, kept: list,
                   precision: str = "float32") -> dict:
    """Readings over the rounds compared: counts add up, a gap, an error
    or a distance is the worst.  A single round is paired with round 0
    for the check that rounds draw afresh."""
    out, draws = {}, []
    kept = sorted(kept, key=lambda k: k[0])
    for r, roll, verdict in kept:
        got, d = compare_round(fleet, r, roll, verdict, precision)
        draws.append(d)
        for k, v in got.items():
            add = k in ("hist_cells_differ", "verdicts_differ",
                        "jitter_off_jobs")
            out[k] = out.get(k, 0) + v if add else max(out.get(k, 0.0), v)
    if len(kept) == 1:
        roll, verdict = fleet.round(0, Spans())
        draws.insert(0, compare_round(fleet, 0, roll, verdict, precision)[1])
    out["round_noise_corr_z"] = fresh_draws(draws)
    return out


def planted_readings(rounds, config: dict,
                     verdicts: list) -> dict:
    """How many rounds missed the planted slowdown, and how many steady
    jobs were flagged, over every round of the window."""
    p = rounds.planted
    first, last = (int(p["start_s"] // config["bucket_s"]),
                   int(-(-p["end_s"] // config["bucket_s"])))
    job = rounds.planted_job
    missed = steady = 0
    for regs, _ in verdicts:
        spans = regs.get(job, [])
        missed += not any(first <= s < last for s, _ in spans)
        steady += sum(1 for j in regs if j != job)
    return {"planted_missed": missed, "steady_flagged": steady}


def run(cell, seed: int, seconds: float, measured, t_start: float,
        device) -> Run:
    """Set up, measure for `seconds` between `measured.start()` and
    `measured.stop()`, then compare; `t_start` is when the process began."""
    import jax
    from repro.kernels import fleet_hist
    cfg = cell.config
    rounds = generate.make(cell.mix, seed)
    fleet = Fleet(cfg, rounds)
    result = Run(peak=None)

    try:                                     # warm-up: every shape compiles
        fleet.round(0, Spans())
    except Exception:
        traceback.print_exc()
        result.failed += 1
    result.spans = Spans()
    route = "pallas" if jax.default_backend() == "tpu" else "xla"
    routes0 = fleet_hist.ROUTES.copy()
    rng = np.random.default_rng(np.random.SeedSequence([seed, 2]))
    kept, verdicts, lat = [], [], []

    measured.start()
    t0 = time.perf_counter()
    result.setup_s = t0 - t_start
    r = 1
    with result.spans("window"):
        while True:
            ts = time.perf_counter()
            try:
                roll, verdict = fleet.round(r, result.spans)
            except Exception:                # a round that fails is counted
                result.failed += 1
                if result.failed == 1:
                    traceback.print_exc()
                roll = None
            te = time.perf_counter()
            lat.append(te - ts)
            if roll is not None:
                verdicts.append(verdict)
                # reservoir sample of the rounds to compare, from the seed
                if len(kept) < rounds.check_rounds:
                    kept.append((r, roll, verdict))
                else:
                    k = int(rng.integers(0, len(verdicts)))
                    if k < rounds.check_rounds:
                        kept[k] = (r, roll, verdict)
            r += 1
            if te - t0 >= seconds:
                break
    result.window_s = te - t0
    measured.stop()
    n = len(lat)
    folds = fleet_hist.ROUTES - routes0
    devsec = len(rounds.jobs) * rounds.devices_per_job * cfg["round_s"]
    result.attempted = n
    result.end_to_end = {       # a round that failed brings no telemetry
        "fleet_devsec_per_s": window.rate(len(verdicts) * devsec,
                                          result.window_s),
        "fleet_round_p95_ms": 1e3 * window.p95(lat)}
    result.counters = {"rounds": n,
                       "samples_per_round": len(rounds.jobs)
                       * rounds.devices_per_job
                       * int(cfg["round_s"] // cfg["scrape_interval_s"])}
    result.memory_peak_bytes = device.peak_bytes()

    readings = {"rounds_failed": result.failed,
                "folds_off_kernel": len(verdicts) * len(rounds.jobs)
                - folds[route]}
    readings.update(planted_readings(rounds, cfg, verdicts))
    if kept:
        readings.update(compare_rounds(fleet, kept))
    result.compared = [Compared(k, float(v), float(cfg["limits"][k]))
                       for k, v in readings.items()]
    return result


def control(cell, seeds, control_seeds, emit) -> None:
    """Program readings for each seed, over rounds 1 and 2; the control's
    (the reference in bfloat16 in the program's place) for each control
    seed."""
    for seed in seeds:
        f = Fleet(cell.config, generate.make(cell.mix, seed))
        kept = [(r, *f.round(r, Spans())) for r in (1, 2)]
        emit("program", seed, compare_rounds(f, kept))
        if seed in control_seeds:
            emit("control", seed, compare_rounds(f, kept, "bfloat16"))
