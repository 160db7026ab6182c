"""Traffic of a fleet cell: a fixed job list for the whole run, and fresh
job seeds for each round.

The mix file gives the number of jobs, the devices of each, the range of
true duty the jobs are drawn from, the archs they cycle over, the shape
of their steps, and the slowdown planted in one of them.  The seed draws
the duties, the planted job and every round's job seeds.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class FleetJob:
    job_id: str
    arch: str
    duty: float
    planted: bool


@dataclass(frozen=True)
class FleetRounds:
    """A fixed job list (sizes and order) for the whole run; each round
    draws only fresh job seeds, so no round brings a new shape."""

    jobs: tuple
    devices_per_job: int
    shape: str
    planted: dict
    check_rounds: int
    seed: int

    def round_seeds(self, r: int) -> list:
        state = np.random.SeedSequence([self.seed, 1, r]).generate_state(
            len(self.jobs))
        return [int(s) for s in state]

    @property
    def planted_job(self) -> str:
        (job,) = [j.job_id for j in self.jobs if j.planted]
        return job


def make(mix: dict, seed: int) -> FleetRounds:
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0]))
    n = int(mix["jobs"])
    lo, hi = mix["duty"]
    duty = rng.uniform(lo, hi, n)
    planted = int(rng.integers(0, n))
    archs = mix["archs"]
    jobs = tuple(FleetJob(f"job{i:03d}", archs[i % len(archs)],
                          float(duty[i]), i == planted) for i in range(n))
    return FleetRounds(jobs, int(mix["devices_per_job"]), mix["shape"],
                       dict(mix["planted"]), int(mix["check_rounds"]),
                       int(seed))
