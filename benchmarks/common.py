"""Shared benchmark plumbing: each benchmark module exposes run() -> rows,
where a row is (name, us_per_call, derived) — us_per_call times the core
operation, derived carries the paper-comparable numbers."""
from __future__ import annotations

import time
from dataclasses import dataclass


@dataclass
class Row:
    name: str
    us_per_call: float
    derived: str

    def csv(self) -> str:
        return f"{self.name},{self.us_per_call:.2f},{self.derived}"


def timed(fn, *args, repeat: int = 3, **kw):
    """(result, us_per_call) for the fastest of `repeat` calls."""
    best = float("inf")
    out = None
    for _ in range(repeat):
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        best = min(best, time.perf_counter() - t0)
    return out, best * 1e6
