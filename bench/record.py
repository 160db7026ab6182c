"""What one run of a cell measured, handed from its driver to the
per-layer readers and to the result line."""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

from bench.peaks import Peak
from bench.spans import Spans


@dataclass
class Compared:
    """One number compared with its limit; it passes at or under it."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclass
class Run:
    peak: Optional[Peak]
    spans: Spans = field(default_factory=Spans)
    attempted: int = 0
    failed: int = 0
    setup_s: float = 0.0
    window_s: float = 0.0
    end_to_end: dict = field(default_factory=dict)
    counters: dict = field(default_factory=dict)
    compared: list = field(default_factory=list)
    memory_peak_bytes: int = 0
    trace: object = None          # bench.trace.Summary of a traced run

    @property
    def correct(self) -> bool:
        return bool(self.compared) and all(c.ok for c in self.compared)

    def per_unit(self, span: str, unit: str) -> Optional[float]:
        """Host milliseconds of a span per counted unit (round, step)."""
        n = self.counters.get(unit, 0)
        if not n or span not in self.spans.count:
            return None
        return 1e3 * self.spans.total_s[span] / n
