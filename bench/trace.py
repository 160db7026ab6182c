"""From a profiler trace to the numbers the per-layer metrics read.

A trace is reduced to a flat list of `Event`s: the device's operations
(`XLA Ops`), its programs (`XLA Modules`) and the harness's own host spans
(`bench.*`).  `reduce` then takes, inside the window that the harness's
`bench.window` span marks:

  * busy time: the union of the intervals in which an operation ran on a
    device, averaged over the devices;
  * device time per program and per operation;
  * the idle gaps, each labelled by the host span that covers most of it
    (the innermost one on a tie).

`events_from_json` reads a trimmed trace kept as a small file, which the
tests reduce.
"""
from __future__ import annotations

import glob
import json
import os
from collections import defaultdict
from dataclasses import dataclass, field

from bench.spans import PREFIX

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = PREFIX + "window"


@dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass
class Summary:
    devices: list
    window_s: float
    busy_s: float                                  # mean over devices
    program_s: dict = field(default_factory=dict)  # module -> s per device
    op_s: dict = field(default_factory=dict)       # op -> s per device
    gaps: list = field(default_factory=list)       # [(label, s)], device 0

    def program_time(self, fragment: str) -> float:
        """Device seconds of every program whose name holds `fragment`."""
        return sum(s for n, s in self.program_s.items() if fragment in n)

    def gap_labels(self, top: int = 10) -> list:
        """Idle seconds by what the host was doing, largest first."""
        by = defaultdict(float)
        for label, s in self.gaps:
            by[label] += s
        return sorted(([k, v] for k, v in by.items()),
                      key=lambda kv: -kv[1])[:top]

    def top_ops(self, top: int = 10) -> list:
        """Device seconds of the costliest operations; a name is cut to
        its first 160 characters (an HLO op's name carries its shapes)."""
        return sorted(([k[:160], v] for k, v in self.op_s.items()),
                      key=lambda kv: -kv[1])[:top]


def is_device(plane: str) -> bool:
    return plane.startswith("/device:")


def load_xplane(log_dir: str) -> list:
    """Events of the one `.xplane.pb` that a trace wrote under log_dir."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        device = is_device(plane.name)
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if device or ev.name.startswith(PREFIX):
                    out.append(Event(plane.name, line.name, ev.name,
                                     float(ev.start_ns),
                                     float(ev.duration_ns)))
    return out


def _union(intervals) -> list:
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _clip(ev: Event, lo: float, hi: float):
    s, e = max(ev.start_ns, lo), min(ev.end_ns, hi)
    return (s, e) if e > s else None


def _label(gap, spans) -> str:
    best, best_key = "none", None
    for ev in spans:
        c = _clip(ev, *gap)
        if c is None:
            continue
        key = (c[1] - c[0], -ev.dur_ns)
        if best_key is None or key > best_key:
            best, best_key = ev.name[len(PREFIX):], key
    return best


def reduce(events) -> Summary:
    """Reduce a trace's events over the harness's window span."""
    (win,) = [e for e in events if e.name == WINDOW_SPAN]
    lo, hi = win.start_ns, win.end_ns
    spans = [e for e in events if not is_device(e.plane)
             and e.name.startswith(PREFIX) and e.name != WINDOW_SPAN]
    devices = sorted({e.plane for e in events
                      if is_device(e.plane) and e.line == OPS_LINE})
    if not devices:
        raise ValueError("the trace holds no device operation")
    busy, program_s, op_s = [], defaultdict(float), defaultdict(float)
    gaps = []
    for k, dev in enumerate(devices):
        ops, cuts = [], []
        for e in events:
            if e.plane != dev:
                continue
            c = _clip(e, lo, hi)
            if c is None:
                continue
            if e.line == MODULES_LINE:
                program_s[e.name] += (c[1] - c[0]) / 1e9 / len(devices)
            elif e.line == OPS_LINE:
                op_s[e.name] += (c[1] - c[0]) / 1e9 / len(devices)
                ops.append(c)
        merged = _union(ops)
        busy.append(sum(e - s for s, e in merged))
        if k == 0:
            edges = [lo] + [x for iv in merged for x in iv] + [hi]
            for s, e in zip(edges[::2], edges[1::2]):
                if e > s:
                    cuts.append((s, e))
            gaps = [(_label(g, spans), (g[1] - g[0]) / 1e9) for g in cuts]
    return Summary(devices, (hi - lo) / 1e9, sum(busy) / len(busy) / 1e9,
                   dict(program_s), dict(op_s), gaps)


def events_from_json(path: str) -> list:
    with open(path) as f:
        return [Event(*row) for row in json.load(f)]
