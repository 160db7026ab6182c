"""The harness's own host spans around the calls into each layer.

Each span is a `jax.profiler.TraceAnnotation` named `bench.<name>`, so a
traced run can label the device's idle gaps by what the host was doing,
and a host timer, so every run knows the host time per layer.
"""
from __future__ import annotations

import time
from collections import defaultdict
from contextlib import contextmanager

PREFIX = "bench."


class Spans:
    def __init__(self):
        self.total_s: dict = defaultdict(float)
        self.count: dict = defaultdict(int)

    @contextmanager
    def __call__(self, name: str):
        from jax.profiler import TraceAnnotation
        with TraceAnnotation(PREFIX + name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.total_s[name] += time.perf_counter() - t0
                self.count[name] += 1
