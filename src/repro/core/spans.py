"""Host spans and counters inside the program, recorded only while a JAX
profiler trace is active.

    with spans.span("rollup.fetch"):       # a `repro.rollup.fetch` event
        hist = np.asarray(hist)
    spans.count("fleet.draw_cache.hit")

Each span is a `jax.profiler.TraceAnnotation` named `repro.<name>`, so it
lands in the profiler's trace on the same clock as the device's operations,
and a host timer, so `snapshot()` gives per name its count, total seconds,
self seconds (total less what its child spans cover) and its parent span.
The profiler is the one switch: with no trace active (`start_trace`, or a
capture through `start_server`), `span` and `count` check once and return.

Nothing here imports JAX up front: if JAX is not loaded, nothing can be
tracing.  Recording is thread-safe: each thread keeps its own stack of
open spans, and the aggregates take a lock.
"""
from __future__ import annotations

import sys
import threading
import time
from collections import defaultdict

PREFIX = "repro."

_lock = threading.Lock()
_local = threading.local()
_spans: dict = {}               # name -> [count, total_s, self_s, parent]
_counters: dict = defaultdict(int)
_jax = None                     # (TraceMe.is_enabled, TraceAnnotation)


def recording() -> bool:
    """True while a JAX profiler trace is active."""
    global _jax
    if _jax is None:
        if "jax" not in sys.modules:
            return False
        from jax._src.lib import _profiler
        from jax.profiler import TraceAnnotation
        _jax = (_profiler.TraceMe.is_enabled, TraceAnnotation)
    return _jax[0]()


class span:
    """Context manager: a named host span of the program (see module doc)."""

    __slots__ = ("name", "_ann", "_t0", "_child_s")

    def __init__(self, name: str):
        self.name = name
        self._ann = None

    def __enter__(self):
        if not recording():
            return self
        self._ann = _jax[1](PREFIX + self.name)
        self._ann.__enter__()
        stack = getattr(_local, "stack", None)
        if stack is None:
            stack = _local.stack = []
        stack.append(self)
        self._child_s = 0.0
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self._ann is None:
            return False
        dt = time.perf_counter() - self._t0
        stack = _local.stack
        stack.pop()
        parent = stack[-1] if stack else None
        if parent is not None:
            parent._child_s += dt
        with _lock:
            rec = _spans.get(self.name)
            if rec is None:
                rec = _spans[self.name] = [
                    0, 0.0, 0.0, None if parent is None else parent.name]
            rec[0] += 1
            rec[1] += dt
            rec[2] += dt - self._child_s
        self._ann.__exit__(*exc)
        self._ann = None
        return False


def count(name: str, n: int = 1) -> None:
    """Add n to a counter, while a trace is active."""
    if not recording():
        return
    with _lock:
        _counters[name] += n


def snapshot() -> dict:
    """{"spans": {name: {count, total_s, self_s, parent}}, "counters":
    {name: n}} of everything recorded since the last `reset`.  A span's
    parent is the one it was first recorded under (None at the top)."""
    with _lock:
        return {"spans": {k: {"count": c, "total_s": t, "self_s": s,
                              "parent": p}
                          for k, (c, t, s, p) in _spans.items()},
                "counters": dict(_counters)}


def reset() -> None:
    """Forget every span and counter recorded so far."""
    with _lock:
        _spans.clear()
        _counters.clear()
