"""generate: host milliseconds per round slicing each job's rows and
columns out of the group's grids, from the program's `engine.slice` span
(recorded while the profiler traces the window)."""


def read(run):
    try:
        from repro.core import spans
    except ImportError:                  # a program without spans
        return None
    s = spans.snapshot()["spans"].get("engine.slice")
    n = run.counters.get("rounds")
    return 1e3 * s["total_s"] / n if s and n else None
