"""rollup ingest: host milliseconds per round folding each job's histogram
into its scopes (`observe_hist`), from the program's `rollup.observe`
span (recorded while the profiler traces the window)."""


def read(run):
    try:
        from repro.core import spans
    except ImportError:                  # a program without spans
        return None
    s = spans.snapshot()["spans"].get("rollup.observe")
    n = run.counters.get("rounds")
    return 1e3 * s["total_s"] / n if s and n else None
