"""rollup ingest: host milliseconds per round bringing each job's histogram
and sums to the host, its wait for the device included, from the
program's `rollup.fetch` span (recorded while the profiler traces the
window)."""


def read(run):
    try:
        from repro.core import spans
    except ImportError:                  # a program without spans
        return None
    s = spans.snapshot()["spans"].get("rollup.fetch")
    n = run.counters.get("rounds")
    return 1e3 * s["total_s"] / n if s and n else None
