"""rollup ingest: host milliseconds per round in
`fleet_hist.ofu_bucket_hist`: host work and dispatch of each job's fold,
up to the kernel call's return, from the program's `hist.launch` span
(recorded while the profiler traces the window)."""


def read(run):
    try:
        from repro.core import spans
    except ImportError:                  # a program without spans
        return None
    s = spans.snapshot()["spans"].get("hist.launch")
    n = run.counters.get("rounds")
    return 1e3 * s["total_s"] / n if s and n else None
