"""generate: host milliseconds per round in job preparation
(`jobs._prep_job`: the profile, the straggler and seed draws, the
`JobSlot`s), from the program's `fleet.prep` span (recorded while the
profiler traces the window)."""


def read(run):
    try:
        from repro.core import spans
    except ImportError:                  # a program without spans
        return None
    s = spans.snapshot()["spans"].get("fleet.prep")
    n = run.counters.get("rounds")
    return 1e3 * s["total_s"] / n if s and n else None
