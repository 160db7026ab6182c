"""generate: host milliseconds per round building the engine group's inputs
(`engine_jax._group_inputs`) and uploading them, from the program's
`engine.inputs` span (recorded while the profiler traces the window)."""


def read(run):
    try:
        from repro.core import spans
    except ImportError:                  # a program without spans
        return None
    s = spans.snapshot()["spans"].get("engine.inputs")
    n = run.counters.get("rounds")
    return 1e3 * s["total_s"] / n if s and n else None
