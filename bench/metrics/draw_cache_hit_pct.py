"""generate: share of `jobs._job_draws` lookups that the draw memo
answered, from the program's `fleet.draw_cache.hit` and `.miss` counters
(recorded while the profiler traces the window)."""


def read(run):
    try:
        from repro.core import spans
    except ImportError:                  # a program without counters
        return None
    c = spans.snapshot()["counters"]
    hit = c.get("fleet.draw_cache.hit", 0)
    lookups = hit + c.get("fleet.draw_cache.miss", 0)
    return 100.0 * hit / lookups if lookups else None
