"""device: idle share of the traced window of a train cell, 1 - busy /
window, busy being the union of the device's operations."""


def read(run):
    if not run.trace or not run.counters.get("steps"):
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
