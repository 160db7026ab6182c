"""generate: host milliseconds per round inside `simulate_fleet` (job
prep, the group's inputs, per-job slicing), from the harness's span."""


def read(run):
    return run.per_unit("simulate", "rounds")
