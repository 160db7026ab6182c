"""detect: host milliseconds per round in `scan_rollup` and
`analyze_rollup`, from the harness's span."""


def read(run):
    return run.per_unit("detect", "rounds")
