"""rollup ingest: host milliseconds per round in the `add_job` calls,
each histogram's wait to reach the host included, from the harness's
span."""


def read(run):
    return run.per_unit("ingest", "rounds")
