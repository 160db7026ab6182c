"""data: host milliseconds per step to make the batch
(`data/pipeline.synthetic_batch`) and put it on the device, from the
harness's span."""


def read(run):
    return run.per_unit("data", "steps")
