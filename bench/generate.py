"""The traffic generator: a mix's data file from `bench/traffic/` names
its kind under `"generator"`, and `bench/generators/<kind>.py` makes the
cell's inputs from the mix and the run's seed alone.  The same seed gives
the same inputs; the program sees only what is made there.  A new kind of
traffic is a new file there."""
from __future__ import annotations

from bench import spec


def make(mix: dict, seed: int):
    return spec.load_module("generators", mix["generator"]).make(mix, seed)
