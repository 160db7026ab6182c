"""Readings that the limits of a cell's comparison are set from.

    python3 -m bench.control --workload <name> --seeds 1,2,... --control-seeds 7,8,9

For each seed it drives the cell's timed path through its set-up (a fleet
round, or a train step's first three steps) and prints the numbers its run
would compare, against the plain reference.  For each control seed it also
prints them for the control (the reference, computed in the precision
below the configuration's, put in the program's place) and, for a train
cell, for a step that leaves half of its batch out.  One JSON line each:
{"side": "program" | "control" | "half_batch", "seed": n, <name>: value}.
The benchmark's own runs do not run this; it needs the chip the cell
names, like `bench.run`.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

from bench import spec


def _emit(side: str, seed: int, readings: dict) -> None:
    print(json.dumps({"side": side, "seed": seed, **readings}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    ints = lambda s: [int(x) for x in s.split(",") if x]
    seeds, control = ints(args.seeds), set(ints(args.control_seeds))
    cell = spec.resolve(args.workload)
    sys.path.insert(0, str(spec.ROOT / "src"))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(spec.ROOT / ".jax_cache")
    import jax
    from bench.run import Device, enable_cache
    Device(jax, cell.chips)
    enable_cache(jax)
    driver = spec.load_module("drivers", cell.config["driver"])
    driver.control(cell, seeds + sorted(control - set(seeds)), control,
                   _emit)
    return 0


if __name__ == "__main__":
    sys.exit(main())
