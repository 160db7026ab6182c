#!/usr/bin/env python
"""Replay the labeled scenario library and score every detector.

Runs `repro.scenarios.run_scorecard` over the scenario library (or a
subset), prints one BENCH line per (scenario, detector) with precision /
recall / time-to-detect, and writes the full scorecard document:

    PYTHONPATH=src python tools/fleet_scorecard.py
    PYTHONPATH=src python tools/fleet_scorecard.py \
        --scenario gloo_regression_2p5x --engine jax --json card.json

`--self-check` is the CI gate: run the whole library and fail (exit 1)
when any pinned precision / recall / time-to-detect floor in
`repro.scenarios.scorecard.FLOORS` regresses.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

try:
    import repro  # noqa: F401
except ImportError:                        # ran without PYTHONPATH=src
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"))

from repro.scenarios import (FLOORS, check_floors, run_scorecard,
                             scenario_names)


def run(names, *, engine: str, json_path=None) -> int:
    doc = run_scorecard(names, engine=engine)
    # one BENCH line per (scenario, detector)
    for scen, entry in sorted(doc["scenarios"].items()):
        for det, s in sorted(entry["detectors"].items()):
            print("BENCH " + json.dumps(
                {"name": f"scorecard/{scen}/{det}",
                 **{k: s[k] for k in ("precision", "recall", "ttd_s",
                                      "n_alerts", "n_labels")}}))
    if json_path:
        with open(json_path, "w") as f:
            json.dump(doc, f, indent=2)
            f.write("\n")
        print(f"scorecard written to {json_path}")
    # a partial run (--scenario) checks only the floors it measured; the
    # full sweep keeps the "missing from scorecard" guard
    floors = FLOORS if names is None else {
        k: v for k, v in FLOORS.items() if k[0] in doc["scenarios"]}
    bad = check_floors(doc, floors)
    for v in bad:
        print(f"FLOOR VIOLATION: {v}", file=sys.stderr)
    n = sum(len(e["detectors"]) for e in doc["scenarios"].values())
    print(f"scorecard: {len(doc['scenarios'])} scenarios, {n} "
          f"(scenario, detector) cells, {len(bad)} floor violations")
    return 1 if bad else 0


def self_check() -> int:
    """CI gate: the whole library must hold every pinned floor."""
    print(f"self-check: {len(scenario_names())} scenarios, "
          f"{len(FLOORS)} pinned floors")
    return run(None, engine="fused")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--scenario", action="append", default=None,
                    metavar="NAME", choices=scenario_names(),
                    help="score only this scenario (repeatable; "
                         "default: all)")
    ap.add_argument("--engine", default="fused",
                    choices=["fused", "jax"],
                    help="simulation backend (faults are post-hoc, so "
                         "ground truth is identical on both)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write the full scorecard document here")
    ap.add_argument("--self-check", action="store_true",
                    help="CI gate: full library, fail on any floor "
                         "violation")
    args = ap.parse_args(argv)
    if args.self_check:
        return self_check()
    return run(args.scenario, engine=args.engine, json_path=args.json)


if __name__ == "__main__":
    sys.exit(main())
