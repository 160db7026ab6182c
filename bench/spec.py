"""`BENCHMARK.json` and the files it names, found by name."""
from __future__ import annotations

import importlib.util
import json
import sys
from dataclasses import dataclass
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: list
    per_layer: list


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _for_cell(metrics: list, cell: str) -> list:
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def resolve(workload: str, bench: dict | None = None,
            root: Path = ROOT) -> Cell:
    bench = bench or load_benchmark(root)
    (w,) = [w for w in bench["workloads"] if w["name"] == workload] or [None]
    if w is None:
        names = [w["name"] for w in bench["workloads"]]
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                         f"(have {names})")
    (c,) = [c for c in bench["configs"] if c["name"] == w["config"]]
    with open(root / c["file"]) as f:
        config = json.load(f)
    with open(BENCH / "traffic" / f"{w['traffic']}.json") as f:
        mix = json.load(f)
    return Cell(w["name"], int(w["chips"]), config, mix,
                _for_cell(bench["end_to_end"], w["name"]),
                _for_cell(bench["per_layer"], w["name"]))


def load_module(kind: str, name: str):
    """bench/<kind>/<name>.py as a module (names may hold dots), loaded
    once into `sys.modules`, so its classes stay the same objects across
    calls (and dataclasses can look their module up)."""
    mod_name = f"bench_{kind}_{name}"
    if mod_name not in sys.modules:
        path = BENCH / kind / f"{name}.py"
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[mod_name] = mod
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[mod_name]
            raise
    return sys.modules[mod_name]
