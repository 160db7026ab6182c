"""Detector scorecard (ISSUE 8 tentpole): scoring semantics, the pinned
precision/recall/time-to-detect floors, the frozen scorecard document,
and the golden fault-injected archive fixture.

The full-library replay runs ONCE per module (it is the same run CI's
scorecard job performs) and every downstream assertion reads from it.
"""
import json
import os

import numpy as np
import pytest

from repro.fleet.collector import Alert
from repro.fleet.engine import CounterFault, apply_faults
from repro.scenarios import (FLOORS, SCHEMA, GroundTruthEvent, Scenario,
                             build, check_floors, run_scenario,
                             run_scorecard, score_alerts)
from repro.fleet.jobs import JobSpec
from repro.telemetry import read_trace
from repro.telemetry.scrape import DeviceGrid

DATA = os.path.join(os.path.dirname(__file__), "data")

# tools/ is scripts, not a package: load the CLI module by path
import importlib.util  # noqa: E402

_spec = importlib.util.spec_from_file_location(
    "fleet_scorecard", os.path.join(os.path.dirname(DATA), "..",
                                    "tools", "fleet_scorecard.py"))
fleet_scorecard = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(fleet_scorecard)
main = fleet_scorecard.main


@pytest.fixture(scope="module")
def card():
    """One full-library scorecard — the exact document CI gates on."""
    return run_scorecard()


# ---------------------------------------------------------------------------
# scoring semantics (synthetic alerts, no simulation)
# ---------------------------------------------------------------------------
def _toy_scenario(labels, tolerance_s=100.0):
    return Scenario("toy", "toy", [JobSpec("a", "llama3.2-3b",
                                           duration_s=1000.0),
                                   JobSpec("b", "qwen3-4b",
                                           duration_s=1000.0)],
                    labels, tolerance_s=tolerance_s)


def _alert(job_id, kind, t_s, round_idx=1):
    return Alert(round_idx, t_s, job_id, kind, "msg", factor=2.0)


def test_score_matching_precision_recall_ttd():
    sc = _toy_scenario([
        GroundTruthEvent("a", "regression", 200.0, end_s=400.0),
        GroundTruthEvent("b", "regression", 600.0),
    ])
    alerts = [
        _alert("a", "regression", 300.0),     # matches label 1, ttd 100
        _alert("a", "regression", 950.0),     # outside a's window: FP
        _alert("b", "divergence", 700.0),     # wrong kind for the label
    ]
    s = score_alerts(sc, alerts)["regression"]
    assert s.n_alerts == 2 and s.n_matched_alerts == 1
    assert s.precision == pytest.approx(0.5)
    assert s.n_labels == 2 and s.n_matched_labels == 1
    assert s.recall == pytest.approx(0.5)
    assert s.ttd_s == pytest.approx(100.0)
    # the divergence alert is scored under its own detector, as a FP
    d = score_alerts(sc, alerts)["divergence"]
    assert d.precision == 0.0 and d.recall == 1.0 and d.n_labels == 0


def test_score_tolerance_window_extends_label_end():
    sc = _toy_scenario([GroundTruthEvent("a", "regression", 200.0,
                                         end_s=400.0)], tolerance_s=150.0)
    assert score_alerts(sc, [_alert("a", "regression", 540.0)]) \
        ["regression"].recall == 1.0
    assert score_alerts(sc, [_alert("a", "regression", 560.0)]) \
        ["regression"].recall == 0.0
    # an alert BEFORE onset never matches (detection cannot precede cause)
    assert score_alerts(sc, [_alert("a", "regression", 150.0)]) \
        ["regression"].precision == 0.0


def test_score_silent_and_unlabeled_edge_cases():
    sc = _toy_scenario([])
    s = score_alerts(sc, [])["regression"]
    assert s.precision == 1.0 and s.recall == 1.0 and s.ttd_s is None


# ---------------------------------------------------------------------------
# the paper scenario + the full-library scorecard
# ---------------------------------------------------------------------------
def test_paper_2p5x_scenario_scores_perfectly():
    sc = build("gloo_regression_2p5x")
    run = run_scenario(sc)
    s = score_alerts(sc, run.alerts)["regression"]
    assert s.precision == 1.0 and s.recall == 1.0
    assert s.ttd_s is not None and s.ttd_s <= 1200.0
    # the alert carries (roughly) the injected 2.5x magnitude
    (a,) = [a for a in run.alerts if a.kind == "regression"]
    assert a.factor == pytest.approx(2.5, rel=0.2)


def test_scorecard_covers_all_detectors_on_all_scenarios(card):
    assert len(card["scenarios"]) >= 6
    for entry in card["scenarios"].values():
        assert set(entry["detectors"]) \
            == {"regression", "divergence", "goodput", "miscalc"}


def test_scorecard_holds_every_pinned_floor(card):
    assert check_floors(card) == []


def test_check_floors_flags_doctored_results(card):
    doc = json.loads(json.dumps(card))
    cell = doc["scenarios"]["gloo_regression_2p5x"] \
              ["detectors"]["regression"]
    cell["precision"] = 0.5
    cell["ttd_s"] = 99999.0
    bad = check_floors(doc)
    assert any("precision 0.500" in v for v in bad)
    assert any("ttd 99999s" in v for v in bad)
    # an undetected floored cell and a missing scenario both violate
    cell["ttd_s"] = None
    del doc["scenarios"]["thermal_throttle"]
    bad = check_floors(doc)
    assert any("no detection" in v for v in bad)
    assert any("thermal_throttle/regression: missing" in v for v in bad)
    # every floor key refers to a real (scenario, detector) cell
    for scen, det in FLOORS:
        assert det in card["scenarios"][scen]["detectors"], (scen, det)


def test_scorecard_document_is_frozen(card):
    """The committed golden scorecard pins BOTH the schema shape and the
    measured values: a detector or engine change that moves any score
    must regenerate tests/data/golden_scorecard.json deliberately
    (PYTHONPATH=src python tools/fleet_scorecard.py
    --json tests/data/golden_scorecard.json --no-bench-json)."""
    with open(os.path.join(DATA, "golden_scorecard.json")) as fh:
        golden = json.load(fh)
    assert card["schema"] == SCHEMA == golden["schema"]
    assert card == golden


# ---------------------------------------------------------------------------
# golden fault-injected archive
# ---------------------------------------------------------------------------
def _golden_base_grid():
    d, s = 3, 20
    iv, t0 = 30.0, 300.0
    tpa = 0.3 + 0.15 * np.sin(2 * np.pi * np.arange(d)[:, None] / 3.0
                              + np.arange(s) / 7.0)
    clk = 1300.0 - 50.0 * np.cos(np.arange(s) / 5.0) \
        + 10.0 * np.arange(d)[:, None]
    return DeviceGrid(iv, tpa, clk, t0_s=t0)


GOLDEN_FAULTS = [
    CounterFault(start_s=600.0, duty_scale=0.4, kind="gloo_regression"),
    CounterFault(start_s=450.0, end_s=750.0, clock_scale=0.7,
                 devices=(1,), kind="thermal"),
]


def test_golden_scenario_archive_is_exact():
    """tests/data/golden_scenario.ctr freezes the fault layer's output:
    re-applying the same `CounterFault`s to the same deterministic base
    grid must reproduce the committed archive EXACTLY, so a semantic
    drift in masking/compounding/clipping fails here before it silently
    relabels every scenario."""
    want = apply_faults(_golden_base_grid(), GOLDEN_FAULTS)
    got = read_trace(os.path.join(DATA, "golden_scenario.ctr"))
    assert got.interval_s == want.interval_s
    assert got.t0_s == want.t0_s
    np.testing.assert_array_equal(got.tpa, want.tpa)
    np.testing.assert_array_equal(got.clock_mhz, want.clock_mhz)


# ---------------------------------------------------------------------------
# the CLI
# ---------------------------------------------------------------------------
def test_cli_single_scenario_exits_clean(tmp_path, capsys):
    out_json = tmp_path / "card.json"
    assert main(["--scenario", "gloo_regression_2p5x",
                 "--json", str(out_json)]) == 0
    doc = json.loads(out_json.read_text())
    assert list(doc["scenarios"]) == ["gloo_regression_2p5x"]
    lines = [l for l in capsys.readouterr().out.splitlines()
             if l.startswith("BENCH ")]
    names = {json.loads(l[6:])["name"] for l in lines}
    assert "scorecard/gloo_regression_2p5x/regression" in names
