"""The trace reduction, on a small recorded trace of two devices."""
from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).parent / "data" / "small_trace.events.json"


@pytest.fixture(scope="module")
def summary():
    return trace.reduce(trace.events_from_json(str(DATA)))


def test_window_is_the_harness_window_span(summary):
    assert summary.window_s == pytest.approx(850e-9)
    assert summary.devices == ["/device:TPU:0", "/device:TPU:1"]


def test_busy_is_the_union_of_ops_averaged_over_devices(summary):
    # TPU:0: [100, 400] + [500, 600] + [650, 700] (the op before the window
    # is cut away, overlapping ops count once); TPU:1: [100, 300]
    assert summary.busy_s == pytest.approx((450 + 200) / 2 * 1e-9)


def test_program_and_op_time(summary):
    assert summary.program_time("_hist_pallas") == pytest.approx(75e-9)
    assert summary.program_time("_group_device_sim") == pytest.approx(150e-9)
    assert summary.program_time("train_step") == 0.0
    # per-device seconds: each op's time over the two devices
    assert summary.top_ops(2) == [["fusion.9", pytest.approx(100e-9)],
                                  ["fusion.2", pytest.approx(80e-9)]]


def test_idle_gaps_labelled_by_the_span_covering_most(summary):
    assert summary.gap_labels() == [["detect", pytest.approx(200e-9)],
                                    ["ingest", pytest.approx(150e-9)],
                                    ["simulate", pytest.approx(50e-9)]]
    assert sum(s for _, s in summary.gaps) == pytest.approx(
        summary.window_s - 450e-9)
    assert summary.gaps[0] == ("simulate", pytest.approx(50e-9))


def test_a_trace_without_device_ops_is_refused():
    events = [e for e in trace.events_from_json(str(DATA))
              if not trace.is_device(e.plane)]
    with pytest.raises(ValueError, match="no device operation"):
        trace.reduce(events)


V5E_ROUND = Path(__file__).parent / "data" / "fleet_v5e_round.events.json"


@pytest.fixture(scope="module")
def chip_round():
    """One round of `fleet_megascale` cut from a traced run on one v5e
    (op names shortened): 81 jobs x 12,288 devices x 120 scrapes.  The
    events are `trace.load_xplane(dir)` of a `jax.profiler.start_trace(dir)`
    / `stop_trace()` pair around the window, as `bench.run --trace 1` takes
    it, cut to one `bench.window`-spanned round, each kept as
    [plane, line, name, start_ns, dur_ns] in a JSON list."""
    return trace.reduce(trace.events_from_json(str(V5E_ROUND)))


def test_chip_round_programs(chip_round):
    assert chip_round.devices == ["/device:TPU:0"]
    events = trace.events_from_json(str(V5E_ROUND))
    modules = [e.name for e in events if e.line == trace.MODULES_LINE]
    assert sum("_hist_pallas" in n for n in modules) == 81
    assert sum("_group_device_sim" in n for n in modules) == 1
    assert chip_round.program_time("_group_device_sim") == pytest.approx(
        42.73e-3, rel=1e-3)
    assert chip_round.program_time("_hist_pallas") == pytest.approx(
        22.77e-3, rel=1e-3)


def test_chip_round_busy_and_gaps(chip_round):
    assert chip_round.window_s == pytest.approx(0.5007, rel=1e-3)
    assert chip_round.busy_s == pytest.approx(71.98e-3, rel=1e-3)
    assert sum(s for _, s in chip_round.gaps) == pytest.approx(
        chip_round.window_s - chip_round.busy_s)
    assert [k for k, _ in chip_round.gap_labels()] == [
        "ingest", "simulate", "detect"]


def test_chip_round_kernel_roofline(chip_round):
    from bench.peaks import peak_for
    from bench.record import Run
    from bench import spec
    run = Run(peak=peak_for("TPU v5 lite"))
    run.counters = {"rounds": 1, "samples_per_round": 81 * 12288 * 120}
    run.trace = chip_round
    share = spec.load_module("metrics", "hist_roofline_pct").read(run)
    assert share == pytest.approx(5.12, abs=0.01)
