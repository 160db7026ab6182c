"""The per-layer metrics read from the program's own spans and counters,
on a tiny fleet round run through the driver with the profiler tracing
the window, as `bench.run --trace 1` takes it."""
import sys

import jax
import pytest

from bench import spec
from bench.run import Window
from bench.tests import tiny

SPANS = {"generate_prep_ms": "fleet.prep",
         "generate_inputs_ms": "engine.inputs",
         "generate_slice_ms": "engine.slice",
         "ingest_launch_ms": "hist.launch",
         "ingest_fetch_ms": "rollup.fetch",
         "ingest_observe_ms": "rollup.observe"}
READERS = [*SPANS, "draw_cache_hit_pct"]


class TracedWindow(Window):
    """The harness's traced window, also counting the folds inside it."""

    def start(self):
        from repro.kernels.fleet_hist import ROUTES
        self.routes0 = ROUTES.copy()
        super().start()

    def stop(self):
        from repro.kernels.fleet_hist import ROUTES
        super().stop()
        self.folds = sum((ROUTES - self.routes0).values())


def read(name, run):
    return spec.load_module("metrics", name).read(run)


@pytest.fixture(scope="module")
def traced():
    from repro.core import spans
    from repro.fleet import jobs
    saved = dict(jobs._DRAW_CACHE)
    jobs._DRAW_CACHE.clear()             # earlier tests may hold these seeds
    spans.reset()
    cell = tiny.fleet_cell()
    window = TracedWindow(jax, trace=True)
    driver = spec.load_module("drivers", cell.config["driver"])
    run = driver.run(cell, tiny.BIG_SEED, 0.3, window, 0.0, tiny._Device())
    yield cell, run, window, spans.snapshot()
    spans.reset()
    jobs._DRAW_CACHE.clear()
    jobs._DRAW_CACHE.update(saved)


def test_every_reader_reads_the_traced_window(traced):
    cell, run, _, snap = traced
    assert run.correct and run.counters["rounds"] >= 1
    for name, span in SPANS.items():
        assert read(name, run) == pytest.approx(
            1e3 * snap["spans"][span]["total_s"] / run.counters["rounds"])
    assert read("draw_cache_hit_pct", run) == 0.0   # fresh seeds each round


def test_each_fold_is_launched_fetched_and_observed_once(traced):
    cell, run, window, snap = traced
    folds = cell.mix["jobs"] * run.counters["rounds"]
    assert window.folds == folds
    for span in ("hist.launch", "rollup.fetch", "rollup.observe",
                 "rollup.ofu"):
        assert snap["spans"][span]["count"] == folds
        assert snap["spans"][span]["parent"] is None
    assert snap["spans"]["fleet.prep"]["count"] == run.counters["rounds"]
    assert snap["counters"]["fleet.draw_cache.miss"] == folds


def test_the_split_lies_inside_the_harness_spans(traced):
    _, run, _, snap = traced
    total = lambda names: sum(snap["spans"][n]["total_s"] for n in names)
    assert total(["hist.launch", "rollup.fetch", "rollup.observe",
                  "rollup.ofu"]) <= run.spans.total_s["ingest"]
    assert total(["fleet.prep", "engine.inputs", "engine.slice"]) \
        <= run.spans.total_s["simulate"]


def test_a_round_simulated_again_hits_the_draw_memo(traced, tmp_path):
    from repro.core import spans
    from bench.drivers.fleet import Fleet
    from bench import generate
    cell, run, _, _ = traced
    fleet = Fleet(cell.config, generate.make(cell.mix, tiny.BIG_SEED))
    spans.reset()
    with jax.profiler.trace(str(tmp_path)):
        fleet.simulate(1)                # round 1 ran inside the window
    assert read("draw_cache_hit_pct", run) == 100.0
    spans.reset()
    assert read("draw_cache_hit_pct", run) is None


def test_readers_find_nothing_without_a_trace_or_without_spans(
        traced, monkeypatch):
    from repro.core import spans
    _, run, _, _ = traced
    spans.reset()
    assert all(read(name, run) is None for name in READERS)
    # a program from before the spans module: nothing to read, no error
    import repro.core
    monkeypatch.delattr(repro.core, "spans")
    monkeypatch.setitem(sys.modules, "repro.core.spans", None)
    assert all(read(name, run) is None for name in READERS)
