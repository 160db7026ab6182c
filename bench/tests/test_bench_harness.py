"""The harness on the CPU: every cell resolves its files by name, traffic
is a function of the seed, the window arithmetic is exact, the FLOP count
agrees with the program's accounting, and `bench.run` refuses to run
without a TPU."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

from bench import flops, generate, spec, window  # noqa: E402
from bench.peaks import PEAKS, peak_for  # noqa: E402
from bench.record import Compared, Run  # noqa: E402
from bench.spans import Spans  # noqa: E402
from bench.trace import Summary  # noqa: E402

BENCH = spec.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
BIG_SEED = 2 ** 31 + 2 ** 33 + 12345


@pytest.mark.parametrize("name", CELLS)
def test_each_cell_resolves_its_files_by_name(name):
    cell = spec.resolve(name, BENCH)
    assert cell.chips in (1, 4)
    driver = spec.load_module("drivers", cell.config["driver"])
    assert callable(driver.run) and callable(driver.control)
    assert generate.make(cell.mix, 1) is not None
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(spec.load_module("metrics", m["name"]).read)
        assert m["moves"] in names
    assert set(cell.config["limits"]) >= {"nonfinite_losses"} or \
        set(cell.config["limits"]) >= {"hist_cells_differ"}


def test_every_named_file_is_under_the_benchmark_paths():
    root = spec.ROOT
    for c in BENCH["configs"]:
        assert (root / c["file"]).is_file()
        assert c["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
    for w in BENCH["workloads"]:
        assert (spec.BENCH / "traffic" / f"{w['traffic']}.json").is_file()
    for m in BENCH["per_layer"]:
        assert (spec.BENCH / "metrics" / f"{m['name']}.py").is_file()


def test_an_unknown_workload_is_refused():
    with pytest.raises(SystemExit, match="no workload"):
        spec.resolve("no_such_cell", BENCH)


@pytest.mark.parametrize("name", CELLS)
def test_traffic_is_a_function_of_the_seed(name):
    mix = spec.resolve(name, BENCH).mix
    a, b = generate.make(mix, BIG_SEED), generate.make(mix, BIG_SEED)
    assert a == b
    assert generate.make(mix, BIG_SEED + 1) != a


def test_fleet_rounds_keep_their_shapes_and_change_their_seeds():
    mix = spec.resolve("fleet_megascale", BENCH).mix
    t = generate.make(mix, BIG_SEED)
    assert len(t.jobs) == mix["jobs"] == 81
    assert sum(j.planted for j in t.jobs) == 1
    lo, hi = mix["duty"]
    assert all(lo <= j.duty <= hi for j in t.jobs)
    assert t.round_seeds(3) == generate.make(mix, BIG_SEED).round_seeds(3)
    assert t.round_seeds(3) != t.round_seeds(4)
    other = generate.make(mix, 7)
    assert [j.arch for j in other.jobs] == [j.arch for j in t.jobs]


def test_p95_is_the_nearest_rank_over_all_values():
    assert window.p95(range(1, 101)) == 95
    assert window.p95([5.0]) == 5.0
    assert window.p95(list(range(1, 21))) == 19
    with pytest.raises(ValueError):
        window.p95([])


def test_rate_is_all_work_over_all_time():
    assert window.rate(3.58e9 * 200, 50.0) == pytest.approx(1.432e10)
    with pytest.raises(ValueError):
        window.rate(1.0, 0.0)


def _fleet_run(**trace):
    run = Run(peak=peak_for("TPU v5 lite"))
    run.counters = {"rounds": 100, "samples_per_round": 120_000_000}
    run.window_s = 25.0
    run.spans.total_s.update(simulate=2.0, ingest=5.0, detect=1.0)
    run.spans.count.update(simulate=100, ingest=100, detect=100)
    run.trace = Summary(["/device:TPU:0"], 25.0, 20.0, **trace)
    return run


def test_fleet_readers():
    run = _fleet_run(program_s={"jit__group_device_sim": 15.0,
                                "jit__hist_pallas": 2.0})
    read = lambda n: spec.load_module("metrics", n).read(run)
    assert read("generate_device_ms") == pytest.approx(150.0)
    assert read("generate_host_ms") == pytest.approx(20.0)
    assert read("ingest_host_ms") == pytest.approx(50.0)
    assert read("detect_ms") == pytest.approx(10.0)
    assert read("device_idle_pct.fleet") == pytest.approx(20.0)
    # 8 bytes x 1.2e10 samples at 819 GB/s over 2 s of kernel programs
    assert read("hist_roofline_pct") == pytest.approx(
        100 * 8 * 1.2e10 / 819e9 / 2.0)
    assert read("train_mfu_pct") is None
    assert read("device_idle_pct.train") is None


def test_a_reader_with_nothing_to_read_returns_none():
    run = _fleet_run(program_s={})
    for name in ("generate_device_ms", "hist_roofline_pct",
                 "train_step_device_ms", "data_ms.train"):
        assert spec.load_module("metrics", name).read(run) is None


def test_train_readers():
    run = Run(peak=peak_for("TPU v5 lite"))
    run.counters = {"steps": 40, "model_flops_per_step": 3.0e13}
    run.window_s = 25.0
    run.spans.total_s.update(data=0.08)
    run.spans.count.update(data=40)
    run.trace = Summary(["/device:TPU:0"], 25.0, 24.0,
                        program_s={"jit_train_step": 24.0})
    read = lambda n: spec.load_module("metrics", n).read(run)
    assert read("train_mfu_pct") == pytest.approx(
        100 * 3.0e13 * 40 / 25.0 / 197e12)
    assert read("train_step_device_ms") == pytest.approx(600.0)
    assert read("data_ms.train") == pytest.approx(2.0)
    assert read("device_idle_pct.train") == pytest.approx(4.0)


def test_qwen3_flops_agree_with_the_program_accounting():
    from repro.configs.base import ShapeSpec
    from repro.flops.accounting import step_flops
    from bench.drivers.train import program_config
    cell = spec.resolve("qwen3_4b_train_b4s2048", BENCH)
    cfg = program_config(cell.config)
    b, s = cell.mix["batch"], cell.mix["seq"]
    ours = flops.dense_train_flops(cell.config, b, s)
    theirs = step_flops(cfg, ShapeSpec("x", s, b, "train"),
                        executed=False).total_mxu
    assert ours == pytest.approx(theirs, rel=1e-12)
    assert 4.0e13 < ours < 4.1e13


def test_the_file_holds_the_configuration_as_run():
    from bench.drivers.train import FIELDS, program_config
    cell = spec.resolve("qwen3_4b_train_b4s2048", BENCH)
    cfg = program_config(cell.config)
    for key, field in FIELDS.items():
        assert getattr(cfg, field) == cell.config[key]
    assert cfg.qk_norm == cell.config["qk_norm"] is True
    assert cfg.family == "dense" and cfg.num_experts == 0
    # the program scales attention by head_dim ** -0.5, as Qwen3 does, and
    # has no sliding window, bias or rope scaling to switch on
    assert not cell.config["attention_bias"]
    assert not cell.config["use_sliding_window"]
    assert cell.config["rope_scaling"] is None


def test_peaks_refuse_an_unknown_kind():
    assert peak_for("TPU v5 lite").bf16_flops_per_s == 197e12
    assert all(p.source for p in PEAKS.values())
    with pytest.raises(ValueError, match="no published peaks"):
        peak_for("cpu")


def test_correct_needs_every_number_within_its_limit():
    run = Run(peak=None, spans=Spans())
    assert not run.correct
    run.compared = [Compared("a", 0.0, 0.0), Compared("b", 1e-6, 1e-5)]
    assert run.correct
    run.compared.append(Compared("c", 2.0, 1.0))
    assert not run.correct


def _bench_run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", "fleet_megascale",
         "--seed", str(BIG_SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=240)


def test_run_exits_nonzero_without_a_tpu():
    p = _bench_run(spec.ROOT)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_run_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _bench_run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert not Path(tmp_path / "src").exists()
