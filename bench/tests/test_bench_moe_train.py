"""The MoE train cell on the CPU: its file holds the configuration as run,
its FLOP count agrees with the program's accounting, the expert matmul's
roofline reader, and a tiny-config driver run and control against
`bench/reference/mla_moe_lm.py`."""
import pytest

from bench import moe_flops, spec
from bench.peaks import peak_for
from bench.record import Run
from bench.tests import tiny
from bench.tests.moe_tiny import CELL, moe_cell
from bench.trace import Summary


def test_moe_flops_agree_with_the_program_accounting():
    from repro.configs.base import ShapeSpec
    from repro.flops.accounting import step_flops
    from bench.drivers.moe_train import program_config
    cell = spec.resolve(CELL)
    cfg = program_config(cell.config)
    b, s = cell.mix["batch"], cell.mix["seq"]
    ours = moe_flops.train_flops(cell.config, b, s)
    theirs = step_flops(cfg, ShapeSpec("x", s, b, "train"),
                        executed=False).total_mxu
    assert ours == pytest.approx(theirs, rel=1e-12)
    # 761 MFLOP a token forward at 1 + 4 layers, times 3, times 32,768
    assert ours == pytest.approx(3 * 32768 * 761.3e6, rel=1e-3)
    assert moe_flops.gmm_flops_per_pair(cell.config) == 18 * 2048 * 1408


def test_the_file_holds_the_configuration_as_run():
    from bench.drivers.moe_train import FIELDS, program_config
    c = spec.resolve(CELL).config
    cfg = program_config(c)
    for key, field in FIELDS.items():
        assert getattr(cfg, field) == c[key]
    assert cfg.family == "mla_moe" and cfg.q_lora_rank == 0
    assert cfg.num_experts == 64 == c["published"]["n_routed_experts"]
    assert cfg.experts_held == c["n_routed_experts"] == 8
    assert (cfg.ep_size, cfg.ep_rank) == (8, 0)
    assert cfg.router_score == "sigmoid" and cfg.router_bias
    assert c["norm_topk_prob"] and cfg.routed_scaling == 2.446
    assert cfg.head_dim == 192 and cfg.first_dense_layers == 1
    assert c["vocab_size"] * 8 == c["published"]["vocab_size"]
    assert c["num_hidden_layers"] == 5 < c["published"]["num_hidden_layers"]
    # nothing the program leaves out: one group, no MTP, no rope scaling
    assert c["n_group"] == c["topk_group"] == 1
    assert c["num_nextn_predict_layers"] == 0 and c["rope_scaling"] is None
    assert c["moe_layer_freq"] == 1 and not c["attention_bias"]


def test_the_registered_model_is_the_published_one():
    from repro.configs import get_config
    cfg = get_config("moonlight-16b-a3b")
    c = spec.resolve(CELL).config
    assert (cfg.num_layers, cfg.num_experts, cfg.vocab_size, cfg.ep_size) \
        == (27, 64, 163_840, 1)
    assert (cfg.d_model, cfg.d_ff, cfg.d_ff_expert, cfg.top_k) == (
        c["hidden_size"], c["intermediate_size"],
        c["moe_intermediate_size"], c["num_experts_per_tok"])


def test_gmm_roofline_reader():
    read = spec.load_module("metrics", "moe_gmm_roofline_pct").read
    run = Run(peak=peak_for("TPU v5 lite"))
    run.counters = {"steps": 10, "moe_held_pairs": 4_000_000,
                    "gmm_flops_per_pair": 18 * 2048 * 1408}
    run.window_s = 20.0
    run.trace = Summary(["/device:TPU:0"], 20.0, 19.0, op_s={
        "transpose_jvp_jit_tgmm___.4": 2.0, "jit_gmm.1": 3.0,
        "fusion.12": 7.0})
    assert read(run) == pytest.approx(
        100 * 18 * 2048 * 1408 * 4_000_000 / 197e12 / 5.0)
    run.trace = Summary(["/device:TPU:0"], 20.0, 19.0, op_s={"fusion.1": 1})
    assert read(run) is None


@pytest.fixture(scope="module")
def tiny_run():
    return tiny.run(moe_cell(), seconds=0.3)


def test_driver_runs_three_steps_against_the_reference(tiny_run):
    run = tiny_run
    assert run.correct, [(c.name, c.value, c.limit) for c in run.compared]
    assert [c.name for c in run.compared] == [
        "grad1_norm_gap", "delta3_norm_gap", "dropped_pairs",
        "nonfinite_losses"]
    n = run.counters["steps"]
    assert n == run.attempted >= 1 and run.failed == 0
    # 4 x 32 tokens, 2 pairs each, on 2 MoE layers: about half on the 4
    # experts held of 8
    assert 0 < run.counters["moe_held_pairs"] <= n * 4 * 32 * 2 * 2
    assert run.end_to_end["train_tokens_per_s"] > 0


def test_moe_control_fails_where_the_program_passes():
    cell = moe_cell()
    got = []
    driver = spec.load_module("drivers", cell.config["driver"])
    driver.control(cell, [21], {21},
                   lambda side, seed, r: got.append((side, r)))
    by = dict(got)
    limits = cell.config["limits"]
    fails = lambda r: [k for k, v in r.items()
                       if k in limits and v > limits[k]]
    assert not fails(by["program"])
    assert fails(by["control"]) and fails(by["half_batch"])
    assert by["half_batch"]["dropped_pairs"] > 0
    assert by["routing"]["program_held"] and by["routing"]["reference_held"]
    assert len(by["routing"]["ties_at_0.01"]) == 3
    assert "loss_rel_gap" in by["program"]


@pytest.mark.parametrize("prog, want", [
    ([100, 90], 0.0), ([95, 90], 0.0), ([94, 90], 6.0), ([101, 80], 0.0)])
def test_dropped_pairs_counts_a_shortfall_past_the_ties_whole(prog, want):
    """Step 1 only: later steps route with weights the two sides updated
    apart."""
    from bench.drivers.moe_train import dropped_pairs
    ref = {"held_pairs": [100, 90], "held_ties": [{1e-3: 5}, {1e-3: 5}]}
    assert dropped_pairs({"held_pairs": prog}, ref, 1e-3) == want
