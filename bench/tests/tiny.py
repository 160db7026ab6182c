"""The benchmark's cells cut to a size the CPU runs in seconds, and a
driver run of one without the harness's look for a chip."""
import time

from bench import spec

BIG_SEED = 2 ** 31 + 2 ** 33 + 777


def fleet_cell():
    cell = spec.resolve("fleet_megascale")
    cell.mix = dict(cell.mix, jobs=6, devices_per_job=64)
    # at 7,680 samples a job, and a jitter of up to 0.03/8, a tpa mean
    # lies up to ~1e-4 from its duty by chance alone
    cell.config = dict(cell.config, limits=dict(cell.config["limits"],
                                                tpa_mean_gap=3e-4))
    return cell


def train_cell():
    cell = spec.resolve("qwen3_4b_train_b4s2048")
    cell.config = dict(
        cell.config, hidden_size=64, intermediate_size=128,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        num_hidden_layers=2, vocab_size=256)
    cell.mix = dict(cell.mix, batch=4, seq=32)
    # the cell's limits are set for its own size; at this size bf16 reads
    # further from the float32 reference
    cell.config["limits"] = {"loss_rel_gap": 1e-3, "grad1_norm_gap": 1e-2,
                             "delta3_norm_gap": 1e-2, "nonfinite_losses": 0}
    return cell


class _Window:
    def start(self):
        pass

    def stop(self):
        pass


class _Device:
    def peak_bytes(self):
        return 0


def run(cell, seconds=0.3, seed=BIG_SEED):
    driver = spec.load_module("drivers", cell.config["driver"])
    return driver.run(cell, seed, seconds, _Window(), time.perf_counter(),
                      _Device())
