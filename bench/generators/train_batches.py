"""Traffic of a train cell: closed-loop steps at the mix's batch and
sequence length.  The program's own data pipeline makes each step's batch
from (data seed, step), and the reference makes it again by the same
stated rule; the seed also gives the weights' init seed."""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class TrainBatches:
    batch: int
    seq: int
    data_seed: int
    init_seed: int


def make(mix: dict, seed: int) -> TrainBatches:
    init_seed = int(np.random.SeedSequence([int(seed), 0])
                    .generate_state(1)[0] % 2 ** 31)
    return TrainBatches(int(mix["batch"]), int(mix["seq"]), int(seed),
                        init_seed)
