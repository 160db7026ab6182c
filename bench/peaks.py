"""Published peaks of the chips the benchmark runs on, keyed by the
`device_kind` that JAX reports.  A kind that is not here is an error: a
share of a peak is never taken against a guess."""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peak:
    kind: str
    bf16_flops_per_s: float
    hbm_bytes_per_s: float
    hbm_bytes: float
    source: str


PEAKS = {
    "TPU v5 lite": Peak(
        kind="TPU v5 lite", bf16_flops_per_s=197e12, hbm_bytes_per_s=819e9,
        hbm_bytes=16e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s"),
}


def peak_for(kind: str) -> Peak:
    try:
        return PEAKS[kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind {kind!r}; "
                         f"known: {sorted(PEAKS)}") from None
