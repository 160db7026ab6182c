"""Chip benchmark of the OFU fleet system and the model jobs it watches.

`python3 -m bench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>`
runs one cell of `BENCHMARK.json` once.  Everything a cell needs is found by
name: its configuration in `bench/configs/`, its traffic in `bench/traffic/`,
the driver its configuration names in `bench/drivers/`, and each per-layer
metric's reader in `bench/metrics/`.
"""
