"""Compile the main path's kernels and jitted steps for a described TPU
v5e, at the sizes the chip smoke runs, without a chip.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.  Nothing here runs; a compile that passes says the
chip's compiler accepts the program and that it fits, nothing about
results or times.
"""
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy

from jax.sharding import (Mesh, NamedSharding, PartitionSpec,  # noqa: E402
                          SingleDeviceSharding)

from repro.fleet.engine import EngineParams, JobSlot, group_slots  # noqa: E402
from repro.fleet.engine_jax import (_group_device_sim,  # noqa: E402
                                    _group_inputs, _split_group)
from repro.kernels.fleet_hist import (_hist_pallas,  # noqa: E402
                                      _hist_pallas_sharded)
from repro.telemetry import Event, StepProfile  # noqa: E402

BINS = 128
INV_FMAX = 1 / 1500.0


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_hist(sharding, D, S, spb, n_buckets):
    grid = _sds((D, S), jnp.float32, sharding)
    return _hist_pallas.lower(
        grid, grid, _sds((BINS + 1,), jnp.float32, sharding), spb=spb,
        n_buckets=n_buckets, inv_fmax=INV_FMAX, interpret=False).compile()


@pytest.mark.parametrize("D,S,spb", [
    (1_000_000, 120, 10),       # 1M devices x 1 h of 30 s scrapes
    (100_000, 2880, 7),         # a day of 30 s scrapes; last bucket short
], ids=["1M_x_1h", "100k_x_24h_short_last"])
def test_hist_kernel_compiles_for_v5e(one_chip, D, S, spb):
    n_buckets = -(-S // spb)
    compiled = _compile_hist(one_chip, D, S, spb, n_buckets)
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= 2 * D * S * 4
    out = jax.eval_shape(
        lambda: _hist_pallas(jnp.zeros((8, S)), jnp.zeros((8, S)),
                             jnp.zeros(BINS + 1), spb=spb,
                             n_buckets=n_buckets, inv_fmax=INV_FMAX,
                             interpret=True))
    assert out[0].shape == (n_buckets, BINS) and out[0].dtype == jnp.int32
    assert out[1].shape == (n_buckets,)


def test_engine_group_step_compiles_for_v5e(one_chip):
    """The jitted device half of the engine at 1M devices x 1 h, with the
    paper's 2.5x slowdown window (every row evented)."""
    slot = JobSlot(StepProfile(mxu_time_s=0.84, step_time_s=2.0), 3600.0,
                   30.0, events=[Event(600, 1200, slowdown=2.5)],
                   stragglers=np.ones(1_000_000))
    (members,) = group_slots([slot]).values()
    args, static = _group_inputs(members, np.random.default_rng(0),
                                 EngineParams(), None)
    shapes = [_sds(np.shape(a), jnp.asarray(a).dtype, one_chip)
              for a in args]
    compiled = _group_device_sim.lower(*shapes, **static).compile()
    mem = compiled.memory_analysis()
    assert 2 * 1_000_000 * 120 * 4 <= mem.output_size_in_bytes < 1e9
    assert mem.temp_size_in_bytes < 8 * 2 ** 30


def test_group_split_compiles_for_v5e(one_chip):
    """The split of an 81-job x 12,288-device group into per-job grids:
    one program, 162 outputs that together copy the group's grids once."""
    J, nd, S = 81, 12_288, 120
    grid = _sds((J * nd, S), jnp.float32, one_chip)
    compiled = _split_group.lower(
        grid, grid, _sds((J,), jnp.int32, one_chip),
        sizes=((nd, S),) * J).compile()
    mem = compiled.memory_analysis()
    grids = 2 * J * nd * S * 4
    assert grids <= mem.output_size_in_bytes < grids + 2 ** 16   # + tuple
    assert mem.temp_size_in_bytes < nd * S * 4
    assert compiled.as_text().count("dynamic-slice(") >= 2 * J


def test_hist_kernel_partitions_over_v5e_mesh(topo):
    """On a 2x2 mesh each chip runs the kernel on its quarter of the rows
    and only the (bins, S) counts cross chips: no all-gather of the grid."""
    mesh = Mesh(np.array(topo.devices), ("devices",))
    rows = NamedSharding(mesh, PartitionSpec("devices", None))
    D, S = 1_000_000, 120
    grid = _sds((D, S), jnp.float32, rows)
    edges = _sds((BINS + 1,), jnp.float32,
                 NamedSharding(mesh, PartitionSpec()))
    compiled = _hist_pallas_sharded.lower(
        grid, grid, edges, mesh=mesh, axis="devices", spb=10,
        n_buckets=12, inv_fmax=INV_FMAX, interpret=False).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" not in text
    assert f"f32[{D // 4},{S}]" in text
    assert compiled.memory_analysis().argument_size_in_bytes \
        < D * S * 4                             # a quarter of each grid


def test_device_programs_carry_stable_stage_names(one_chip):
    """The kernel's custom call is named `ofu_hist`, and the engine's
    stages (`duty`, `jitter`, `clock_ou`) reach the compiled ops'
    metadata, so a device trace can split each program by stage."""
    S = 120
    kernel = _compile_hist(one_chip, 1024, S, 10, 12).as_text()
    assert "%ofu_hist" in kernel and "tpu_custom_call" in kernel
    slot = JobSlot(StepProfile(mxu_time_s=0.84, step_time_s=2.0), 3600.0,
                   30.0, events=[Event(600, 1200, slowdown=2.5)],
                   stragglers=np.ones(1024))
    (members,) = group_slots([slot]).values()
    args, static = _group_inputs(members, np.random.default_rng(0),
                                 EngineParams(), None)
    shapes = [_sds(np.shape(a), jnp.asarray(a).dtype, one_chip)
              for a in args]
    engine = _group_device_sim.lower(*shapes, **static).compile().as_text()
    for stage in ("duty", "jitter", "clock_ou"):
        assert f"jit(_group_device_sim)/{stage}/" in engine, stage
