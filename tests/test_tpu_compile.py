"""Compile the fleet's scan for TPU v5e chips that are described, not attached.

The TPU compiler refuses what interpret mode and the CPU backend accept: a
program that does not fit the chip's memory, a sharding it cannot
partition. These tests compile the served path at the paper's geometry (8
ranks x 64 cores x 16 tasklets, 32 MiB heaps) for one chip of a ``v5e:2x2``
and rank-sharded over all four. The topology is described inside a fixture,
so only the worker that runs this file loads the TPU compiler.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from repro.core import heap as heap_api
from repro.core import system as sysm
from repro.launch.serve_fleet import FleetServe

R, C, T, HEAP, ROUNDS = 8, 64, 16, 32 << 20, 64
V5E_HBM_BYTES = 16 << 30


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache, so keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


def _compile_scan(engine, state_sharding, grid_sharding):
    cfg = engine.cfg
    state = jax.eval_shape(lambda: heap_api.sharded_init(cfg, R, C))
    state = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=state_sharding), state)
    grid = jax.ShapeDtypeStruct((ROUNDS, R, C, T), jnp.int32,
                                sharding=grid_sharding)
    return engine._scan.lower(state, grid, grid, grid, grid).compile()


def _device_bytes(ma) -> int:
    """Bytes one device holds while the program runs (donated outputs
    alias their arguments)."""
    return (ma.argument_size_in_bytes + ma.temp_size_in_bytes
            + ma.output_size_in_bytes - ma.alias_size_in_bytes)


def test_hwsw_fleet_scan_fits_one_v5e(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    cfg = sysm.SystemConfig(kind="hwsw", heap_bytes=HEAP, num_threads=T)
    ma = _compile_scan(FleetServe(cfg, R, C), one_chip,
                       one_chip).memory_analysis()
    assert ma.alias_size_in_bytes > 0, "the heap state is not donated"
    assert _device_bytes(ma) < V5E_HBM_BYTES


def test_rank_sharded_fleet_scan_compiles_on_2x2(topo):
    mesh = Mesh(np.array(topo.devices), ("ranks",),
                axis_types=(AxisType.Auto,))
    cfg = sysm.SystemConfig(kind="hwsw", heap_bytes=HEAP, num_threads=T)
    compiled = _compile_scan(
        FleetServe(cfg, R, C, mesh=mesh),
        NamedSharding(mesh, PartitionSpec("ranks")),
        NamedSharding(mesh, PartitionSpec(None, "ranks")))
    ma = compiled.memory_analysis()
    state_bytes = sum(
        s.size * s.dtype.itemsize for s in jax.tree.leaves(jax.eval_shape(
            lambda: heap_api.sharded_init(cfg, R, C))))
    # each chip holds its two ranks of the state, not the whole fleet
    assert ma.argument_size_in_bytes < state_bytes / 3
    assert _device_bytes(ma) < V5E_HBM_BYTES
