"""Compile the fleet's scan for TPU v5e chips that are described, not attached.

The TPU compiler refuses what interpret mode and the CPU backend accept: a
program that does not fit the chip's memory, a sharding it cannot
partition. These tests compile the served path at the paper's geometry (8
ranks x 64 cores x 16 tasklets, 32 MiB heaps) for one chip of a ``v5e:2x2``
and rank-sharded over all four. The topology is described inside a fixture,
so only the worker that runs this file loads the TPU compiler.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from repro.core import heap as heap_api
from repro.core import pim_malloc as pm
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


def test_malloc_refill_is_not_a_per_core_loop(topo):
    """Under the fleet's two vmaps, a freelist-row write at a per-core class
    index is a batched gather and scatter, which the TPU backend expands
    into serial `while` loops over the cores. malloc writes its refilled
    rows in one dense select after the backend scan, so no such loop may
    carry `stacks` in the compiled program."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    cfg = pm.PimMallocConfig(heap_bytes=1 << 20, num_threads=T)
    ranks, cores = 2, 8
    state = jax.eval_shape(jax.vmap(jax.vmap(lambda _: pm.init(cfg))),
                           jnp.zeros((ranks, cores)))
    state = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=one_chip), state)
    sizes = jax.ShapeDtypeStruct((ranks, cores, T), jnp.int32,
                                 sharding=one_chip)
    hlo = jax.jit(jax.vmap(jax.vmap(lambda s, z: pm.malloc(cfg, s, z)))).lower(
        state, sizes).compile().as_text()
    stacks = f"s32[{ranks},{cores},{T},{cfg.nc},{cfg.cap}]"
    whiles = [line for line in hlo.splitlines()
              if re.search(r"= .*\bwhile\(", line)]
    assert whiles, "no while loop found: the HLO text format changed"
    row_loops = [line.split(" = ")[0].strip() for line in whiles
                 if stacks in line
                 and re.search(r'op_name="[^"]*/(scatter|gather)"', line)]
    assert not row_loops, row_loops


def _computations(hlo: str) -> dict[str, list[str]]:
    """HLO text -> {computation name: its instruction lines}."""
    comps, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) \(.*\{$", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif line == "}":
            name = None
        elif name is not None:
            comps[name].append(line)
    return comps


def _reachable(comps: dict[str, list[str]], root: str) -> set[str]:
    """`root` and every computation it calls, transitively (loop bodies,
    fusions, reducers, branches)."""
    seen, todo = set(), [root]
    while todo:
        name = todo.pop()
        if name not in seen:
            seen.add(name)
            todo += [n for line in comps[name]
                     for n in re.findall(r"%([\w.\-]+)", line) if n in comps]
    return seen


@pytest.mark.parametrize("kind", ["sw", "hwsw"])
def test_cache_pass_is_not_a_per_core_gather(topo, kind):
    """Under the fleet's two vmaps, a cache-state read or write at a
    per-core entry index is a batched gather or scatter. Inside the cache
    pass's inner loop (one access per step, 2T x trace_len steps a round)
    each one costs tens of microseconds on a v5e, so the access functions
    select their entry with a dense one-hot mask instead. No gather or
    scatter may sit in the inner `simulate_traces` loop body."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    cfg = sysm.SystemConfig(kind=kind, heap_bytes=1 << 20, num_threads=T)
    ranks, cores = 2, 8

    def spec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    cache = jax.eval_shape(jax.vmap(jax.vmap(lambda _: cfg.cache_init())),
                           jnp.zeros((ranks, cores)))
    cache = jax.tree.map(lambda s: spec(s.shape, s.dtype), cache)
    bpos = spec((ranks, cores, 2 * T))
    traces = spec((ranks, cores, 2 * T, cfg.trace_len))
    hlo = jax.jit(jax.vmap(jax.vmap(
        lambda c, b, t: sysm._cache_pass(cfg, c, b, t)))).lower(
            cache, bpos, traces).compile().as_text()
    comps = _computations(hlo)
    loops = [(name, re.search(r"body=%([\w.\-]+)", line).group(1))
             for name, lines in comps.items() for line in lines
             if re.search(r"= .*\bwhile\(", line)]
    assert len(loops) >= 2, "no nested while loops found: the HLO text " \
                            "format or the loop structure changed"
    outer = set().union(*(_reachable(comps, body) for _, body in loops))
    inner = [body for name, body in loops if name in outer]
    assert inner, "no while loop nested in another: the HLO text format " \
                  "or the loop structure changed"
    hits = [f"{comp}: {line.strip()[:120]}"
            for body in inner for comp in _reachable(comps, body)
            for line in comps[comp] if re.search(r"\b(gather|scatter)\(", line)]
    assert not hits, hits
