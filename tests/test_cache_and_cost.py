"""Tests for metadata-cache simulators, cost model, design space, and the
composed system (strawman / PIM-malloc-SW / PIM-malloc-HW/SW)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.core import buddy_cache as bc
from repro.core import cost_model as cm
from repro.core import design_space as ds
from repro.core import system as sysm


# ---------------------------------------------------------------- buddy cache
def test_cam_lru_behavior():
    cfg = bc.BuddyCacheConfig(n_entries=2)
    st = bc.buddy_cache_init(cfg)
    acc = jax.jit(functools.partial(bc.buddy_cache_access, cfg))
    # words: nodes 0-15 -> word 0, 16-31 -> word 1, 32-47 -> word 2
    st, h, d = acc(st, jnp.int32(0))
    assert not bool(h) and int(d) == bc.WORD_BYTES
    st, h, _ = acc(st, jnp.int32(5))   # same word -> hit
    assert bool(h)
    st, h, _ = acc(st, jnp.int32(16))  # second entry
    assert not bool(h)
    st, h, _ = acc(st, jnp.int32(32))  # evicts LRU (word 0)
    assert not bool(h)
    st, h, _ = acc(st, jnp.int32(17))  # word 1 still resident
    assert bool(h)
    st, h, _ = acc(st, jnp.int32(1))   # word 0 was evicted
    assert not bool(h)


def test_cam_vs_python_lru():
    """Random trace: CAM sim matches a dict-based LRU reference."""
    import random

    cfg = bc.BuddyCacheConfig(n_entries=4)
    st = bc.buddy_cache_init(cfg)
    acc = jax.jit(functools.partial(bc.buddy_cache_access, cfg))
    lru, clock = {}, 0
    rng = random.Random(0)
    for _ in range(200):
        node = rng.randrange(0, 512)
        word = node // bc.NODES_PER_WORD
        st, h, _ = acc(st, jnp.int32(node))
        py_hit = word in lru
        assert bool(h) == py_hit, (node, word, lru)
        if not py_hit and len(lru) == 4:
            del lru[min(lru, key=lru.get)]
        lru[word] = clock
        clock += 1


def test_sw_buffer_direct_mapped():
    cfg = bc.SWBufferConfig(buf_bytes=128, line_bytes=64)  # 2 lines
    st = bc.sw_buffer_init(cfg)
    acc = jax.jit(functools.partial(bc.sw_buffer_access, cfg))
    st, h, d = acc(st, jnp.int32(0))       # line 0
    assert not bool(h) and int(d) == 64
    st, h, _ = acc(st, jnp.int32(100))     # word 6, line 0 -> hit
    assert bool(h)
    st, h, _ = acc(st, jnp.int32(300))     # word 18, line 1
    assert not bool(h)
    st, h, _ = acc(st, jnp.int32(1026))    # word 64, line 4 -> maps to slot 0, evict
    assert not bool(h)
    st, h, _ = acc(st, jnp.int32(0))       # line 0 was evicted
    assert not bool(h)


def test_invalid_nodes_skipped():
    cfg = bc.BuddyCacheConfig()
    st = bc.buddy_cache_init(cfg)
    traces = jnp.array([[-1, -1, 3, -1]], jnp.int32)
    st, stats = bc.simulate_traces(
        functools.partial(bc.buddy_cache_access, cfg), st, traces
    )
    assert int(stats.hits[0]) == 0 and int(stats.misses[0]) == 1


# The indexed formulations the access functions had before they became dense
# one-hot selects: the reference the dense forms must match bit for bit.
def _indexed_sw_buffer_access(cfg, st, node):
    valid = node >= 0
    word = jnp.maximum(node, 0) // bc.NODES_PER_WORD
    line = word // cfg.line_words
    idx = line % cfg.n_lines
    hit = valid & (st.tags[idx] == line)
    miss = valid & ~hit
    tags = st.tags.at[idx].set(jnp.where(miss, line, st.tags[idx]))
    dram = jnp.where(miss, cfg.line_bytes, 0).astype(jnp.int32)
    return bc.SWBufferState(tags=tags), hit, dram


def _indexed_buddy_cache_access(cfg, st, node):
    del cfg
    valid = node >= 0
    word = jnp.maximum(node, 0) // bc.NODES_PER_WORD
    match = st.tags == word
    hit = valid & jnp.any(match)
    idx = jnp.where(hit, jnp.argmax(match), jnp.argmin(st.last_used))
    tags = st.tags.at[idx].set(jnp.where(valid, word, st.tags[idx]))
    last = st.last_used.at[idx].set(
        jnp.where(valid, st.clock, st.last_used[idx]))
    clock = st.clock + valid.astype(jnp.int32)
    dram = jnp.where(valid & ~hit, bc.WORD_BYTES, 0).astype(jnp.int32)
    return bc.BuddyCacheState(tags=tags, last_used=last, clock=clock), hit, dram


# name -> (config, init, dense access, indexed access, distinct words a
# full cache holds, nodes per slot)
_CACHES = {
    "sw": (bc.SWBufferConfig(), bc.sw_buffer_init, bc.sw_buffer_access,
           _indexed_sw_buffer_access, bc.SWBufferConfig().n_lines,
           bc.SWBufferConfig().line_words * bc.NODES_PER_WORD),
    "hwsw2": (bc.BuddyCacheConfig(n_entries=2), bc.buddy_cache_init,
              bc.buddy_cache_access, _indexed_buddy_cache_access, 2,
              bc.NODES_PER_WORD),
    "hwsw16": (bc.BuddyCacheConfig(n_entries=16), bc.buddy_cache_init,
               bc.buddy_cache_access, _indexed_buddy_cache_access, 16,
               bc.NODES_PER_WORD),
}


def _node_stream(rng, shape, slots, per_slot):
    """Nodes over ~3x as many words (or lines) as the cache holds, so words
    repeat and evict each other, with a quarter of them invalid (-1)."""
    nodes = rng.integers(0, 3 * slots * per_slot, size=shape)
    return np.where(rng.random(shape) < 0.25, -1, nodes).astype(np.int32)


def _assert_trees_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("start", ["empty", "full"])
@pytest.mark.parametrize("cache", sorted(_CACHES))
def test_dense_access_matches_indexed(cache, start):
    """The one-hot access functions give the indexed formulation's state,
    hit and DRAM bytes after every access of a random stream."""
    cfg, init, dense, indexed, slots, per_slot = _CACHES[cache]
    rng = np.random.default_rng(sum(map(ord, cache + start)))
    st = init(cfg)
    if start == "full":  # one distinct word (or line) in every slot
        fill = jnp.arange(slots, dtype=jnp.int32) * per_slot
        st, _ = lax.scan(lambda s, n: (indexed(cfg, s, n)[0], None), st, fill)
        assert int(jnp.min(st.tags)) >= 0
    nodes = jnp.asarray(_node_stream(rng, (400,), slots, per_slot))

    def run(access):
        return jax.jit(lambda s, ns: lax.scan(
            lambda c, n: (lambda o: (o[0], o[1:]))(access(cfg, c, n)),
            s, ns))(st, nodes)

    _assert_trees_equal(run(dense), run(indexed))


@pytest.mark.parametrize("kind", ["sw", "hwsw"])
def test_dense_cache_pass_matches_indexed_on_a_fleet(kind, monkeypatch):
    """`simulate_traces` and `system._cache_pass` under the fleet's two
    vmaps: the dense access functions give the indexed ones' final state
    and per-op hits, misses and DRAM bytes, over two rounds (the second
    starts from the first's warm, full caches)."""
    R, C, T = 2, 3, 4
    cfg = sysm.SystemConfig(kind=kind, heap_bytes=1 << 18, num_threads=T)
    L = cfg.trace_len
    rng = np.random.default_rng(7)
    slots = (cfg.bc.n_entries if kind == "hwsw" else cfg.sw_buf.n_lines)
    per_slot = bc.NODES_PER_WORD * (1 if kind == "hwsw"
                                    else cfg.sw_buf.line_words)
    rounds = []
    for _ in range(2):
        # a random subset of the 2T ops reaches the backend, in random order
        backend = rng.random((R, C, 2 * T)) < 0.6
        bpos = np.where(backend, np.argsort(rng.random((R, C, 2 * T)), -1), -1)
        traces = _node_stream(rng, (R, C, 2 * T, L), slots, per_slot)
        traces = np.where(backend[..., None], traces, -1)
        rounds.append((jnp.asarray(bpos, jnp.int32), jnp.asarray(traces)))
    cache0 = jax.vmap(jax.vmap(lambda _: cfg.cache_init()))(jnp.zeros((R, C)))

    def run():
        fleet = jax.jit(jax.vmap(jax.vmap(
            lambda c, b, t: sysm._cache_pass(cfg, c, b, t))))
        sim = jax.jit(jax.vmap(jax.vmap(
            lambda c, t: bc.simulate_traces(cfg.access_fn, c, t))))
        out, cache = [], cache0
        for bpos, traces in rounds:
            cache, stats = fleet(cache, bpos, traces)
            out += [cache, stats, sim(cache0, traces)]
        return out

    dense = run()
    assert int(jnp.min(dense[0].tags)) >= 0, "round 1 left a cache not full"
    monkeypatch.setattr(sysm, "sw_buffer_access", _indexed_sw_buffer_access)
    monkeypatch.setattr(sysm, "buddy_cache_access",
                        _indexed_buddy_cache_access)
    _assert_trees_equal(dense, run())


# ------------------------------------------------------------------ cost model
def test_queuing_latency():
    cost = cm.DPUCost()
    path = jnp.array([2, 0, 2, -1], jnp.int32)
    pos = jnp.array([0, -1, 1, -1], jnp.int32)
    svc = jnp.array([100.0, 0.0, 200.0, 0.0], jnp.float32)
    lat = cm.round_latency_cyc(cost, path, pos, svc)
    assert float(lat[0]) == 100.0            # first backend user: no wait
    assert float(lat[1]) == cost.cyc_front_hit
    assert float(lat[2]) == 100.0 + 200.0    # waits for user 0
    assert float(lat[3]) == 0.0


# ---------------------------------------------------------------- design space
def test_fig5_qualitative_shape():
    sweep = ds.sweep(n_cores_list=(1, 64, 512))
    red = sweep["pim_meta_pim_exec"]
    # winner: flat in N
    assert abs(red[512]["total"] - red[1]["total"]) / red[1]["total"] < 1e-6
    # all others grow with N and are worse at 512 cores
    for s in ds.STRATEGIES:
        if s == "pim_meta_pim_exec":
            continue
        assert sweep[s][512]["total"] > sweep[s][1]["total"]
        assert sweep[s][512]["total"] > red[512]["total"], s
    # metadata movers are transfer-dominated at 512 cores (Fig 5b)
    for s in ("host_meta_pim_exec", "pim_meta_host_exec"):
        assert sweep[s][512]["xfer"] > sweep[s][512]["exec"] * 0.5, s


# --------------------------------------------------------------------- system
@pytest.mark.parametrize("kind", sysm.KINDS)
def test_system_round_runs(kind):
    cfg = sysm.SystemConfig(kind=kind, heap_bytes=1 << 18, num_threads=4)
    st = sysm.system_init(cfg)
    st, ptrs, info = jax.jit(lambda s, z: sysm.malloc_round(cfg, s, z))(
        st, jnp.array([32, 256, 2048, 8192], jnp.int32)
    )
    assert all(int(p) >= 0 for p in ptrs)
    assert np.all(np.asarray(info.latency_cyc) >= 0)
    st, info_f = jax.jit(lambda s, p: sysm.free_round(cfg, s, p))(st, ptrs)
    assert np.all(np.asarray(info_f.latency_cyc) >= 0)


def test_hierarchy_beats_strawman_small_sizes():
    lat = {}
    for kind in ("strawman", "sw"):
        cfg = sysm.SystemConfig(kind=kind, heap_bytes=1 << 20, num_threads=4)
        st = sysm.system_init(cfg)
        sz = jnp.full((16, 4), 32, jnp.int32)
        st, ptrs, infos = jax.jit(
            lambda s, z: sysm.run_alloc_rounds(cfg, s, z)
        )(st, sz)
        lat[kind] = float(np.mean(np.asarray(infos.latency_cyc)))
    assert lat["strawman"] > 10 * lat["sw"]


def test_hwsw_reduces_dram_traffic():
    """Fig 16(c): fine-grained buddy cache moves fewer DRAM bytes than SW."""
    traffic = {}
    for kind in ("sw", "hwsw"):
        cfg = sysm.SystemConfig(kind=kind, heap_bytes=1 << 20, num_threads=4)
        st = sysm.system_init(cfg)
        sz = jnp.full((32, 4), 4096, jnp.int32)  # all backend ops
        st, ptrs, infos = jax.jit(
            lambda s, z: sysm.run_alloc_rounds(cfg, s, z)
        )(st, sz)
        traffic[kind] = int(np.sum(np.asarray(infos.dram_bytes)))
    assert traffic["hwsw"] < traffic["sw"]


def test_contention_fluctuation():
    """Fig 7: multi-thread straw-man latency fluctuates via busy-wait."""
    cfg = sysm.SystemConfig(kind="strawman", heap_bytes=1 << 20, num_threads=8)
    st = sysm.system_init(cfg)
    sz = jnp.full((8, 8), 256, jnp.int32)
    st, ptrs, infos = jax.jit(lambda s, z: sysm.run_alloc_rounds(cfg, s, z))(st, sz)
    lat = np.asarray(infos.latency_cyc)
    spread = lat.max(axis=1) / np.maximum(lat.min(axis=1), 1)
    assert spread.max() > 3  # later mutex waiters see multiples of the service time
