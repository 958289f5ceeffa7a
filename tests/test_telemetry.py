"""Heap-telemetry invariants: conservation, high-water mark, fragmentation.

The core property (ISSUE acceptance): after ANY request stream, on every
backend,

    live_bytes + buddy free bytes + cached thread-cache bytes == heap_bytes

with live_bytes/hwm advanced incrementally in `system._price_round` and the
other two terms recomputed independently from the metadata snapshot
(`repro.core.telemetry` / `buddy.free_bytes`).
"""
import random

import jax.numpy as jnp
import numpy as np
import pytest

from conftest import hypothesis_or_skip

from repro.core import buddy, heap, system as sysm, telemetry

given, settings, st_ = hypothesis_or_skip()

T = 4
HEAP = 1 << 18


def _cfg(kind):
    return sysm.SystemConfig(kind=kind, heap_bytes=HEAP, num_threads=T)


def _drive_random_stream(kind, seed, rounds=10):
    """Random mixed-op rounds (incl. misuse-free streams); asserts the
    conservation law and hwm monotonicity after every round."""
    rng = random.Random(seed)
    cfg = _cfg(kind)
    st = heap.init(cfg)
    live = [[] for _ in range(T)]
    hwm_prev = 0
    for _ in range(rounds):
        roll = rng.random()
        if roll < 0.45:
            req = heap.malloc_request(jnp.array(
                [rng.choice([16, 100, 256, 2048, 3000, 8192])
                 for _ in range(T)], jnp.int32))
        elif roll < 0.7:
            ptrs = [live[t].pop(rng.randrange(len(live[t])))
                    if live[t] and rng.random() < 0.85 else -1
                    for t in range(T)]
            req = heap.free_request(jnp.array(ptrs, jnp.int32))
        elif roll < 0.9:
            ptrs = [live[t].pop(rng.randrange(len(live[t])))
                    if live[t] and rng.random() < 0.85 else -1
                    for t in range(T)]
            req = heap.realloc_request(
                jnp.array(ptrs, jnp.int32),
                jnp.array([rng.choice([0, 16, 100, 300, 3000, 8192])
                           for _ in range(T)], jnp.int32))
        else:
            req = heap.calloc_request(
                jnp.array([rng.randint(0, 64) for _ in range(T)], jnp.int32),
                jnp.array([rng.choice([0, 16, 40]) for _ in range(T)],
                          jnp.int32))
        st, resp = heap.step(cfg, st, req)
        for t in range(T):
            if int(resp.ptr[t]) >= 0:
                live[t].append(int(resp.ptr[t]))
        snap = telemetry.snapshot(cfg, st)
        assert snap["conservation_residual"] == 0, (kind, seed, snap)
        assert snap["hwm_bytes"] >= snap["live_bytes"]
        assert snap["hwm_bytes"] >= hwm_prev          # monotone
        hwm_prev = snap["hwm_bytes"]
        assert snap["free_bytes"] >= 0 and snap["cached_frontend_bytes"] >= 0
    return st, cfg


@pytest.mark.parametrize("kind", sysm.KINDS)
@pytest.mark.parametrize("seed", [0, 1])
def test_conservation_on_random_streams(kind, seed):
    _drive_random_stream(kind, seed)


@given(st_.integers(min_value=0, max_value=2 ** 31 - 1))
@settings(max_examples=8, deadline=None)
def test_prop_conservation_any_stream(seed):
    """Property: the telemetry invariant holds on arbitrary streams for the
    software-buffer (sw) and buddy-cache (hwsw) backends alike."""
    _drive_random_stream("sw", seed, rounds=6)
    _drive_random_stream("hwsw", seed, rounds=6)


def test_histogram_matches_buddy_free_bytes():
    """The per-level maximal-free histogram sums exactly to the buddy's
    independent free-bytes accounting, as fragmentation develops."""
    cfg = _cfg("sw")
    st = heap.init(cfg)
    bcfg = cfg.pm.buddy_cfg
    for sizes in ([8192] * T, [16384, 0, 8192, 0], [65536, 0, 0, 0]):
        st, resp = heap.step(cfg, st, heap.malloc_request(
            jnp.array(sizes, jnp.int32)))
        hist = telemetry.free_block_histogram(bcfg, st.alloc.buddy.longest)
        got = telemetry.free_bytes_from_histogram(bcfg, hist)
        want = int(buddy.free_bytes(bcfg, st.alloc.buddy))
        assert got == want
        # free half of what we just got -> holes -> histogram must follow
        st, _ = heap.step(cfg, st, heap.free_request(
            jnp.where(jnp.arange(T) % 2 == 0, resp.ptr, -1)))
        hist = telemetry.free_block_histogram(bcfg, st.alloc.buddy.longest)
        assert (telemetry.free_bytes_from_histogram(bcfg, hist)
                == int(buddy.free_bytes(bcfg, st.alloc.buddy)))


@pytest.mark.parametrize("kind", ["sw", "hwsw"])
def test_conservation_when_moved_realloc_free_is_dropped(kind):
    """A moved realloc whose old-block free overflows a full freelist
    (dropped, path 2) leaks the block: live_bytes must keep it, or the
    conservation law breaks."""
    import repro.core.pim_malloc as pm
    pmc = pm.PimMallocConfig(heap_bytes=HEAP, num_threads=T,
                             size_classes=(512, 1024, 2048), cap=8)
    cfg = sysm.SystemConfig(kind=kind, heap_bytes=HEAP, num_threads=T,
                            pm=pmc)
    st = heap.init(cfg)
    # t0 and t1 each pop a 512 B sub-block (counts 7), then t0 pushes t1's
    # block back onto ITS OWN list -> t0's 512-class stack is full (cap=8)
    st, r0 = heap.step(cfg, st, heap.malloc_request(
        jnp.array([512, 512, 0, 0], jnp.int32)))
    st, _ = heap.step(cfg, st, heap.free_request(
        jnp.array([int(r0.ptr[1]), -1, -1, -1], jnp.int32)))
    # moved realloc of t0's block: the vacated 512 B free overflows -> drop
    dropped0 = int(st.alloc.stats.dropped_frees)
    st, r1 = heap.step(cfg, st, heap.realloc_request(
        r0.ptr, jnp.array([8192, 0, 0, 0], jnp.int32),
        active=jnp.array([True, False, False, False])))
    assert bool(r1.moved[0]) and int(r1.ptr[0]) >= 0
    assert int(st.alloc.stats.dropped_frees) == dropped0 + 1
    snap = telemetry.snapshot(cfg, st)
    assert snap["conservation_residual"] == 0, snap
    # the leaked 512 B stays live alongside the new 8 KB block
    assert snap["live_bytes"] >= 8192 + 512


def test_hwm_tracks_peak_not_current():
    cfg = _cfg("sw")
    st = heap.init(cfg)
    st, r = heap.step(cfg, st, heap.malloc_request(
        jnp.full((T,), 8192, jnp.int32)))
    peak = int(st.telem.live_bytes)
    st, _ = heap.step(cfg, st, heap.free_request(r.ptr))
    assert int(st.telem.live_bytes) == 0
    assert int(st.telem.hwm_bytes) == peak == 4 * 8192


def test_multicore_states_carry_independent_telemetry():
    cfg = _cfg("sw")
    mch = heap.MultiCoreHeap(cfg, num_cores=3)
    sizes = jnp.zeros((3, T), jnp.int32).at[0].set(
        jnp.full((T,), 2048, jnp.int32))
    mch.malloc(sizes)
    live = np.asarray(mch.state.telem.live_bytes)
    assert live.shape == (3,)
    assert live[0] == 4 * 2048 and (live[1:] == 0).all()
