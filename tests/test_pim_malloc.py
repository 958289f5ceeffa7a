"""Tests for the hierarchical PIM-malloc-SW allocator (thread cache + buddy)."""
import functools
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import hypothesis_or_skip

given, settings, st = hypothesis_or_skip()

from repro.core import buddy
from repro.core import pim_malloc as pm
from repro.core.oracle import PyPimMalloc

CFG = pm.PimMallocConfig(heap_bytes=1 << 20, num_threads=4)


@pytest.fixture(scope="module")
def ops():
    return (
        jax.jit(lambda s, z: pm.malloc(CFG, s, z)),
        jax.jit(lambda s, p: pm.free(CFG, s, p)),
        jax.jit(lambda s: pm.gc(CFG, s)),
    )


def _assert_state_equal(st_, py, where=""):
    assert py.buddy.longest == [int(x) for x in st_.buddy.longest], where
    for t in range(CFG.num_threads):
        for c in range(CFG.nc):
            n = int(st_.counts[t][c])
            assert py.counts[t][c] == n, (where, t, c)
            assert py.stacks[t][c] == [int(x) for x in st_.stacks[t][c][:n]], (where, t, c)


def test_prepopulate_matches_paper():
    """init pre-carves one 4 KB block per freelist (paper Sec 4.1)."""
    st_ = pm.init(CFG)
    for t in range(CFG.num_threads):
        for c, csize in enumerate(CFG.size_classes):
            assert int(st_.counts[t][c]) == CFG.block_bytes // csize


def test_hit_is_frontend_path(ops):
    malloc, _, _ = ops
    st_ = pm.init(CFG)
    st_, ptrs, ev = malloc(st_, jnp.full((4,), 128, jnp.int32))
    assert all(int(p) == 0 for p in ev.path)  # all thread-cache hits
    assert all(int(p) >= 0 for p in ptrs)
    assert int(st_.stats.front_hits) == 4


def test_bypass_path(ops):
    malloc, free, _ = ops
    st_ = pm.init(CFG)
    st_, ptrs, ev = malloc(st_, jnp.full((4,), 8192, jnp.int32))
    assert all(int(p) == 2 for p in ev.path)  # all bypass
    assert all(int(x) % 8192 == 0 for x in ptrs)
    # ptr-only free works for bypass blocks
    st_, fev = free(st_, ptrs)
    assert all(int(p) == 1 for p in fev.path)


def test_miss_refills_from_buddy(ops):
    malloc, _, _ = ops
    st_ = pm.init(CFG)
    # 2048-class prepopulated with 2 sub-blocks; third alloc misses
    sizes = jnp.full((4,), 2048, jnp.int32)
    st_, _, ev0 = malloc(st_, sizes)
    st_, _, ev1 = malloc(st_, sizes)
    st_, ptrs, ev2 = malloc(st_, sizes)
    assert all(int(p) == 0 for p in ev1.path)
    assert all(int(p) == 1 for p in ev2.path)  # refill
    assert all(int(x) >= 0 for x in ptrs)


def test_backend_serialization_order(ops):
    malloc, _, _ = ops
    st_ = pm.init(CFG)
    st_, _, ev = malloc(st_, jnp.array([8192, 64, 16384, 4096], jnp.int32))
    # threads 0, 2, 3 bypass -> backend positions 0, 1, 2 in thread order
    assert [int(x) for x in ev.backend_pos] == [0, -1, 1, 2]


def test_gc_merges_full_blocks(ops):
    malloc, free, gc = ops
    st_ = pm.init(CFG)
    # exhaust + free the 1024-class, then gc twice
    st_, p1, _ = malloc(st_, jnp.full((4,), 1024, jnp.int32))
    st_, p2, _ = malloc(st_, jnp.full((4,), 1024, jnp.int32))
    st_, p3, _ = malloc(st_, jnp.full((4,), 1024, jnp.int32))
    for p in (p1, p2, p3):
        st_, _ = free(st_, p)
    st_ = gc(st_)
    st_ = gc(st_)
    assert int(st_.stats.gc_blocks) >= 4


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000))
def test_property_matches_oracle(seed):
    cfg = pm.PimMallocConfig(heap_bytes=1 << 18, num_threads=4)
    st_ = pm.init(cfg)
    py = PyPimMalloc(heap_bytes=1 << 18, num_threads=4)
    jm = jax.jit(lambda s, z: pm.malloc(cfg, s, z))
    jf = jax.jit(lambda s, p: pm.free(cfg, s, p))
    jg = jax.jit(lambda s: pm.gc(cfg, s))
    rng = random.Random(seed)
    live = [[] for _ in range(4)]
    for i in range(30):
        op = rng.random()
        if op < 0.55:
            sizes = [rng.choice([16, 100, 256, 2048, 3000, 8192]) for _ in range(4)]
            st_, ptrs, ev = jm(st_, jnp.array(sizes, jnp.int32))
            pptrs, ppaths = py.malloc(sizes)
            assert [int(x) for x in ptrs] == pptrs, (seed, i)
            assert [int(x) for x in ev.path] == ppaths, (seed, i)
            for t in range(4):
                if pptrs[t] >= 0:
                    live[t].append(pptrs[t])
        elif op < 0.9:
            ptrs = [live[t].pop(rng.randrange(len(live[t])))
                    if live[t] and rng.random() < 0.8 else -1 for t in range(4)]
            st_, _ = jf(st_, jnp.array(ptrs, jnp.int32))
            py.free(ptrs)
        else:
            st_ = jg(st_)
            py.gc()
    _assert_state_equal(st_, py, f"seed={seed}")
    sd = {k: int(v) for k, v in st_.stats._asdict().items()}
    assert sd["dropped_frees"] == py.stats["dropped"]
    assert sd["gc_blocks"] == py.stats["gc_blocks"]


def test_no_overlap_across_threads(ops):
    """Live pointers from different threads never overlap (heap safety)."""
    malloc, free, _ = ops
    st_ = pm.init(CFG)
    rng = random.Random(3)
    live = []  # (ptr, rounded_size)
    for _ in range(25):
        sizes = [rng.choice([16, 64, 256, 2048, 8192]) for _ in range(4)]
        st_, ptrs, _ = malloc(st_, jnp.array(sizes, jnp.int32))
        for t in range(4):
            p = int(ptrs[t])
            if p >= 0:
                rs = max(1 << (sizes[t] - 1).bit_length(), 16)
                live.append((p, rs))
        ivs = sorted((p, p + s) for p, s in live)
        for (a0, a1), (b0, b1) in zip(ivs, ivs[1:]):
            assert a1 <= b0


def test_api_allocator_roundtrip():
    from repro.core.api import initAllocator

    a = initAllocator(1 << 18, num_threads=4)
    p1 = a.pimMalloc(100)
    p2 = a.pimMalloc(100)
    assert p1 >= 0 and p2 >= 0 and p1 != p2
    a.pimFree(p1)
    a.pimFree(p2)
    assert a.stats["front_hits"] == 2
    assert a.stats["frees_small"] == 2


# Rounds of a batch of cores: (sizes, active), each [K cores][T threads].
K = 3
_IDLE = ([[64] * 4] * K, [[False] * 4] * K)   # the fleet's all-false mask


def _round(sizes):
    return sizes, [[z > 0 for z in row] for row in sizes]


def _exhausted_rounds():
    """Each core holds 4 blocks. Threads 0-2 refill class 2048 >> k and
    drain it to count 0 (stale rows stay), then all four refill: thread 0
    takes the last block and threads 1-3 fail on the exhausted heap."""
    sizes = [2048 >> k for k in range(K)]
    drains = [CFG.block_bytes // z - 1 for z in sizes]   # hits after a refill
    rounds = [_round([[z] * 3 + [0] for z in sizes])]
    for d in range(max(drains)):
        rounds.append(_round([[z] * 3 + [0] if d < n else [0] * 4
                              for z, n in zip(sizes, drains)]))
    return rounds + [_round([[z] * 4 for z in sizes])]


CLASSES = PyPimMalloc().cfg["classes"]
REFILL_CASES = {
    # every thread of a core refills one class; the class differs by core
    "same_class": (1 << 18, False, [_round([[16 << k] * 4 for k in range(K)])] * 2),
    # each thread refills its own class, then others with a bypass between
    "mixed_classes": (1 << 18, False, [
        _round([[CLASSES[(t + k) % 8] for t in range(4)] for k in range(K)]),
        _round([[CLASSES[(t + k + 4) % 8] for t in range(3)] + [8192]
                for k in range(K)])]),
    # prepopulated lists of 2048 >> k drain and refill on different rounds
    "prepopulated": (1 << 18, True,
                     [_round([[2048 >> k] * 4 for k in range(K)])] * 9),
    "exhausted": (1 << 14, False, _exhausted_rounds()),
}


def _expected_stacks(cfg, stacks, sizes, ptrs, paths):
    """The freelists a malloc round must leave on one core: a thread that
    refilled (path 1) gets its new block carved into stacks[t, c, :max_sub],
    and every other entry keeps its old value, stale ones included."""
    out = np.array(stacks)
    for t, (z, ptr, path) in enumerate(zip(sizes, ptrs, paths)):
        if path == 1:
            c = next(i for i, s in enumerate(cfg.size_classes) if z <= s)
            csize = cfg.size_classes[c]
            sub = cfg.block_bytes // csize
            base = ptr - (sub - 1) * csize
            out[t, c, :cfg.max_sub] = [base + i * csize if i < sub else -1
                                       for i in range(cfg.max_sub)]
    return out


@pytest.mark.parametrize("case", sorted(REFILL_CASES))
def test_vmapped_refills_match_oracle(case):
    """malloc vmapped over cores, as the fleet runs it, against one oracle
    per core: pointers, every MallocEvent field (buddy fields replayed
    through `buddy.alloc` in backend order), the full `stacks` array and
    the rest of the state, round by round, ending with an idle round."""
    heap, prepopulate, rounds = REFILL_CASES[case]
    cfg = pm.PimMallocConfig(heap_bytes=heap, num_threads=4)
    st_ = jax.tree.map(lambda x: jnp.stack([x] * K), pm.init(cfg, prepopulate))
    pys = [PyPimMalloc(heap_bytes=heap, num_threads=4, prepopulate=prepopulate)
           for _ in range(K)]
    step = jax.jit(jax.vmap(lambda s, z, a: pm.malloc(cfg, s, z, a)))
    balloc = jax.jit(functools.partial(buddy.alloc, cfg.buddy_cfg))
    tlen = cfg.buddy_cfg.trace_len
    seen = set()
    for r, (sizes, active) in enumerate(rounds + [_IDLE]):
        prev = jax.tree.map(np.asarray, st_)
        st_, ptrs, ev = step(st_, jnp.array(sizes, jnp.int32),
                             jnp.array(active))
        for k, py in enumerate(pys):
            where = (case, r, k)
            pptrs, ppaths = py.malloc(sizes[k], active[k])
            assert np.asarray(ptrs[k]).tolist() == pptrs, where
            assert np.asarray(ev.path[k]).tolist() == ppaths, where
            # the backend in thread order, from the round's first state
            bst = buddy.BuddyState(longest=jnp.asarray(prev.buddy.longest[k]))
            want = {"backend_pos": [-1] * 4, "levels_down": [0] * 4,
                    "levels_up": [0] * 4, "trace": [[-1] * tlen] * 4}
            for pos, t in enumerate(t for t in range(4) if ppaths[t] > 0):
                z = sizes[k][t]
                bsize = (cfg.block_bytes if z <= cfg.max_class
                         else max(1 << (z - 1).bit_length(), cfg.block_bytes))
                bst, _, bev = balloc(bst, jnp.int32(bsize))
                want["backend_pos"][t] = pos
                want["levels_down"][t] = int(bev.levels_down)
                want["levels_up"][t] = int(bev.levels_up)
                want["trace"][t] = np.asarray(bev.trace).tolist()
            for name, value in want.items():
                assert np.asarray(getattr(ev, name)[k]).tolist() == value, (
                    where, name)
            # state
            got = jax.tree.map(lambda x: np.asarray(x[k]), st_)
            np.testing.assert_array_equal(
                got.stacks, _expected_stacks(cfg, prev.stacks[k], sizes[k],
                                             pptrs, ppaths), err_msg=str(where))
            assert got.buddy.longest.tolist() == py.buddy.longest, where
            assert got.counts.tolist() == py.counts, where
            for t in range(4):
                for c in range(cfg.nc):
                    n = py.counts[t][c]
                    assert got.stacks[t, c, :n].tolist() == py.stacks[t][c], where
            nb = range(cfg.nb)
            assert got.block_cls.tolist() == [py.block_cls.get(b, -1) for b in nb]
            assert got.block_free.tolist() == [py.block_free.get(b, 0) for b in nb]
            assert got.big_log2.tolist() == [py.big_log2.get(b, -1) for b in nb]
            for name in ("front_hits", "front_misses", "bypass", "fails"):
                assert int(getattr(got.stats, name)) == py.stats[name], (
                    where, name)
            seen.update(ppaths)
    # refills committed on every case, and refills failed where the heap runs out
    assert 1 in seen and (3 in seen) == (case == "exhausted"), seen
