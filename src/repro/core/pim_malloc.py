"""PIM-malloc-SW: the paper's hierarchical per-core allocator (Section 4.1).

Two levels, exactly as in Fig 8:
  frontend  — per-thread *thread caches*: NC size classes (16 B … 2 KB),
              LIFO freelists of sub-blocks carved from `block_bytes` (4 KB)
              blocks. O(1) pop/push, no mutex (vectorized across threads).
  backend   — shared buddy allocator over the per-core heap with minimum
              grain `block_bytes` (tree depth 20 → 13 for 32 MB), protected
              by a mutex (modeled: `lax.scan` serializes backend users and
              the cost model charges queuing/busy-wait).

The state is a fixed-shape pytree so a whole PIM system is just
`vmap(malloc)` across cores, and a mesh of devices is `shard_map` of that —
the paper's winning *PIM-Metadata/PIM-Executed* design point: allocator
metadata lives in (and never leaves) each core's local memory.

Workflow cases of Fig 9:
  case 1  thread-cache hit     path=0
  case 2  thread-cache miss    path=1 (refill 4 KB from buddy, carve, pop)
  case 3  bypass (> 2 KB)      path=2 (buddy alloc, rounded pow2 >= 4 KB)
  fail    heap exhausted       path=3
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax.numpy as jnp
from jax import lax

from . import buddy
from .buddy import BuddyConfig, BuddyState, ilog2, next_pow2

INVALID = jnp.int32(-1)


@dataclasses.dataclass(frozen=True)
class PimMallocConfig:
    heap_bytes: int = 32 * 1024 * 1024
    num_threads: int = 16          # paper: up to 24 tasklets per DPU
    size_classes: tuple = (16, 32, 64, 128, 256, 512, 1024, 2048)
    block_bytes: int = 4096        # thread-cache refill unit == buddy min grain
    cap: int = 1024                # freelist capacity per (thread, class)
    max_gc: int = 8                # full blocks merged back per gc() pass

    def __post_init__(self):
        assert all(s & (s - 1) == 0 for s in self.size_classes)
        assert tuple(sorted(self.size_classes)) == tuple(self.size_classes)
        assert self.block_bytes > max(self.size_classes)
        assert self.cap >= self.block_bytes // min(self.size_classes)

    @property
    def nc(self) -> int:
        return len(self.size_classes)

    @property
    def nb(self) -> int:  # number of 4 KB blocks in the heap
        return self.heap_bytes // self.block_bytes

    @property
    def max_sub(self) -> int:  # sub-blocks per block for the smallest class
        return self.block_bytes // min(self.size_classes)

    @property
    def buddy_cfg(self) -> BuddyConfig:
        return BuddyConfig(heap_bytes=self.heap_bytes, min_block=self.block_bytes)

    @property
    def log2_min_class(self) -> int:
        return min(self.size_classes).bit_length() - 1

    @property
    def max_class(self) -> int:
        return max(self.size_classes)


class Stats(NamedTuple):
    front_hits: jnp.ndarray
    front_misses: jnp.ndarray
    bypass: jnp.ndarray
    fails: jnp.ndarray
    frees_small: jnp.ndarray
    frees_big: jnp.ndarray
    dropped_frees: jnp.ndarray
    gc_blocks: jnp.ndarray


def _zero_stats() -> Stats:
    z = jnp.int32(0)
    return Stats(z, z, z, z, z, z, z, z)


class PimMallocState(NamedTuple):
    buddy: BuddyState
    counts: jnp.ndarray      # int32[T, NC] free sub-blocks per freelist
    stacks: jnp.ndarray      # int32[T, NC, CAP] LIFO freelists (byte offsets)
    block_cls: jnp.ndarray   # int32[NB] owning size class, -1 if not cache-owned
    block_free: jnp.ndarray  # int32[NB] free sub-blocks currently cached, per block
    big_log2: jnp.ndarray    # int32[NB] log2(size) for bypass allocs at base block, -1
    stats: Stats


class MallocEvent(NamedTuple):
    """Per-thread record for the cost model / cache sims."""

    path: jnp.ndarray         # int32[T]: 0 hit / 1 refill / 2 bypass / 3 fail / -1 idle
    backend_pos: jnp.ndarray  # int32[T]: serialization order at backend, -1 if none
    levels_down: jnp.ndarray  # int32[T]
    levels_up: jnp.ndarray    # int32[T]
    trace: jnp.ndarray        # int32[T, trace_len] buddy-tree nodes touched


class FreeEvent(NamedTuple):
    path: jnp.ndarray         # int32[T]: 0 small / 1 big / 2 dropped / -1 idle
    backend_pos: jnp.ndarray
    levels_up: jnp.ndarray
    trace: jnp.ndarray


class ReallocMeta(NamedTuple):
    """Size-class analysis of live pointers for pim_realloc (all [T])."""

    valid_old: jnp.ndarray  # bool — ptr maps to tracked metadata
    in_place: jnp.ndarray   # bool — rounded size class unchanged
    old_bytes: jnp.ndarray  # int32 rounded bytes of the live block (0 if invalid)
    new_bytes: jnp.ndarray  # int32 rounded bytes of the requested size


class ReallocEvent(NamedTuple):
    malloc: "MallocEvent"     # alloc phase of moved reallocs
    free: "FreeEvent"         # release phase of moved reallocs
    in_place: jnp.ndarray     # bool[T] served without touching the heap
    moved: jnp.ndarray        # bool[T] relocated (new ptr, old freed)
    copy_bytes: jnp.ndarray   # int32[T] payload DMA'd old -> new block


def _class_of(cfg: PimMallocConfig, sizes):
    rounded = next_pow2(jnp.maximum(sizes, min(cfg.size_classes)))
    return jnp.clip(ilog2(rounded) - cfg.log2_min_class, 0, cfg.nc - 1)


def init(cfg: PimMallocConfig, prepopulate: bool = True) -> PimMallocState:
    """initAllocator(): reset metadata; optionally pre-carve one 4 KB block per
    freelist (paper: done once by thread 0)."""
    st = PimMallocState(
        buddy=buddy.init(cfg.buddy_cfg),
        counts=jnp.zeros((cfg.num_threads, cfg.nc), jnp.int32),
        stacks=jnp.full((cfg.num_threads, cfg.nc, cfg.cap), INVALID, jnp.int32),
        block_cls=jnp.full((cfg.nb,), INVALID, jnp.int32),
        block_free=jnp.zeros((cfg.nb,), jnp.int32),
        big_log2=jnp.full((cfg.nb,), INVALID, jnp.int32),
        stats=_zero_stats(),
    )
    if not prepopulate:
        return st

    class_sizes = jnp.array(cfg.size_classes, jnp.int32)

    def carve(st: PimMallocState, tc):
        t, c = tc
        bstate, off, _ = buddy.alloc(cfg.buddy_cfg, st.buddy, jnp.int32(cfg.block_bytes))
        ok = off >= 0
        csize = class_sizes[c]
        sub = cfg.block_bytes // csize
        offs = off + jnp.arange(cfg.max_sub, dtype=jnp.int32) * csize
        row = jnp.where(jnp.arange(cfg.max_sub) < sub, offs, INVALID)
        stacks = st.stacks.at[t, c, : cfg.max_sub].set(
            jnp.where(ok, row, st.stacks[t, c, : cfg.max_sub])
        )
        counts = st.counts.at[t, c].set(jnp.where(ok, sub, st.counts[t, c]))
        b = off // cfg.block_bytes
        bsafe = jnp.where(ok, b, 0)
        block_cls = st.block_cls.at[bsafe].set(jnp.where(ok, c, st.block_cls[bsafe]))
        block_free = st.block_free.at[bsafe].set(jnp.where(ok, sub, st.block_free[bsafe]))
        return (
            st._replace(buddy=bstate, stacks=stacks, counts=counts,
                        block_cls=block_cls, block_free=block_free),
            None,
        )

    t_idx, c_idx = jnp.meshgrid(
        jnp.arange(cfg.num_threads, dtype=jnp.int32),
        jnp.arange(cfg.nc, dtype=jnp.int32),
        indexing="ij",
    )
    st, _ = lax.scan(carve, st, (t_idx.ravel(), c_idx.ravel()))
    return st


def malloc(cfg: PimMallocConfig, st: PimMallocState, sizes, active=None):
    """Service one batched request round: sizes int32[T] per thread.

    Returns (state, ptrs int32[T], MallocEvent). ptr = -1 for failed/idle.
    """
    T = cfg.num_threads
    assert sizes.shape == (T,)
    if active is None:
        active = jnp.ones((T,), bool)
    class_sizes = jnp.array(cfg.size_classes, jnp.int32)
    t_idx = jnp.arange(T, dtype=jnp.int32)
    tlen = cfg.buddy_cfg.trace_len

    # ---------------- Phase A: vectorized thread-cache pops (case 1) --------
    # sizes beyond the heap fail outright (and must not reach next_pow2,
    # which wraps int32 for sizes > 2^30 — e.g. calloc overflow sentinels).
    too_big = active & (sizes > cfg.heap_bytes)
    small = active & (sizes <= cfg.max_class) & (sizes > 0)
    c = _class_of(cfg, sizes)
    cnt = st.counts[t_idx, c]
    hit = small & (cnt > 0)
    pos = jnp.maximum(cnt - 1, 0)
    ptr_a = st.stacks[t_idx, c, pos]
    counts = st.counts.at[t_idx, c].add(jnp.where(hit, -1, 0))
    blk_a = jnp.where(hit, ptr_a // cfg.block_bytes, cfg.nb)  # nb -> dropped
    block_free = st.block_free.at[blk_a].add(-1, mode="drop")

    # ---------------- Phase B: serialized backend (cases 2 & 3, mutex) ------
    refill = small & ~hit
    bypass = active & (sizes > cfg.max_class) & ~too_big
    need = refill | bypass

    # `stacks` stays out of the carry: nothing here reads it and thread t
    # writes only its own row, so the rows are carved after the scan. A row
    # write in the step, at a per-core index under vmap, is a gather and a
    # scatter that XLA expands into serial loops over the cores.
    def step(carry, x):
        bstate, counts, block_cls, block_free, big_log2, border = carry
        t, need_t, refill_t, bypass_t, size_t, c_t = x
        alloc_size = jnp.where(
            bypass_t, next_pow2(jnp.maximum(size_t, cfg.block_bytes)),
            jnp.int32(cfg.block_bytes),
        )
        bstate2, off, bev = buddy.alloc(cfg.buddy_cfg, bstate, alloc_size)
        ok = need_t & (off >= 0)
        # commit buddy mutation only if this thread actually used the backend
        bstate = BuddyState(
            longest=jnp.where(need_t, bstate2.longest, bstate.longest)
        )
        b = jnp.where(off >= 0, off // cfg.block_bytes, 0)

        # -- refill: carve block into sub-blocks, push all, pop top ----------
        csize = class_sizes[c_t]
        sub = cfg.block_bytes // csize
        do_refill = refill_t & ok
        counts = counts.at[t, c_t].set(
            jnp.where(do_refill, sub - 1, counts[t, c_t])
        )
        block_cls = block_cls.at[b].set(jnp.where(do_refill, c_t, block_cls[b]))
        block_free = block_free.at[b].set(jnp.where(do_refill, sub - 1, block_free[b]))
        ptr_refill = off + (sub - 1) * csize

        # -- bypass: record size for ptr-only pimFree -------------------------
        do_bypass = bypass_t & ok
        big_log2 = big_log2.at[b].set(
            jnp.where(do_bypass, ilog2(alloc_size), big_log2[b])
        )

        ptr = jnp.where(do_refill, ptr_refill, jnp.where(do_bypass, off, INVALID))
        bpos = jnp.where(need_t, border, INVALID)
        border = border + need_t.astype(jnp.int32)
        ev = (
            jnp.where(need_t, bev.levels_down, 0),
            jnp.where(need_t, bev.levels_up, 0),
            jnp.where(need_t, bev.trace, jnp.full((tlen,), INVALID, jnp.int32)),
            bpos,
            ok,
        )
        return (bstate, counts, block_cls, block_free, big_log2, border), (
            ptr, off, ev)

    carry = (st.buddy, counts, st.block_cls, block_free, st.big_log2,
             jnp.int32(0))
    xs = (t_idx, need, refill, bypass, sizes, c)
    carry, (ptr_b, off_b, (lv_down, lv_up, trace, bpos, ok_b)) = lax.scan(
        step, carry, xs)
    bstate, counts, block_cls, block_free, big_log2, _ = carry
    refilled = refill & ok_b

    # carve each refilled block into its thread's class-c freelist at once
    slot = jnp.arange(cfg.cap, dtype=jnp.int32)
    csize = class_sizes[c][:, None]
    rows = jnp.where(slot < cfg.block_bytes // csize,
                     off_b[:, None] + slot * csize, INVALID)       # [T, CAP]
    write = refilled[:, None] & (c[:, None] == jnp.arange(cfg.nc))  # [T, NC]
    stacks = jnp.where(write[:, :, None] & (slot < cfg.max_sub),
                       rows[:, None, :], st.stacks)

    ptrs = jnp.where(hit, ptr_a, ptr_b)
    path = jnp.where(
        hit, 0,
        jnp.where(refilled, 1,
                  jnp.where(bypass & ok_b, 2,
                            jnp.where(need | too_big, 3, INVALID))),
    ).astype(jnp.int32)

    stats = st.stats._replace(
        front_hits=st.stats.front_hits + jnp.sum(hit),
        front_misses=st.stats.front_misses + jnp.sum(refill),
        bypass=st.stats.bypass + jnp.sum(bypass),
        fails=st.stats.fails + jnp.sum((need & ~ok_b) | too_big),
    )
    new_st = PimMallocState(
        buddy=bstate, counts=counts, stacks=stacks, block_cls=block_cls,
        block_free=block_free, big_log2=big_log2, stats=stats,
    )
    ev = MallocEvent(path=path, backend_pos=bpos, levels_down=lv_down,
                     levels_up=lv_up, trace=trace)
    return new_st, ptrs, ev


def free(cfg: PimMallocConfig, st: PimMallocState, ptrs, active=None):
    """pimFree(ptr) batched over threads: size recovered from block metadata.

    C-like misuse accounting: a NULL free (ptr == -1) is a benign no-op
    (path -1); any other requested free that cannot be served — negative
    garbage, out-of-heap offsets, pointers in untracked blocks, double
    frees of bypass blocks, or a freelist at capacity — is *dropped*
    (path 2) and counted in `Stats.dropped_frees` so workload replays
    surface allocator misuse. (Detection is block-granularity: a double
    free of a sub-block whose 4 KB block is still cache-owned cannot be
    distinguished from a legitimate free and is served as a push.)
    """
    T = cfg.num_threads
    assert ptrs.shape == (T,)
    if active is None:
        active = jnp.ones((T,), bool)
    requested = active & (ptrs != -1)
    active = requested & (ptrs >= 0) & (ptrs < cfg.heap_bytes)
    t_idx = jnp.arange(T, dtype=jnp.int32)
    tlen = cfg.buddy_cfg.trace_len

    b = jnp.where(active, ptrs // cfg.block_bytes, 0)
    cls = st.block_cls[b]
    small = active & (cls >= 0)
    big = active & (cls < 0) & (st.big_log2[b] >= 0) & (ptrs % cfg.block_bytes == 0)

    # -------- small frees: vectorized push to the calling thread's list -----
    csel = jnp.maximum(cls, 0)
    pos = st.counts[t_idx, csel]
    overflow = small & (pos >= cfg.cap)
    push = small & ~overflow
    possafe = jnp.minimum(pos, cfg.cap - 1)
    stacks = st.stacks.at[t_idx, csel, possafe].set(
        jnp.where(push, ptrs, st.stacks[t_idx, csel, possafe])
    )
    counts = st.counts.at[t_idx, csel].add(jnp.where(push, 1, 0))
    block_free = st.block_free.at[jnp.where(push, b, cfg.nb)].add(1, mode="drop")

    # -------- big frees: serialized buddy frees (mutex) ---------------------
    def step(carry, x):
        bstate, big_log2, border = carry
        big_t, ptr_t, b_t = x
        size = jnp.int32(1) << jnp.maximum(big_log2[b_t], 0)
        bstate2, bev = buddy.free(cfg.buddy_cfg, bstate, ptr_t, size)
        bstate = BuddyState(
            longest=jnp.where(big_t, bstate2.longest, bstate.longest)
        )
        big_log2 = big_log2.at[b_t].set(jnp.where(big_t, INVALID, big_log2[b_t]))
        bpos = jnp.where(big_t, border, INVALID)
        border = border + big_t.astype(jnp.int32)
        ev = (
            jnp.where(big_t, bev.levels_up, 0),
            jnp.where(big_t, bev.trace, jnp.full((tlen,), INVALID, jnp.int32)),
            bpos,
        )
        return (bstate, big_log2, border), ev

    carry = (st.buddy, st.big_log2, jnp.int32(0))
    carry, (lv_up, trace, bpos) = lax.scan(step, carry, (big, ptrs, b))
    bstate, big_log2, _ = carry

    dropped = requested & ~push & ~big
    path = jnp.where(push, 0, jnp.where(big, 1, jnp.where(dropped, 2, INVALID)))
    stats = st.stats._replace(
        frees_small=st.stats.frees_small + jnp.sum(push),
        frees_big=st.stats.frees_big + jnp.sum(big),
        dropped_frees=st.stats.dropped_frees + jnp.sum(dropped),
    )
    new_st = PimMallocState(
        buddy=bstate, counts=counts, stacks=stacks, block_cls=st.block_cls,
        block_free=block_free, big_log2=big_log2, stats=stats,
    )
    ev = FreeEvent(path=path.astype(jnp.int32), backend_pos=bpos,
                   levels_up=lv_up, trace=trace)
    return new_st, ev


def realloc_meta(cfg: PimMallocConfig, st: PimMallocState, ptrs, sizes) -> ReallocMeta:
    """Classify live pointers against requested sizes (no state change).

    A pointer is small iff its block is thread-cache-owned (block_cls >= 0),
    big iff it is the base of a recorded bypass allocation. Grow/shrink stays
    in place iff the rounded size class (small) or rounded pow2 (big) is
    unchanged — exactly when the paper's allocator can return the same block.
    """
    valid = (ptrs >= 0) & (ptrs < cfg.heap_bytes)
    b = jnp.where(valid, ptrs // cfg.block_bytes, 0)
    cls = st.block_cls[b]
    small_old = valid & (cls >= 0)
    big_old = (valid & (cls < 0) & (st.big_log2[b] >= 0)
               & (ptrs % cfg.block_bytes == 0))
    class_sizes = jnp.array(cfg.size_classes, jnp.int32)
    old_bytes = jnp.where(
        small_old, class_sizes[jnp.maximum(cls, 0)],
        jnp.where(big_old, jnp.int32(1) << jnp.maximum(st.big_log2[b], 0), 0),
    )
    new_small = sizes <= cfg.max_class
    new_bytes = jnp.where(
        new_small, class_sizes[_class_of(cfg, sizes)],
        next_pow2(jnp.maximum(sizes, cfg.block_bytes)),
    )
    in_place = ((small_old & new_small) | (big_old & ~new_small)) & (
        new_bytes == old_bytes)
    return ReallocMeta(valid_old=small_old | big_old, in_place=in_place,
                       old_bytes=old_bytes, new_bytes=new_bytes)


def realloc(cfg: PimMallocConfig, st: PimMallocState, ptrs, sizes, active=None):
    """pimRealloc(ptr, size) batched over threads.

    Semantics mirror C realloc on the PIM heap:
      * same rounded size class      -> grow/shrink in place (ptr unchanged)
      * class changed                -> malloc new + copy payload + free old
      * ptr invalid/untracked        -> plain malloc(size)
      * size <= 0 with live ptr      -> free(ptr), returns -1
      * relocation malloc fails      -> -1, old block left intact

    Returns (state, new_ptrs int32[T], ReallocEvent).
    """
    T = cfg.num_threads
    assert ptrs.shape == (T,)
    if active is None:
        active = jnp.ones((T,), bool)
    sizes = jnp.asarray(sizes, jnp.int32)

    meta = realloc_meta(cfg, st, ptrs, sizes)
    live = active & (sizes > 0)
    in_place = live & meta.in_place
    moved = live & ~meta.in_place
    free_as_zero = active & (sizes <= 0) & (ptrs >= 0)

    st, mptrs, mev = malloc(cfg, st, jnp.where(moved, sizes, 0), moved)
    ok_new = mptrs >= 0
    f_active = (moved & meta.valid_old & ok_new) | free_as_zero
    st, fev = free(cfg, st, jnp.where(f_active, ptrs, INVALID), f_active)

    new_ptrs = jnp.where(in_place, ptrs,
                         jnp.where(moved & ok_new, mptrs, INVALID))
    copy_bytes = jnp.where(moved & ok_new & meta.valid_old,
                           jnp.minimum(meta.old_bytes, meta.new_bytes), 0)
    ev = ReallocEvent(malloc=mev, free=fev, in_place=in_place,
                      moved=moved & ok_new, copy_bytes=copy_bytes)
    return st, new_ptrs, ev


def calloc(cfg: PimMallocConfig, st: PimMallocState, nmemb, elem_sizes,
           active=None):
    """pimCalloc(nmemb, size): malloc(nmemb * size) rounded to a size class.

    The returned block is zero-initialized by construction here (the heap is
    functional metadata; payload zero-fill is charged by the system cost
    model). An nmemb * size product that overflows int32 becomes a failing
    (heap-sized) request instead of wrapping small.
    """
    T = cfg.num_threads
    nmemb = jnp.asarray(nmemb, jnp.int32)
    elem_sizes = jnp.asarray(elem_sizes, jnp.int32)
    assert nmemb.shape == (T,)
    if active is None:
        active = jnp.ones((T,), bool)
    total = total_calloc_bytes(nmemb, elem_sizes)
    return malloc(cfg, st, total, active & (total > 0))


def total_calloc_bytes(nmemb, elem_sizes):
    """nmemb * size in int32 with the C-calloc overflow guard: a wrapping
    product maps to INT32_MAX (which no heap can satisfy), never to a small
    positive size."""
    nmemb = jnp.asarray(nmemb, jnp.int32)
    elem_sizes = jnp.asarray(elem_sizes, jnp.int32)
    prod = nmemb * elem_sizes
    exact = (prod > 0) & (prod // jnp.maximum(elem_sizes, 1) == nmemb)
    requested = (nmemb > 0) & (elem_sizes > 0)
    return jnp.where(requested,
                     jnp.where(exact, prod, jnp.int32(jnp.iinfo(jnp.int32).max)),
                     0)


def gc(cfg: PimMallocConfig, st: PimMallocState):
    """Merge fully-free 4 KB blocks back into the buddy (paper Fig 8(b)).

    Processes up to cfg.max_gc blocks per call; leftover full blocks are
    handled by later calls (bounded work per step keeps shapes static).
    """
    class_sizes = jnp.array(cfg.size_classes, jnp.int32)
    sub_of = cfg.block_bytes // jnp.maximum(class_sizes[jnp.maximum(st.block_cls, 0)], 1)
    full = (st.block_cls >= 0) & (st.block_free == sub_of)
    score = jnp.where(full, 1, 0)
    _, cand = lax.top_k(score, cfg.max_gc)
    cand_ok = full[cand]

    def step(carry, x):
        bstate, counts, stacks, block_cls, block_free = carry
        b, ok = x
        c = jnp.maximum(block_cls[b], 0)
        # remove this block's sub-blocks from every thread's class-c freelist
        T, NC, CAP = stacks.shape
        pos = jnp.arange(CAP)
        valid = pos[None, :] < counts[:, c][:, None]          # [T, CAP]
        rows = stacks[:, c, :]                                 # [T, CAP]
        is_b = valid & (rows // cfg.block_bytes == b) & ok
        keep = ~is_b
        # stable-compact kept valid entries to the front (False sorts first)
        key = ~(keep & valid)
        order = jnp.argsort(key, axis=1, stable=True)
        compacted = jnp.take_along_axis(rows, order, axis=1)
        newcnt = jnp.sum(keep & valid, axis=1).astype(jnp.int32)
        compacted = jnp.where(pos[None, :] < newcnt[:, None], compacted, INVALID)
        apply = ok
        stacks = stacks.at[:, c, :].set(jnp.where(apply, compacted, rows))
        counts = counts.at[:, c].set(jnp.where(apply, newcnt, counts[:, c]))
        bstate2, _ = buddy.free(
            cfg.buddy_cfg, bstate, b * cfg.block_bytes, jnp.int32(cfg.block_bytes)
        )
        bstate = BuddyState(longest=jnp.where(apply, bstate2.longest, bstate.longest))
        block_cls = block_cls.at[b].set(jnp.where(apply, INVALID, block_cls[b]))
        block_free = block_free.at[b].set(jnp.where(apply, 0, block_free[b]))
        return (bstate, counts, stacks, block_cls, block_free), apply

    carry = (st.buddy, st.counts, st.stacks, st.block_cls, st.block_free)
    carry, applied = lax.scan(step, carry, (cand, cand_ok))
    bstate, counts, stacks, block_cls, block_free = carry
    stats = st.stats._replace(gc_blocks=st.stats.gc_blocks + jnp.sum(applied))
    return st._replace(
        buddy=bstate, counts=counts, stacks=stacks, block_cls=block_cls,
        block_free=block_free, stats=stats,
    )
