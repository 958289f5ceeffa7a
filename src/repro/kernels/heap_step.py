"""Fused Pallas kernel: one full heap-protocol round per PIM core.

This is the ``pallas`` design point of `repro.core.system`: the entire
`AllocRequest -> AllocResponse` round — per-thread op dispatch (MALLOC /
FREE / REALLOC / CALLOC / NOOP), the per-thread freelist frontend, the
shared buddy backend, and the 16-entry LRU *buddy cache* of metadata words
— executes as ONE `pl.pallas_call` per core instead of a chain of
`lax.scan`s stitched together at the JAX level.

Layout (one kernel invocation = one PIM core, batched across cores by
`vmap` — Pallas turns the batch into a grid dimension on TPU):

  * the whole per-core state pytree (buddy ``longest[]`` tree, freelist
    ``stacks``/``counts``, block metadata, LRU cache tags) is VMEM-resident
    for the duration of the round, generalizing `freelist.py` (LIFO stacks)
    and `buddy_traverse.py` (down/up tree walk) into one fused body;
  * frontend pops/pushes are vectorized across threads (the paper's
    lock-free thread caches);
  * cache misses fall back to the in-kernel buddy traversal, serialized in
    thread order (the paper's backend mutex), carving refilled blocks back
    into the thread cache (refill) and spilling bypass blocks;
  * every buddy-tree node touched passes through an in-kernel LRU word
    cache with hit/miss counters — the paper's HW buddy cache (Section
    4.2), fused with the access path rather than simulated afterwards.

Semantics are bit-identical to the ``hwsw`` reference round in
`repro.core.system._protocol_round` (pinned by tests/test_pallas_heap.py):
pointer sequences, full metadata state, and cache hit/miss counters all
match, so the cost model prices both paths identically and
fig15-style cache sweeps work unchanged on the kernel path.

`protocol_round` is the pure-jnp round body; the kernel loads refs, runs
it, and stores the results, so interpret mode (CPU CI) and the compiled
TPU path share one implementation.

**Batched same-class refill (``batch_refill``, default on).** When every
backend op of a round allocates at block granularity — refills always do,
and bypasses do whenever ``next_pow2(size) == block_bytes`` — leftmost-fit
guarantees the k-th needy thread (in mutex order) carves leaf ``b0 + k``,
where ``b0`` is the leftmost free block. The kernel then serves the whole
round with ONE vectorized run-carve (`buddy_traverse.carve_run`) plus a
bulk freelist refill (`freelist.bulk_refill`) and an exact replay of the
serial threads' LRU-cache access sequence, instead of T serial buddy
walks; rounds with no backend op skip the walk entirely, and rounds with
odd (> block) bypass classes fall back to the serial loop. All three
paths are bitwise-equal — responses, state, cache counters — so the hwsw
contract above is unchanged (pinned by tests/test_pallas_heap.py's
batch-vs-serial parametrization). Disable via
``PIM_MALLOC_BATCH_REFILL=0`` or ``SystemConfig(kernel_batch_refill=
False)`` (the wall-clock bench lane measures both).
"""
from __future__ import annotations

import functools
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.core.buddy import ilog2 as _ilog2
from repro.core.buddy import next_pow2 as _next_pow2
from repro.core.buddy_cache import NODES_PER_WORD
from repro.kernels.buddy_traverse import carve_run, leftmost_block, \
    run_blocks_free
from repro.kernels.freelist import bulk_refill

INVALID = -1  # plain int: Pallas kernels cannot close over array constants


def _access(cache, node):
    """One LRU buddy-cache access (node < 0 = inactive). Mirrors
    `buddy_cache.buddy_cache_access` exactly; returns (cache, hit, miss)."""
    tags, lu, clock = cache
    valid = node >= 0
    word = jnp.maximum(node, 0) // NODES_PER_WORD
    match = tags == word
    hit = valid & jnp.any(match)
    idx = jnp.where(hit, jnp.argmax(match), jnp.argmin(lu))
    tags = tags.at[idx].set(jnp.where(valid, word, tags[idx]))
    lu = lu.at[idx].set(jnp.where(valid, clock, lu[idx]))
    clock = clock + valid.astype(jnp.int32)
    return ((tags, lu, clock), (valid & hit).astype(jnp.int32),
            (valid & ~hit).astype(jnp.int32))


def _buddy_alloc(longest, cache, size, need, *, heap_bytes, block_bytes,
                 depth):
    """Buddy descent/up-walk fused with the LRU metadata cache.

    Equivalent to `buddy.alloc` + trace replay through the cache, with
    state committed only where `need`. Returns
    (longest, cache, off, lvd, lvu, hits, misses); lvd/lvu are unmasked
    (caller zeroes them where ~need, as the event path does).
    """
    size_r = jnp.maximum(_next_pow2(size), block_bytes)
    ok = (size_r <= heap_bytes) & (longest[1] >= size_r)
    cache, hh, mm = _access(cache, jnp.where(need, 1, INVALID))  # root visit

    def down(i, carry):
        node, node_size, lvd, cache, hh, mm = carry
        descend = node_size > size_r
        left = 2 * node
        go_left = longest[left] >= size_r
        node = jnp.where(descend, jnp.where(go_left, left, left + 1), node)
        node_size = jnp.where(descend, node_size >> 1, node_size)
        lvd = lvd + descend.astype(jnp.int32)
        cache, h, m = _access(cache, jnp.where(need & descend, node, INVALID))
        return node, node_size, lvd, cache, hh + h, mm + m

    node, node_size, lvd, cache, hh, mm = lax.fori_loop(
        0, depth, down,
        (jnp.int32(1), jnp.int32(heap_bytes), jnp.int32(0), cache, hh, mm))

    offset = node * node_size - heap_bytes
    longest = longest.at[node].set(jnp.where(need & ok, 0, longest[node]))

    def up(i, carry):
        longest, n, lvu, cache, hh, mm = carry
        parent = n >> 1
        active = ok & (parent >= 1)
        p = jnp.maximum(parent, 1)
        newval = jnp.maximum(longest[2 * p], longest[2 * p + 1])
        longest = longest.at[p].set(
            jnp.where(need & active, newval, longest[p]))
        lvu = lvu + active.astype(jnp.int32)
        cache, h, m = _access(cache, jnp.where(need & active, p, INVALID))
        return longest, jnp.where(active, p, jnp.int32(0)), lvu, cache, \
            hh + h, mm + m

    longest, _, lvu, cache, hh, mm = lax.fori_loop(
        0, depth, up, (longest, node, jnp.int32(0), cache, hh, mm))
    off = jnp.where(ok, offset, INVALID)
    return longest, cache, off, lvd, lvu, hh, mm


def _buddy_free(longest, cache, ptr, lg, big, *, heap_bytes, depth, n_nodes):
    """Buddy coalescing up-walk fused with the cache, committed where `big`.

    `lg` is the recorded log2(size) of the bypass block (from big_log2)."""
    fsize = jnp.int32(1) << jnp.maximum(lg, 0)
    node = jnp.clip((ptr + heap_bytes) // jnp.maximum(fsize, 1), 0,
                    n_nodes - 1)
    valid = big & (ptr >= 0) & (ptr < heap_bytes) & (longest[node] == 0)
    cache, hh, mm = _access(cache, jnp.where(big, node, INVALID))
    longest = longest.at[node].set(jnp.where(valid, fsize, longest[node]))

    def up(i, carry):
        longest, n, nsize, lvu, cache, hh, mm = carry
        parent = n >> 1
        active = valid & (parent >= 1)
        p = jnp.maximum(parent, 1)
        psize = nsize << 1
        l, r = longest[2 * p], longest[2 * p + 1]
        newval = jnp.where((l == nsize) & (r == nsize), psize,
                           jnp.maximum(l, r))
        longest = longest.at[p].set(jnp.where(active, newval, longest[p]))
        lvu = lvu + active.astype(jnp.int32)
        cache, h, m = _access(cache, jnp.where(big & active, p, INVALID))
        return longest, jnp.where(active, p, jnp.int32(0)), psize, lvu, \
            cache, hh + h, mm + m

    longest, _, _, lvu, cache, hh, mm = lax.fori_loop(
        0, depth, up,
        (longest, node, fsize, jnp.int32(0), cache, hh, mm))
    return longest, cache, lvu, hh, mm


class FusedRoundOut(NamedTuple):
    """Kernel outputs: new state leaves + per-thread int32 round records."""

    longest: jnp.ndarray
    counts: jnp.ndarray
    stacks: jnp.ndarray
    block_cls: jnp.ndarray
    block_free: jnp.ndarray
    big_log2: jnp.ndarray
    tags: jnp.ndarray
    last_used: jnp.ndarray
    clock: jnp.ndarray        # int32[1]
    m_ptr: jnp.ndarray        # malloc-phase result pointer (-1 idle/fail)
    m_hit: jnp.ndarray        # thread-cache hit (case 1)
    m_refill: jnp.ndarray     # thread-cache miss -> backend refill (case 2)
    m_bypass: jnp.ndarray     # > max class -> backend bypass (case 3)
    m_okb: jnp.ndarray        # backend op succeeded
    m_bpos: jnp.ndarray       # backend serialization order, -1 = frontend
    m_lvdown: jnp.ndarray
    m_lvup: jnp.ndarray
    m_hits: jnp.ndarray       # buddy-cache hits charged to this thread
    m_miss: jnp.ndarray
    f_push: jnp.ndarray       # free pushed to the caller's freelist
    f_big: jnp.ndarray        # free went to the buddy backend
    f_over: jnp.ndarray       # free dropped (freelist at capacity)
    f_bpos: jnp.ndarray
    f_lvup: jnp.ndarray
    f_hits: jnp.ndarray
    f_miss: jnp.ndarray
    valid_old: jnp.ndarray    # realloc meta: ptr maps to tracked metadata
    in_place: jnp.ndarray     # realloc served in place (live request)
    moved_raw: jnp.ndarray    # realloc needs relocation (pre-alloc-success)
    old_bytes: jnp.ndarray
    new_bytes: jnp.ndarray


def protocol_round(op, size, ptr, longest, counts, stacks, block_cls,
                   block_free, big_log2, tags, last_used, clock,
                   class_sizes=None, *, heap_bytes: int, block_bytes: int,
                   size_classes: tuple,
                   batch_refill: bool = False) -> FusedRoundOut:
    """Pure-jnp body of the fused round (the kernel runs exactly this).

    Mirrors `system._protocol_round` over the pim_malloc primitives: realloc
    size-class analysis on pre-round metadata, one batched malloc phase
    (MALLOC/CALLOC + relocating REALLOCs; vectorized frontend pops, then the
    serial backend), one batched free phase (FREE + vacated realloc blocks),
    with every backend tree touch passing through the in-kernel LRU cache in
    mutex serialization order (malloc phase drains first).

    ``batch_refill=True`` routes block-granularity backend rounds through
    the vectorized run-carve fast path (see module docstring); ``False``
    always runs the original serial walks — both are bitwise-identical.
    """
    T = op.shape[0]
    nb = heap_bytes // block_bytes
    n_nodes = 2 * nb
    depth = nb.bit_length() - 1
    nc = len(size_classes)
    cap = stacks.shape[-1]
    max_sub = block_bytes // min(size_classes)
    max_class = max(size_classes)
    log2_min_class = min(size_classes).bit_length() - 1
    if class_sizes is None:  # direct (non-kernel) calls build it inline
        class_sizes = jnp.array(size_classes, jnp.int32)
    t_idx = jnp.arange(T, dtype=jnp.int32)
    cache = (tags, last_used, clock)

    def class_of(z):
        rounded = _next_pow2(jnp.maximum(z, min(size_classes)))
        return jnp.clip(_ilog2(rounded) - log2_min_class, 0, nc - 1)

    is_alloc = (op == 1) | (op == 4)          # OP_MALLOC | OP_CALLOC
    is_re = op == 3                           # OP_REALLOC
    is_free = op == 2                         # OP_FREE
    # OP_EPOCH_RESET (5) intentionally matches none of the above: backends
    # without an arena frontend answer a reset round as idle (path -1),
    # exactly like `system._protocol_round`, so hwsw/pallas stay bitwise
    # equal on tapes containing resets. The arena/tlregion wrapper consumes
    # op 5 before forwarding, so the fused kernel only ever sees it on
    # raw-backend replays of arena-managed tapes.

    # ---- realloc size-class analysis on the pre-round metadata ------------
    pvalid = (ptr >= 0) & (ptr < heap_bytes)
    pb = jnp.where(pvalid, ptr // block_bytes, 0)
    pcls = block_cls[pb]
    small_old = pvalid & (pcls >= 0)
    big_old = (pvalid & (pcls < 0) & (big_log2[pb] >= 0)
               & (ptr % block_bytes == 0))
    old_bytes = jnp.where(
        small_old, class_sizes[jnp.maximum(pcls, 0)],
        jnp.where(big_old, jnp.int32(1) << jnp.maximum(big_log2[pb], 0), 0))
    new_small = size <= max_class
    new_bytes = jnp.where(new_small, class_sizes[class_of(size)],
                          _next_pow2(jnp.maximum(size, block_bytes)))
    in_place_meta = ((small_old & new_small) | (big_old & ~new_small)) & (
        new_bytes == old_bytes)
    valid_old = small_old | big_old
    re_live = is_re & (size > 0)
    in_place = re_live & in_place_meta
    moved = re_live & ~in_place_meta
    re_free0 = is_re & (size <= 0) & (ptr >= 0)

    # ---- malloc phase A: vectorized thread-cache pops ---------------------
    m_active = (is_alloc & (size > 0)) | moved
    msizes = jnp.where(m_active, size, 0)
    too_big = m_active & (msizes > heap_bytes)
    small = m_active & (msizes <= max_class) & (msizes > 0)
    c = class_of(msizes)
    cnt = counts[t_idx, c]
    hit = small & (cnt > 0)
    pos = jnp.maximum(cnt - 1, 0)
    ptr_a = stacks[t_idx, c, pos]
    counts = counts.at[t_idx, c].add(jnp.where(hit, -1, 0))
    blk_a = jnp.where(hit, ptr_a // block_bytes, nb)
    block_free = block_free.at[blk_a].add(-1, mode="drop")
    refill = small & ~hit
    bypass = m_active & (msizes > max_class) & ~too_big
    need = refill | bypass

    # ---- malloc phase B: serial backend (mutex order = thread order) ------
    z = jnp.zeros((T,), jnp.int32)

    def mstep(t, carry):
        (longest, counts, stacks, block_cls, block_free, big_log2, cache,
         border, m_ptr, m_bpos, m_okb, m_lvd, m_lvu, m_hits, m_miss) = carry
        need_t, refill_t, bypass_t = need[t], refill[t], bypass[t]
        size_t, c_t = msizes[t], c[t]
        alloc_size = jnp.where(
            bypass_t, _next_pow2(jnp.maximum(size_t, block_bytes)),
            jnp.int32(block_bytes))
        longest, cache, off, lvd, lvu, hh, mm = _buddy_alloc(
            longest, cache, alloc_size, need_t, heap_bytes=heap_bytes,
            block_bytes=block_bytes, depth=depth)
        ok = need_t & (off >= 0)

        # refill: carve the block into sub-blocks, push all, pop the top
        csize = class_sizes[c_t]
        sub = block_bytes // csize
        offs = off + jnp.arange(max_sub, dtype=jnp.int32) * csize
        row = jnp.where(jnp.arange(max_sub) < sub, offs, INVALID)
        do_refill = refill_t & ok
        stacks = stacks.at[t, c_t, :max_sub].set(
            jnp.where(do_refill, row, stacks[t, c_t, :max_sub]))
        counts = counts.at[t, c_t].set(
            jnp.where(do_refill, sub - 1, counts[t, c_t]))
        b = jnp.where(off >= 0, off // block_bytes, 0)
        block_cls = block_cls.at[b].set(
            jnp.where(do_refill, c_t, block_cls[b]))
        block_free = block_free.at[b].set(
            jnp.where(do_refill, sub - 1, block_free[b]))
        ptr_refill = off + (sub - 1) * csize

        # bypass: record size so a ptr-only free can recover it
        do_bypass = bypass_t & ok
        big_log2 = big_log2.at[b].set(
            jnp.where(do_bypass, _ilog2(alloc_size), big_log2[b]))

        ptr_t = jnp.where(do_refill, ptr_refill,
                          jnp.where(do_bypass, off, INVALID))
        m_ptr = m_ptr.at[t].set(ptr_t)
        m_bpos = m_bpos.at[t].set(jnp.where(need_t, border, INVALID))
        m_okb = m_okb.at[t].set(ok.astype(jnp.int32))
        m_lvd = m_lvd.at[t].set(jnp.where(need_t, lvd, 0))
        m_lvu = m_lvu.at[t].set(jnp.where(need_t, lvu, 0))
        m_hits = m_hits.at[t].set(hh)
        m_miss = m_miss.at[t].set(mm)
        border = border + need_t.astype(jnp.int32)
        return (longest, counts, stacks, block_cls, block_free, big_log2,
                cache, border, m_ptr, m_bpos, m_okb, m_lvd, m_lvu, m_hits,
                m_miss)

    carry = (longest, counts, stacks, block_cls, block_free, big_log2, cache,
             jnp.int32(0), z - 1, z - 1, z, z, z, z, z)

    def _serial_backend(ca):
        return lax.fori_loop(0, T, mstep, ca)

    if batch_refill:
        need_i = need.astype(jnp.int32)
        n_need = jnp.sum(need_i)
        rank = jnp.cumsum(need_i) - need_i  # mutex order among needy threads
        alloc_size = jnp.where(
            bypass, _next_pow2(jnp.maximum(msizes, block_bytes)),
            jnp.int32(block_bytes))
        all_block = jnp.all(jnp.where(need, alloc_size == block_bytes, True))
        b0 = leftmost_block(longest, heap_bytes=heap_bytes,
                            block_bytes=block_bytes, depth=depth)
        run_ok = run_blocks_free(longest, b0, n_need, window=T,
                                 heap_bytes=heap_bytes,
                                 block_bytes=block_bytes)
        eligible = (all_block & (longest[1] >= block_bytes)
                    & (b0 + n_need <= nb) & run_ok)

        def _skip_backend(ca):
            return ca  # idle defaults in `carry` already match the serial loop

        def _fast_backend(ca):
            (longest, counts, stacks, block_cls, block_free, big_log2, cache,
             border, m_ptr, m_bpos, m_okb, m_lvd, m_lvu, m_hits, m_miss) = ca
            blocks = b0 + rank
            leaf = nb + blocks
            # exact serial LRU access order: per needy thread root + down
            # path + up path (INVALID lanes for non-needy are state no-ops)
            sh_dn = depth - 1 - jnp.arange(depth, dtype=jnp.int32)
            sh_up = 1 + jnp.arange(depth, dtype=jnp.int32)
            seq = jnp.concatenate([
                jnp.full((T, 1), 1, jnp.int32),
                leaf[:, None] >> sh_dn[None, :],
                leaf[:, None] >> sh_up[None, :]], axis=1)
            seq = jnp.where(need[:, None], seq, INVALID).reshape(-1)

            def acc(cache, node):
                cache, h, m = _access(cache, node)
                return cache, (h, m)

            cache, (hh, mm) = lax.scan(acc, cache, seq, unroll=16)
            k = 2 * depth + 1
            m_hits = hh.reshape(T, k).sum(axis=1)
            m_miss = mm.reshape(T, k).sum(axis=1)

            longest = carve_run(longest, b0, n_need, window=T,
                                heap_bytes=heap_bytes,
                                block_bytes=block_bytes)

            off = blocks * block_bytes
            csize = class_sizes[c]
            sub = block_bytes // csize
            offs = (off[:, None]
                    + jnp.arange(max_sub, dtype=jnp.int32)[None, :]
                    * csize[:, None])
            rows = jnp.where(jnp.arange(max_sub)[None, :] < sub[:, None],
                             offs, INVALID)
            stacks, counts = bulk_refill(stacks, counts, refill, c, rows,
                                         sub - 1)
            bsel = jnp.where(refill, blocks, nb)
            block_cls = block_cls.at[bsel].set(c, mode="drop")
            block_free = block_free.at[bsel].set(sub - 1, mode="drop")
            big_log2 = big_log2.at[jnp.where(bypass, blocks, nb)].set(
                _ilog2(jnp.int32(block_bytes)), mode="drop")

            ptr_refill = off + (sub - 1) * csize
            m_ptr = jnp.where(refill, ptr_refill,
                              jnp.where(bypass, off, INVALID))
            m_bpos = jnp.where(need, rank, INVALID)
            lvl = jnp.full((T,), depth, jnp.int32)
            m_lvd = jnp.where(need, lvl, 0)
            m_lvu = jnp.where(need, lvl, 0)
            return (longest, counts, stacks, block_cls, block_free, big_log2,
                    cache, n_need, m_ptr, m_bpos, need_i, m_lvd, m_lvu,
                    m_hits, m_miss)

        branch = jnp.where(n_need == 0, 0,
                           jnp.where(eligible, 1, 2)).astype(jnp.int32)
        out_b = lax.switch(branch,
                           (_skip_backend, _fast_backend, _serial_backend),
                           carry)
    else:
        out_b = _serial_backend(carry)
    (longest, counts, stacks, block_cls, block_free, big_log2, cache, _,
     m_ptr_b, m_bpos, m_okb, m_lvd, m_lvu, m_hits, m_miss) = out_b
    mptrs = jnp.where(hit, ptr_a, m_ptr_b)
    mok = m_active & (mptrs >= 0)

    # ---- free phase: explicit frees + vacated realloc blocks --------------
    f_active = is_free | (moved & valid_old & mok) | re_free0
    fptr = jnp.where(f_active, ptr, INVALID)
    factive = f_active & (fptr >= 0) & (fptr < heap_bytes)
    fb = jnp.where(factive, fptr // block_bytes, 0)
    fcls = block_cls[fb]
    fsmall = factive & (fcls >= 0)
    fbig = (factive & (fcls < 0) & (big_log2[fb] >= 0)
            & (fptr % block_bytes == 0))
    csel = jnp.maximum(fcls, 0)
    fpos = counts[t_idx, csel]
    over = fsmall & (fpos >= cap)
    push = fsmall & ~over
    possafe = jnp.minimum(fpos, cap - 1)
    stacks = stacks.at[t_idx, csel, possafe].set(
        jnp.where(push, fptr, stacks[t_idx, csel, possafe]))
    counts = counts.at[t_idx, csel].add(jnp.where(push, 1, 0))
    block_free = block_free.at[jnp.where(push, fb, nb)].add(1, mode="drop")

    def fstep(t, carry):
        longest, big_log2, cache, border, f_bpos, f_lvu, f_hits, f_miss = \
            carry
        big_t = fbig[t]
        longest, cache, lvu, hh, mm = _buddy_free(
            longest, cache, fptr[t], big_log2[fb[t]], big_t,
            heap_bytes=heap_bytes, depth=depth, n_nodes=n_nodes)
        big_log2 = big_log2.at[fb[t]].set(
            jnp.where(big_t, INVALID, big_log2[fb[t]]))
        f_bpos = f_bpos.at[t].set(jnp.where(big_t, border, INVALID))
        f_lvu = f_lvu.at[t].set(jnp.where(big_t, lvu, 0))
        f_hits = f_hits.at[t].set(hh)
        f_miss = f_miss.at[t].set(mm)
        border = border + big_t.astype(jnp.int32)
        return longest, big_log2, cache, border, f_bpos, f_lvu, f_hits, f_miss

    fcarry = (longest, big_log2, cache, jnp.int32(0), z - 1, z, z, z)
    if batch_refill:
        # a round with no backend free skips the serial coalescing loop:
        # fstep with fbig[t]=False everywhere is a state no-op (INVALID
        # cache accesses, masked writes), so the defaults are bitwise-equal
        out_f = lax.cond(jnp.any(fbig),
                         lambda ca: lax.fori_loop(0, T, fstep, ca),
                         lambda ca: ca, fcarry)
    else:
        out_f = lax.fori_loop(0, T, fstep, fcarry)
    longest, big_log2, cache, _, f_bpos, f_lvu, f_hits, f_miss = out_f

    tags, last_used, clock = cache
    i32 = lambda m: m.astype(jnp.int32)  # noqa: E731
    return FusedRoundOut(
        longest=longest, counts=counts, stacks=stacks, block_cls=block_cls,
        block_free=block_free, big_log2=big_log2, tags=tags,
        last_used=last_used, clock=clock,
        m_ptr=mptrs, m_hit=i32(hit), m_refill=i32(refill),
        m_bypass=i32(bypass), m_okb=m_okb, m_bpos=m_bpos, m_lvdown=m_lvd,
        m_lvup=m_lvu, m_hits=m_hits, m_miss=m_miss,
        f_push=i32(push), f_big=i32(fbig), f_over=i32(over), f_bpos=f_bpos,
        f_lvup=f_lvu, f_hits=f_hits, f_miss=f_miss,
        valid_old=i32(valid_old), in_place=i32(in_place),
        moved_raw=i32(moved), old_bytes=old_bytes, new_bytes=new_bytes)


def _kernel(op_ref, size_ref, ptr_ref, longest_ref, counts_ref, stacks_ref,
            bcls_ref, bfree_ref, blog_ref, tags_ref, lu_ref, clock_ref,
            csizes_ref, *out_refs, heap_bytes: int, block_bytes: int,
            size_classes: tuple, batch_refill: bool):
    out = protocol_round(
        op_ref[...], size_ref[...], ptr_ref[...], longest_ref[...],
        counts_ref[...], stacks_ref[...], bcls_ref[...], bfree_ref[...],
        blog_ref[...], tags_ref[...], lu_ref[...], clock_ref[0],
        csizes_ref[...], heap_bytes=heap_bytes, block_bytes=block_bytes,
        size_classes=size_classes, batch_refill=batch_refill)
    vals = list(out)
    vals[8] = jnp.reshape(vals[8], (1,))  # clock back to its [1] slot
    for ref, val in zip(out_refs, vals):
        ref[...] = val


def _batch_refill_default() -> bool:
    """Env-resolved default for the batched fast path (on unless disabled)."""
    return os.environ.get("PIM_MALLOC_BATCH_REFILL", "1").lower() not in (
        "0", "false", "off")


def fused_heap_step(op, size, ptr, longest, counts, stacks, block_cls,
                    block_free, big_log2, tags, last_used, clock, *,
                    heap_bytes: int, block_bytes: int, size_classes: tuple,
                    interpret: bool | None = None,
                    batch_refill: bool | None = None) -> FusedRoundOut:
    """One fused protocol round for a single core (clock is int32[1]).

    Batch across cores/ranks with `vmap` — Pallas maps the batch onto the
    kernel grid; this is what `heap.MultiCoreHeap` / `heap.ShardedHeap` do
    through the registered ``pallas`` backend. ``batch_refill=None``
    resolves from ``PIM_MALLOC_BATCH_REFILL`` (default on); both settings
    are bitwise-identical, ``False`` merely forces the pre-batching serial
    walk (the wall-clock bench lane's comparison point).

    ``interpret=None`` interprets off the TPU and compiles on it. The
    interpreter never runs on a TPU: asking for it there raises. The
    compiled kernel does not lower for v5e yet (ROADMAP A2).
    """
    from repro.kernels.ops import on_tpu
    if interpret is None:
        interpret = not on_tpu()
    elif interpret and on_tpu():
        raise NotImplementedError(
            "fused_heap_step: interpret mode is refused on a TPU backend; "
            "the compiled kernel does not lower for TPU yet (ROADMAP A2)")
    if batch_refill is None:
        batch_refill = _batch_refill_default()
    return _fused_heap_step(
        op, size, ptr, longest, counts, stacks, block_cls, block_free,
        big_log2, tags, last_used, clock, heap_bytes=heap_bytes,
        block_bytes=block_bytes, size_classes=size_classes,
        interpret=bool(interpret), batch_refill=bool(batch_refill))


@functools.partial(jax.jit, static_argnames=("heap_bytes", "block_bytes",
                                             "size_classes", "interpret",
                                             "batch_refill"))
def _fused_heap_step(op, size, ptr, longest, counts, stacks, block_cls,
                     block_free, big_log2, tags, last_used, clock, *,
                     heap_bytes: int, block_bytes: int, size_classes: tuple,
                     interpret: bool, batch_refill: bool) -> FusedRoundOut:
    T = op.shape[0]
    out_shape = FusedRoundOut(
        longest=jax.ShapeDtypeStruct(longest.shape, jnp.int32),
        counts=jax.ShapeDtypeStruct(counts.shape, jnp.int32),
        stacks=jax.ShapeDtypeStruct(stacks.shape, jnp.int32),
        block_cls=jax.ShapeDtypeStruct(block_cls.shape, jnp.int32),
        block_free=jax.ShapeDtypeStruct(block_free.shape, jnp.int32),
        big_log2=jax.ShapeDtypeStruct(big_log2.shape, jnp.int32),
        tags=jax.ShapeDtypeStruct(tags.shape, jnp.int32),
        last_used=jax.ShapeDtypeStruct(last_used.shape, jnp.int32),
        clock=jax.ShapeDtypeStruct((1,), jnp.int32),
        **{f: jax.ShapeDtypeStruct((T,), jnp.int32)
           for f in FusedRoundOut._fields[9:]})
    kern = functools.partial(_kernel, heap_bytes=heap_bytes,
                             block_bytes=block_bytes,
                             size_classes=tuple(size_classes),
                             batch_refill=batch_refill)
    out = pl.pallas_call(kern, out_shape=list(out_shape),
                         interpret=interpret)(
        op, size, ptr, longest, counts, stacks, block_cls, block_free,
        big_log2, tags, last_used, clock,
        jnp.array(size_classes, jnp.int32))
    return FusedRoundOut(*out)
