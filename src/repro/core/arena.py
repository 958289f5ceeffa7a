"""Layered frontend/backend allocator: bump-pointer arena over the pim stack.

The paper's §2 design space is about *where allocator metadata lives and who
manages it*; this module adds the two missing frontend points as a thin,
composable layer over the existing backend instead of a fifth fork of the
step function:

  arena     one shared bump-pointer region (half the heap, carved out of
            the buddy at init). Small allocs (<= max size class) are served
            by bumping a pointer — O(1), no freelist, no buddy mutex; the
            shared bump add is an atomic, so same-round contenders
            serialize for ``cyc_bump_atomic`` cycles each. Frees hole-mark
            (space is NOT reclaimed); the new ``OP_EPOCH_RESET`` protocol
            op retires the whole epoch in O(1) — the EAlloc Temp /
            round-scoped allocation pattern.
  tlregion  the same frontend with the region pre-split per thread: each
            thread bumps its own private region and resets its own region,
            so the fast path has no cross-thread atomic at all (the TLS
            allocator-class point).

Everything the arena does not own — big allocs, arena exhaustion
(spill-to-buddy), non-arena pointers — is forwarded verbatim to the full
hwsw stack (freelists + buddy + metadata cache).

Layout and conservation: the region occupies ``[0, arena_bytes)`` (the
leftmost-descent buddy hands a pristine heap's first ``heap_bytes // 2``
request offset 0 deterministically) and is never visible to the backend's
metadata, so the conservation law holds with the arena's unallocated +
holed bytes counted as *cached frontend* bytes (see
`repro.core.telemetry.frontend_cached_bytes`).

Epoch-reset semantics (mirrored by the PyArena oracle, the sanitizer's
shadow epochs, and the ``trace_lint`` rule): a reset applies at *round
start* — same-round frees of arena pointers see the cleared map and drop,
and no recorded pointer may be referenced across a reset round.
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
from jax import lax

from . import buddy, cost_model, pim_malloc
from .heap import (OP_CALLOC, OP_EPOCH_RESET, OP_FREE, OP_MALLOC, OP_NOOP,
                   OP_REALLOC, AllocRequest, AllocResponse)
from .pim_malloc import INVALID

# Arena placements are tracked at allocation start granules, like the
# sanitizer's shadow map: every size class is a multiple of 16 B.
GRANULE = 16


def arena_bytes(cfg) -> int:
    """Static size of the region carved for the bump frontend: half the
    heap, which keeps the backend's buddy tree usable for big/spill work."""
    ab = cfg.heap_bytes // 2
    assert ab % cfg.pm.block_bytes == 0, \
        f"arena region {ab} must be block-aligned ({cfg.pm.block_bytes})"
    return ab


# ---------------------------------------------------------------------------
# arena frontend primitives (the bump-pointer fast path fused ahead of the
# buddy mutex phase). Pure jnp so they trace inside jitted step bodies and
# stay visible to the pimcheck verifier passes.
# ---------------------------------------------------------------------------
def arena_bump_shared(bump, cand, gneed, limit: int):
    """Shared-arena bump allocation: contenders serialize in thread order.

    bump: int32[] granules consumed; cand: bool[T] attempts this round;
    gneed: int32[T] granules wanted. A failed fit does NOT consume space —
    a later, smaller request can still be served (hence the scan, which is
    also the modeled serialization point of the shared atomic add).
    Returns (new_bump, start_granule int32[T] (-1 on fail), served bool[T]).
    """

    def body(b, x):
        want, need = x
        fits = want & (b + need <= limit)
        g0 = jnp.where(fits, b, jnp.int32(-1))
        return b + jnp.where(fits, need, 0), (g0, fits)

    bump, (g0, served) = lax.scan(body, bump, (cand, gneed))
    return bump, g0, served


def arena_bump_tl(bump, cand, gneed, region_gran: int):
    """Per-thread-region bump allocation: fully vectorized, no cross-thread
    serialization (the tlregion fast path). ``bump`` is int32[T], each entry
    an offset inside thread t's private region of ``region_gran`` granules.
    Returns (new_bump, absolute start granule int32[T] (-1 on fail), served).
    """
    T = bump.shape[0]
    fits = cand & (bump + gneed <= region_gran)
    base = jnp.arange(T, dtype=jnp.int32) * region_gran
    g0 = jnp.where(fits, base + bump, jnp.int32(-1))
    return bump + jnp.where(fits, gneed, 0), g0, fits


def arena_mark(cls_map, g, cls, on):
    """Record an arena placement: cls_map[g] = cls where ``on`` (scatter with
    an out-of-bounds park slot for masked threads, drop-guarded)."""
    n = cls_map.shape[0]
    idx = jnp.where(on, jnp.clip(g, 0, n - 1), jnp.int32(n))
    return cls_map.at[idx].set(jnp.where(on, cls, jnp.int32(-1)), mode="drop")


def arena_hole(cls_map, g, on):
    """Retire an arena block: cls_map[g] = -1 where ``on`` (bump space is
    not reclaimed until the next epoch reset — holes stay holes)."""
    n = cls_map.shape[0]
    idx = jnp.where(on, jnp.clip(g, 0, n - 1), jnp.int32(n))
    return cls_map.at[idx].set(jnp.int32(-1), mode="drop")


def arena_region_reset(cls_map, class_sizes, region_mask):
    """Bulk epoch reset over ``region_mask`` granules: clears every placement
    in the region and returns (new_cls_map, freed_bytes) where freed_bytes
    is the rounded occupancy being retired (the telemetry delta)."""
    nc = class_sizes.shape[0]
    live = region_mask & (cls_map >= 0)
    freed = jnp.sum(jnp.where(
        live, class_sizes[jnp.clip(cls_map, 0, nc - 1)], 0))
    return jnp.where(region_mask, jnp.int32(-1), cls_map), freed


def n_granules(cfg) -> int:
    return arena_bytes(cfg) // GRANULE


def region_granules(cfg) -> int:
    """Granules per thread region (``tlregion``) or the whole arena."""
    n = n_granules(cfg)
    if cfg.kind != "tlregion":
        return n
    assert n % cfg.num_threads == 0, \
        f"{n} granules not splittable across {cfg.num_threads} threads"
    return n // cfg.num_threads


class ArenaSystemState(NamedTuple):
    """Backend state + the arena frontend's placement map.

    The leading (alloc, cache, telem) triple mirrors `system.SystemState`,
    so telemetry snapshots, replay reports, and `api.HeapClient.stats`
    read this state unchanged (same contract as `SanitizerState`).
    """

    alloc: object            # PimMallocState (the spill backend)
    cache: object            # BuddyCacheState (hwsw metadata path)
    telem: object            # system.HeapTelemetry
    cls_map: jnp.ndarray     # int32[n_gran] size-class index at start granule, -1
    bump: jnp.ndarray        # int32[1] (arena) | int32[T] (tlregion) granules used
    epoch: jnp.ndarray       # int32[] completed-reset counter


def init_state(cfg) -> ArenaSystemState:
    """Carve the arena region out of a pristine backend heap.

    The freelists start empty (spills refill them on demand) and the region
    is deliberately NOT recorded in the backend's block metadata: a
    forwarded free of an arena-range pointer is untracked there and drops,
    which is exactly the misuse accounting the other kinds apply.
    """
    from .system import telemetry_init

    pmc = cfg.pm
    ab = arena_bytes(cfg)
    inner = pim_malloc.init(pmc, prepopulate=False)
    bst, _off, _ev = buddy.alloc(pmc.buddy_cfg, inner.buddy, jnp.int32(ab))
    inner = inner._replace(buddy=bst)
    n_bump = cfg.num_threads if cfg.kind == "tlregion" else 1
    region_granules(cfg)  # validate the per-thread split early
    return ArenaSystemState(
        alloc=inner, cache=cfg.cache_init(), telem=telemetry_init(),
        cls_map=jnp.full((n_granules(cfg),), -1, jnp.int32),
        bump=jnp.zeros((n_bump,), jnp.int32),
        epoch=jnp.int32(0),
    )


def arena_live_bytes(cfg, cls_map) -> jnp.ndarray:
    """Rounded bytes currently placed in the arena (start granules only)."""
    class_sizes = jnp.array(cfg.pm.size_classes, jnp.int32)
    nc = cfg.pm.nc
    return jnp.sum(jnp.where(
        cls_map >= 0, class_sizes[jnp.clip(cls_map, 0, nc - 1)], 0))


def step(cfg, st: ArenaSystemState, req: AllocRequest, inner_step):
    """One layered protocol round: arena pass, then the forwarded backend
    round, then the merge.

    ``inner_step`` is the spill backend (`system._step_pim`). Phases:

      0. EPOCH_RESET applies at round start (shared: any resetting thread
         clears the whole arena, idempotently; tlregion: each resetting
         thread clears only its own region).
      1. Ownership classification against the post-reset map; bump
         allocation for small MALLOC/CALLOC and small relocation targets
         (failed fits do not consume space).
      2. Forward everything unowned/unserved to the backend.
      3. Merge: hole-mark retired arena blocks, fold arena counters into
         the shared Stats, price arena-served ops with the bump-path
         cycles, and advance telemetry with the arena's byte deltas.
    """
    from .system import SystemState, _advance_telemetry

    pmc = cfg.pm
    dpu = cfg.dpu
    tl = cfg.kind == "tlregion"
    ab = arena_bytes(cfg)
    n_gran = n_granules(cfg)
    region_gran = region_granules(cfg)
    class_sizes = jnp.array(pmc.size_classes, jnp.int32)

    op, size, ptr = req.op, req.size, req.ptr
    is_alloc = (op == OP_MALLOC) | (op == OP_CALLOC)
    is_re = op == OP_REALLOC
    is_free = op == OP_FREE
    is_reset = op == OP_EPOCH_RESET

    # ---- phase 0: epoch reset at round start ------------------------------
    if tl:
        gran_owner = jnp.arange(n_gran, dtype=jnp.int32) // region_gran
        reset_gran = is_reset[jnp.clip(gran_owner, 0, cfg.num_threads - 1)]
        bump = jnp.where(is_reset, 0, st.bump)
    else:
        any_reset = jnp.any(is_reset)
        reset_gran = jnp.broadcast_to(any_reset, (n_gran,))
        bump = jnp.where(any_reset, 0, st.bump)
    cls_map, reset_freed = arena_region_reset(
        st.cls_map, class_sizes, reset_gran)
    epoch = st.epoch + jnp.any(is_reset).astype(jnp.int32)

    # ---- ownership classification (post-reset map) ------------------------
    in_arena = (ptr >= 0) & (ptr < ab) & (ptr % GRANULE == 0)
    g_old = jnp.clip(jnp.where(in_arena, ptr // GRANULE, 0), 0, n_gran - 1)
    owned = in_arena & (cls_map[g_old] >= 0)
    old_cls = jnp.where(owned, cls_map[g_old], -1)
    old_bytes = jnp.where(
        owned, class_sizes[jnp.clip(old_cls, 0, pmc.nc - 1)], 0)

    small = (size > 0) & (size <= pmc.max_class)
    cls = pim_malloc._class_of(pmc, size)
    cls_bytes = class_sizes[cls]
    gneed = cls_bytes // GRANULE

    re_free0 = is_re & (size <= 0) & (ptr >= 0)
    arena_free = (is_free | re_free0) & owned
    re_live = is_re & (size > 0)
    re_arena = re_live & owned
    re_inplace = re_arena & small & (cls == old_cls)
    re_move = re_arena & ~(small & (cls == old_cls))

    # ---- phase 1: bump allocation -----------------------------------------
    plain_small = is_alloc & small
    bump_cand = plain_small | (re_move & small)
    if tl:
        bump, g_new, served = arena_bump_tl(
            bump, bump_cand, gneed, region_gran)
        bump_wait = jnp.zeros_like(size, jnp.float32)
    else:
        b, g_new, served = arena_bump_shared(
            bump[0], bump_cand, gneed, n_gran)
        bump = jnp.reshape(b, (1,))
        # every attempter serializes on the shared atomic add, served or not
        rank = jnp.cumsum(bump_cand.astype(jnp.int32)) - bump_cand
        bump_wait = jnp.where(
            bump_cand, rank.astype(jnp.float32) * dpu.cyc_bump_atomic, 0.0)

    arena_alloc = plain_small & served
    re_move_bump = re_move & small & served
    move_to_inner = re_move & ~re_move_bump   # big new size, or arena full

    # ---- phase 2: forwarded backend round ---------------------------------
    consumed = arena_alloc | arena_free | re_inplace | re_move_bump | is_reset
    inner_req = AllocRequest(
        op=jnp.where(move_to_inner, OP_MALLOC,
                     jnp.where(consumed, OP_NOOP, op)).astype(jnp.int32),
        size=jnp.where(consumed & ~move_to_inner, 0, size),
        ptr=jnp.where(consumed | move_to_inner, INVALID, ptr),
    )
    inner_st = SystemState(alloc=st.alloc, cache=st.cache, telem=st.telem)
    inner_st, r = inner_step(cfg, inner_st, inner_req)

    # ---- phase 3: merge ----------------------------------------------------
    move_ok = re_move_bump | (move_to_inner & r.ok)
    cls_map = arena_mark(cls_map, g_new, cls,
                                  arena_alloc | re_move_bump)
    cls_map = arena_hole(cls_map, g_old, arena_free | move_ok)

    new_ptr = g_new * GRANULE
    passthrough = ~consumed & ~move_to_inner

    # pricing: bump-path cycles for arena-served ops, the same DMA pricing
    # as the backend for calloc zero-fill and relocation copies
    new_rounded = jnp.where(
        small, cls_bytes,
        buddy.next_pow2(jnp.maximum(size, pmc.block_bytes)))
    copy_bytes = jnp.minimum(old_bytes, new_rounded)
    zero_cyc = jnp.where((op == OP_CALLOC) & arena_alloc,
                         cost_model.mram_access_cyc(dpu, size), 0.0)
    lat = jnp.where(passthrough, r.latency_cyc, 0.0)
    lat = lat + jnp.where(arena_alloc, dpu.cyc_bump + bump_wait + zero_cyc,
                          0.0)
    lat = lat + jnp.where(
        re_move_bump,
        dpu.cyc_bump + bump_wait + cost_model.mram_access_cyc(dpu, copy_bytes),
        0.0)
    lat = lat + jnp.where(
        move_to_inner,
        r.latency_cyc + jnp.where(
            r.ok, cost_model.mram_access_cyc(dpu, copy_bytes), 0.0),
        0.0)
    lat = lat + jnp.where(re_inplace, jnp.float32(dpu.cyc_front_hit), 0.0)
    lat = lat + jnp.where(arena_free, jnp.float32(dpu.cyc_front_push), 0.0)
    lat = lat + jnp.where(is_reset, jnp.float32(dpu.cyc_epoch_reset), 0.0)

    arena_ok = arena_alloc | re_move_bump | re_inplace | arena_free | is_reset
    fwd = passthrough | move_to_inner
    resp = AllocResponse(
        ptr=jnp.where(arena_alloc | re_move_bump, new_ptr,
                      jnp.where(re_inplace, ptr,
                                jnp.where(fwd, r.ptr, INVALID))),
        ok=jnp.where(fwd, r.ok, arena_ok),
        path=jnp.where(arena_ok, 0, jnp.where(fwd, r.path, INVALID))
            .astype(jnp.int32),
        moved=re_move_bump | (move_to_inner & r.ok) | (passthrough & r.moved),
        latency_cyc=lat,
        backend_cyc=jnp.where(fwd, r.backend_cyc, 0.0),
        meta_hits=jnp.where(fwd, r.meta_hits, 0),
        meta_misses=jnp.where(fwd, r.meta_misses, 0),
        dram_bytes=jnp.where(fwd, r.dram_bytes, 0),
    )

    # arena-served work folds into the shared Stats so replay reports and
    # the Table-2 facade see one coherent counter set across the layers
    stats = inner_st.alloc.stats
    stats = stats._replace(
        front_hits=stats.front_hits + jnp.sum(arena_alloc | re_move_bump),
        frees_small=stats.frees_small + jnp.sum(arena_free | move_ok),
    )
    arena_alloc_bytes = jnp.sum(
        jnp.where(arena_alloc | re_move_bump, cls_bytes, 0))
    arena_freed_bytes = reset_freed + jnp.sum(
        jnp.where(arena_free | move_ok, old_bytes, 0))
    telem = _advance_telemetry(inner_st.telem, arena_alloc_bytes,
                               arena_freed_bytes)
    new_st = ArenaSystemState(
        alloc=inner_st.alloc._replace(stats=stats), cache=inner_st.cache,
        telem=telem, cls_map=cls_map, bump=bump, epoch=epoch,
    )
    return new_st, resp
