"""End-to-end allocator system simulation: the paper's design points.

  strawman : buddy_alloc_PIM_DRAM — single-level buddy over the whole heap,
             min block 32 B (20-level tree for 32 MB), shared mutex, coarse
             SW metadata buffer. (Section 3.2/3.3.)
  sw       : PIM-malloc-SW — per-thread caches + 13-level buddy backend +
             coarse SW metadata buffer. (Section 4.1.)
  hwsw     : PIM-malloc-HW/SW — same frontend/backend, but backend metadata
             served by the 16-entry LRU hardware buddy cache. (Section 4.2.)
  sanitizer: hwsw wrapped in a shadow map + quarantine ring
             (`repro.core.sanitizer`) — turns double-free /
             use-after-free / realloc-after-free / wild pointers into
             deterministic tagged reports. The debugging design point.
  arena    : layered frontend/backend split (`repro.core.arena`): a shared
             bump-pointer arena serves small allocs in O(1) and retires
             whole epochs with one EPOCH_RESET op; everything else spills
             to the full hwsw stack (freelists + buddy). The churn-workload
             design point.
  tlregion : the arena frontend with per-thread regions — no cross-thread
             atomic on the bump fast path (and per-thread epoch resets).

All these kinds serve the `repro.core.heap` request/response protocol: this
module registers one cost-model-instrumented `heap.step` implementation per
kind. A step services one mixed-op round (per-thread MALLOC / FREE /
REALLOC / CALLOC / NOOP), persists metadata-cache state across rounds, and
returns per-thread latencies — including mutex busy-wait for backend users
(Fig 7), payload-copy DMA for relocating reallocs, and zero-fill DMA for
callocs. A whole multi-core PIM system is `vmap` over cores of `heap.step`
(see `heap.MultiCoreHeap` / benchmarks/fig5) and a TPU mesh deployment is
`shard_map` of that (`repro.launch`).

`malloc_round` / `free_round` remain as single-op conveniences; they build
the corresponding protocol request and run the same step.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from . import buddy, buddy_cache, cost_model, heap, pim_malloc
from .buddy import BuddyConfig, BuddyState, ilog2, next_pow2
from .buddy_cache import (BuddyCacheConfig, SWBufferConfig, buddy_cache_access,
                          buddy_cache_init, sw_buffer_access, sw_buffer_init)
from .cost_model import DPUCost
from .heap import (OP_CALLOC, OP_FREE, OP_MALLOC, OP_NOOP, OP_REALLOC,
                   AllocRequest, AllocResponse)
from .pim_malloc import INVALID, PimMallocConfig

# Backend enumeration has ONE source of truth: the protocol registry
# (`heap.REGISTRY`, populated by the `@heap.register` decorators below).
# `KINDS` is derived from it on attribute access (PEP 562), so registering
# a backend — from this module or anywhere else — auto-enrolls it in every
# KINDS-parametrized suite (pinned in tests/test_heap_api.py).
def __getattr__(name: str):
    if name == "KINDS":
        heap._ensure_backends()
        return tuple(heap.REGISTRY)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


# --------------------------------------------------------------------------
# Straw-man allocator: buddy-only over the full heap, min 32 B
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class StrawmanConfig:
    heap_bytes: int = 32 * 1024 * 1024
    num_threads: int = 16
    min_block: int = 32

    @property
    def buddy_cfg(self) -> BuddyConfig:
        return BuddyConfig(heap_bytes=self.heap_bytes, min_block=self.min_block)


class StrawmanState(NamedTuple):
    buddy: BuddyState
    leaf_log2: jnp.ndarray  # int8[n_leaf] alloc size exponent at base leaf, -1


def strawman_init(cfg: StrawmanConfig) -> StrawmanState:
    return StrawmanState(
        buddy=buddy.init(cfg.buddy_cfg),
        leaf_log2=jnp.full((cfg.buddy_cfg.n_leaf,), -1, jnp.int8),
    )


def strawman_malloc(cfg: StrawmanConfig, st: StrawmanState, sizes, active=None):
    T = cfg.num_threads
    if active is None:
        active = jnp.ones((T,), bool)
    requested = active & (sizes > 0)
    # heap-exceeding sizes fail without reaching next_pow2 (int32 wrap > 2^30)
    active = requested & (sizes <= cfg.heap_bytes)
    tlen = cfg.buddy_cfg.trace_len

    def step(carry, x):
        bstate, leaf_log2, border = carry
        need, size = x
        bstate2, off, bev = buddy.alloc(cfg.buddy_cfg, bstate, size)
        ok = need & (off >= 0)
        bstate = BuddyState(longest=jnp.where(need, bstate2.longest, bstate.longest))
        leaf = jnp.where(ok, off // cfg.min_block, 0)
        lg = ilog2(next_pow2(jnp.maximum(size, cfg.min_block)))
        leaf_log2 = leaf_log2.at[leaf].set(
            jnp.where(ok, lg.astype(jnp.int8), leaf_log2[leaf])
        )
        ptr = jnp.where(ok, off, INVALID)
        bpos = jnp.where(need, border, INVALID)
        border = border + need.astype(jnp.int32)
        ev = (
            jnp.where(need, bev.levels_down, 0),
            jnp.where(need, bev.levels_up, 0),
            jnp.where(need, bev.trace, jnp.full((tlen,), INVALID, jnp.int32)),
            bpos, ok,
        )
        return (bstate, leaf_log2, border), (ptr, ev)

    carry = (st.buddy, st.leaf_log2, jnp.int32(0))
    carry, (ptrs, (lv_down, lv_up, trace, bpos, ok)) = lax.scan(
        step, carry, (active, sizes)
    )
    bstate, leaf_log2, _ = carry
    path = jnp.where(active & ok, 2,
                     jnp.where(requested, 3, INVALID)).astype(jnp.int32)
    ev = pim_malloc.MallocEvent(path=path, backend_pos=bpos, levels_down=lv_down,
                                levels_up=lv_up, trace=trace)
    return StrawmanState(buddy=bstate, leaf_log2=leaf_log2), ptrs, ev


def strawman_free(cfg: StrawmanConfig, st: StrawmanState, ptrs, active=None):
    """Strawman free round. Same misuse accounting as `pim_malloc.free`:
    NULL (-1) frees are benign no-ops (path -1); any other requested free
    that is out of range or untracked is dropped (path 2)."""
    T = cfg.num_threads
    if active is None:
        active = jnp.ones((T,), bool)
    requested = active & (ptrs != INVALID)
    active = requested & (ptrs >= 0) & (ptrs < cfg.heap_bytes)
    tlen = cfg.buddy_cfg.trace_len

    def step(carry, x):
        bstate, leaf_log2, border = carry
        need, ptr = x
        leaf = jnp.where(need, ptr // cfg.min_block, 0)
        lg = leaf_log2[leaf].astype(jnp.int32)
        need = need & (lg >= 0)
        size = jnp.int32(1) << jnp.maximum(lg, 0)
        bstate2, bev = buddy.free(cfg.buddy_cfg, bstate, ptr, size)
        bstate = BuddyState(longest=jnp.where(need, bstate2.longest, bstate.longest))
        leaf_log2 = leaf_log2.at[leaf].set(
            jnp.where(need, jnp.int8(-1), leaf_log2[leaf])
        )
        bpos = jnp.where(need, border, INVALID)
        border = border + need.astype(jnp.int32)
        ev = (
            jnp.where(need, bev.levels_up, 0),
            jnp.where(need, bev.trace, jnp.full((tlen,), INVALID, jnp.int32)),
            bpos,
        )
        return (bstate, leaf_log2, border), ev

    carry = (st.buddy, st.leaf_log2, jnp.int32(0))
    carry, (lv_up, trace, bpos) = lax.scan(step, carry, (active, ptrs))
    bstate, leaf_log2, _ = carry
    dropped = requested & (bpos < 0)
    path = jnp.where(bpos >= 0, 1, jnp.where(dropped, 2, INVALID)).astype(jnp.int32)
    ev = pim_malloc.FreeEvent(path=path, backend_pos=bpos, levels_up=lv_up,
                              trace=trace)
    return StrawmanState(buddy=bstate, leaf_log2=leaf_log2), ev


# --------------------------------------------------------------------------
# Composite simulator
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class SystemConfig:
    kind: str = "sw"
    heap_bytes: int = 32 * 1024 * 1024
    num_threads: int = 16
    pm: PimMallocConfig = None
    straw: StrawmanConfig = None
    sw_buf: SWBufferConfig = SWBufferConfig()
    bc: BuddyCacheConfig = BuddyCacheConfig()
    dpu: DPUCost = DPUCost()

    def __post_init__(self):
        heap._ensure_backends()
        assert self.kind in heap.REGISTRY, \
            f"unknown kind {self.kind!r} (registered: {tuple(heap.REGISTRY)})"
        if self.pm is None:
            object.__setattr__(self, "pm", PimMallocConfig(
                heap_bytes=self.heap_bytes, num_threads=self.num_threads))
        if self.straw is None:
            object.__setattr__(self, "straw", StrawmanConfig(
                heap_bytes=self.heap_bytes, num_threads=self.num_threads))

    @property
    def trace_len(self) -> int:
        cfg = self.straw.buddy_cfg if self.kind == "strawman" else self.pm.buddy_cfg
        return cfg.trace_len

    @property
    def access_fn(self):
        if self.kind in ("hwsw", "sanitizer", "arena", "tlregion"):
            return functools.partial(buddy_cache_access, self.bc)
        return functools.partial(sw_buffer_access, self.sw_buf)

    def cache_init(self):
        if self.kind in ("hwsw", "sanitizer", "arena", "tlregion"):
            return buddy_cache_init(self.bc)
        return sw_buffer_init(self.sw_buf)

    @property
    def dma_bytes_per_miss(self) -> int:
        if self.kind in ("hwsw", "sanitizer", "arena", "tlregion"):
            return buddy_cache.WORD_BYTES
        return self.sw_buf.line_bytes


class HeapTelemetry(NamedTuple):
    """Per-core heap-health counters, advanced on every protocol round.

    Rounded (size-class / pow2) bytes, i.e. allocator-side occupancy, not
    user-requested bytes. For any well-formed request stream the
    conservation law

        live_bytes + buddy free bytes + cached thread-cache bytes
            == heap_bytes

    holds after every round (pinned in tests/test_telemetry.py); the two
    snapshot terms come from `repro.core.telemetry`. Both counters are
    identical across backends — the deltas are computed in `_price_round`,
    which every kind goes through.
    """

    live_bytes: jnp.ndarray  # int32[] rounded bytes currently handed out
    hwm_bytes: jnp.ndarray   # int32[] high-water mark of live_bytes


def telemetry_init() -> HeapTelemetry:
    z = jnp.int32(0)
    return HeapTelemetry(live_bytes=z, hwm_bytes=z)


def _advance_telemetry(t: HeapTelemetry, alloc_bytes, freed_bytes):
    live = t.live_bytes + alloc_bytes - freed_bytes
    return HeapTelemetry(live_bytes=live,
                         hwm_bytes=jnp.maximum(t.hwm_bytes, live))


class SystemState(NamedTuple):
    alloc: object            # PimMallocState | StrawmanState
    cache: object            # BuddyCacheState | SWBufferState
    telem: HeapTelemetry     # fragmentation/utilization counters


class RoundInfo(NamedTuple):
    latency_cyc: jnp.ndarray   # float32[T]
    path: jnp.ndarray          # int32[T]
    meta_hits: jnp.ndarray     # int32[T]
    meta_misses: jnp.ndarray   # int32[T]
    dram_bytes: jnp.ndarray    # int32[T]
    backend_cyc: jnp.ndarray   # float32[T] service time excl. queuing


def system_init(cfg: SystemConfig, prepopulate: bool = True):
    if cfg.kind in ("arena", "tlregion"):
        # the layered frontend owns its region carve — freelists start empty
        # and spill-refill on demand (see repro.core.arena.init_state)
        from . import arena
        return arena.init_state(cfg)
    if cfg.kind == "strawman":
        alloc = strawman_init(cfg.straw)
    else:
        alloc = pim_malloc.init(cfg.pm, prepopulate=prepopulate)
    base = SystemState(alloc=alloc, cache=cfg.cache_init(),
                       telem=telemetry_init())
    if cfg.kind == "sanitizer":
        from . import sanitizer
        return sanitizer.init_state(cfg, base)
    return base


def _cache_pass(cfg: SystemConfig, cache_st, backend_pos, traces):
    """Run the metadata cache over this round's backend ops in mutex order."""
    T = traces.shape[0]
    key = jnp.where(backend_pos >= 0, backend_pos, jnp.int32(1 << 30))
    order = jnp.argsort(key)
    traces_sorted = traces[order]
    cache_st, stats = buddy_cache.simulate_traces(cfg.access_fn, cache_st,
                                                  traces_sorted)
    inv = jnp.zeros((T,), jnp.int32).at[order].set(jnp.arange(T, dtype=jnp.int32))
    return cache_st, buddy_cache.TraceStats(
        hits=stats.hits[inv], misses=stats.misses[inv],
        dram_bytes=stats.dram_bytes[inv],
    )


def _strawman_realloc_meta(cfg: StrawmanConfig, st: StrawmanState, ptrs, sizes):
    """Strawman counterpart of pim_malloc.realloc_meta over leaf_log2."""
    valid = (ptrs >= 0) & (ptrs < cfg.heap_bytes)
    leaf = jnp.where(valid, ptrs // cfg.min_block, 0)
    lg = st.leaf_log2[leaf].astype(jnp.int32)
    valid_old = valid & (lg >= 0)
    old_bytes = jnp.where(valid_old, jnp.int32(1) << jnp.maximum(lg, 0), 0)
    new_bytes = next_pow2(jnp.maximum(sizes, cfg.min_block))
    return pim_malloc.ReallocMeta(
        valid_old=valid_old, in_place=valid_old & (new_bytes == old_bytes),
        old_bytes=old_bytes, new_bytes=new_bytes)


def _protocol_round(cfg: SystemConfig, st: SystemState, req: AllocRequest,
                    malloc_fn, free_fn, meta_fn, free_path_fn):
    """One mixed-op protocol round over kind-specific allocator primitives.

    Phases: (1) realloc size-class analysis on the pre-round metadata,
    (2) one batched malloc round (MALLOC/CALLOC + relocating REALLOCs),
    (3) one batched free round (FREE + released old realloc blocks), then a
    single metadata-cache pass + mutex queue over both phases' backend ops
    in serialization order (malloc phase drains first — mutex FIFO).
    Each phase runs under a `jax.named_scope` (`realloc_meta`, `malloc`,
    `free`, `cache_pass`, `price`), so its device ops carry its name.
    """
    op, size, ptr = req.op, req.size, req.ptr
    is_alloc = (op == OP_MALLOC) | (op == OP_CALLOC)
    is_re = op == OP_REALLOC
    is_free = op == OP_FREE

    with jax.named_scope("realloc_meta"):
        meta = meta_fn(st.alloc, ptr, size)
    re_live = is_re & (size > 0)
    in_place = re_live & meta.in_place
    moved = re_live & ~meta.in_place
    re_free0 = is_re & (size <= 0) & (ptr >= 0)

    # ---- phase 1: batched malloc (new blocks) ------------------------------
    with jax.named_scope("malloc"):
        m_active = (is_alloc & (size > 0)) | moved
        alloc_st, mptrs, mev = malloc_fn(
            st.alloc, jnp.where(m_active, size, 0), m_active)
        mok = m_active & (mptrs >= 0)

    # ---- phase 2: batched free (explicit frees + vacated realloc blocks) ---
    with jax.named_scope("free"):
        f_active = is_free | (moved & meta.valid_old & mok) | re_free0
        alloc_st, fev = free_fn(alloc_st, jnp.where(f_active, ptr, INVALID),
                                f_active)
        fpath = free_path_fn(fev)

    # ---- one cache pass + shared pricing over both phases ------------------
    with jax.named_scope("cache_pass"):
        n_back_m = jnp.sum(mev.backend_pos >= 0)
        bpos = jnp.concatenate([
            mev.backend_pos,
            jnp.where(fev.backend_pos >= 0, fev.backend_pos + n_back_m,
                      INVALID),
        ])
        traces = jnp.concatenate([mev.trace, fev.trace], axis=0)
        cache_st, tstats = _cache_pass(cfg, st.cache, bpos, traces)
    T = op.shape[0]
    with jax.named_scope("price"):
        resp, alloc_bytes, freed_bytes = _price_round(
            cfg, req, mptrs=mptrs, m_path=mev.path, m_bpos=mev.backend_pos,
            m_lvdown=mev.levels_down, m_lvup=mev.levels_up, fpath=fpath,
            f_bpos=fev.backend_pos, f_lvup=fev.levels_up,
            hits_m=tstats.hits[:T], miss_m=tstats.misses[:T],
            dram_m=tstats.dram_bytes[:T], hits_f=tstats.hits[T:],
            miss_f=tstats.misses[T:], dram_f=tstats.dram_bytes[T:],
            in_place=in_place, moved=moved, mok=mok,
            valid_old=meta.valid_old, old_bytes=meta.old_bytes,
            new_bytes=meta.new_bytes, re_free0=re_free0)
        telem = _advance_telemetry(st.telem, alloc_bytes, freed_bytes)
    return SystemState(alloc=alloc_st, cache=cache_st, telem=telem), resp


def _price_round(cfg: SystemConfig, req: AllocRequest, *, mptrs, m_path,
                 m_bpos, m_lvdown, m_lvup, fpath, f_bpos, f_lvup, hits_m,
                 miss_m, dram_m, hits_f, miss_f, dram_f, in_place, moved,
                 mok, valid_old, old_bytes, new_bytes, re_free0):
    """Price one protocol round; returns (AllocResponse, alloc_bytes,
    freed_bytes) — the heap-telemetry deltas of the round in rounded
    allocator bytes (see :class:`HeapTelemetry`).

    Every backend's round feeds it the metadata cache sim's per-op stats.
    """
    op, size, ptr = req.op, req.size, req.ptr
    is_alloc = (op == OP_MALLOC) | (op == OP_CALLOC)
    is_free = op == OP_FREE

    n_back_m = jnp.sum(m_bpos >= 0)
    bpos = jnp.concatenate(
        [m_bpos, jnp.where(f_bpos >= 0, f_bpos + n_back_m, INVALID)])
    cyc_m = cost_model.backend_op_cyc(cfg.dpu, m_lvdown, m_lvup,
                                      hits_m, miss_m, dram_m)
    cyc_m = jnp.where(m_bpos >= 0, cyc_m, 0.0)
    cyc_f = cost_model.backend_op_cyc(cfg.dpu, jnp.zeros_like(f_lvup),
                                      f_lvup, hits_f, miss_f, dram_f)
    cyc_f = jnp.where(f_bpos >= 0, cyc_f, 0.0)

    # mutex busy-wait: position k waits for the service of positions < k
    svc = jnp.concatenate([cyc_m, cyc_f])
    key = jnp.where(bpos >= 0, bpos, jnp.int32(1 << 30))
    order = jnp.argsort(key)
    wait_sorted = jnp.cumsum(svc[order]) - svc[order]
    wait = jnp.zeros_like(svc).at[order].set(wait_sorted)
    wait = jnp.where(bpos >= 0, wait, 0.0)
    T = op.shape[0]
    wait_m, wait_f = wait[:T], wait[T:]

    dpu = cfg.dpu
    own_m = (jnp.where(m_path == 0, dpu.cyc_front_hit, 0.0)
             + jnp.where(m_path == 1, dpu.cyc_front_hit + dpu.cyc_refill, 0.0)
             + cyc_m)
    lat_m = jnp.where(m_path >= 0, own_m + wait_m, 0.0)
    own_f = jnp.where(fpath == 0, dpu.cyc_front_push, 0.0) + cyc_f
    lat_f = jnp.where(fpath >= 0, own_f + wait_f, 0.0)
    # relocating realloc DMAs the surviving payload; calloc zero-fills.
    copy_cyc = jnp.where(
        moved & mok & valid_old,
        cost_model.mram_access_cyc(dpu, jnp.minimum(old_bytes, new_bytes)),
        0.0)
    zero_cyc = jnp.where((op == OP_CALLOC) & mok,
                         cost_model.mram_access_cyc(dpu, size), 0.0)
    # in-place realloc: O(1) metadata peek, no heap traffic.
    inplace_cyc = jnp.where(in_place, jnp.float32(dpu.cyc_front_hit), 0.0)
    latency = lat_m + lat_f + copy_cyc + zero_cyc + inplace_cyc

    m_active = (is_alloc & (size > 0)) | moved
    out_ptr = jnp.where(is_alloc & mok, mptrs,
                        jnp.where(in_place, ptr,
                                  jnp.where(moved & mok, mptrs, INVALID)))
    ok = (is_alloc & mok) | in_place | (moved & mok) | (
        (is_free | re_free0) & ((fpath == 0) | (fpath == 1)))
    path = jnp.where(m_active, m_path,
                     jnp.where(is_free | re_free0, fpath,
                               jnp.where(in_place, 0, INVALID)))
    # heap-telemetry deltas: rounded bytes handed out / returned this round
    # (new_bytes/old_bytes come from the kind's realloc-meta rounding, which
    # matches the malloc/free paths' actual placement sizes)
    new_alloc = (is_alloc & mok) | (moved & mok)
    alloc_bytes = jnp.sum(jnp.where(new_alloc, new_bytes, 0))
    # every free-phase participant — explicit frees, realloc(p, 0), and a
    # moved realloc's vacated old block — only returns bytes when the free
    # actually served (fpath 0/1): a capacity-dropped push (fpath 2) leaks
    # the block, which must stay in live_bytes for conservation to hold
    freed_served = ((is_free | re_free0 | (moved & mok & valid_old))
                    & ((fpath == 0) | (fpath == 1)))
    freed_bytes = jnp.sum(jnp.where(freed_served, old_bytes, 0))
    resp = AllocResponse(
        ptr=out_ptr, ok=ok, path=path.astype(jnp.int32), moved=moved & mok,
        latency_cyc=latency, backend_cyc=cyc_m + cyc_f,
        meta_hits=hits_m + hits_f, meta_misses=miss_m + miss_f,
        dram_bytes=dram_m + dram_f,
    )
    return resp, alloc_bytes, freed_bytes


@heap.register("strawman")
def _step_strawman(cfg: SystemConfig, st: SystemState, req: AllocRequest):
    return _protocol_round(
        cfg, st, req,
        malloc_fn=lambda s, z, a: strawman_malloc(cfg.straw, s, z, a),
        free_fn=lambda s, p, a: strawman_free(cfg.straw, s, p, a),
        meta_fn=lambda s, p, z: _strawman_realloc_meta(cfg.straw, s, p, z),
        free_path_fn=lambda ev: ev.path,
    )


@heap.register("sw")
@heap.register("hwsw")
def _step_pim(cfg: SystemConfig, st: SystemState, req: AllocRequest):
    return _protocol_round(
        cfg, st, req,
        malloc_fn=lambda s, z, a: pim_malloc.malloc(cfg.pm, s, z, a),
        free_fn=lambda s, p, a: pim_malloc.free(cfg.pm, s, p, a),
        meta_fn=lambda s, p, z: pim_malloc.realloc_meta(cfg.pm, s, p, z),
        free_path_fn=lambda ev: ev.path,
    )


@functools.partial(jax.jit, static_argnums=0)
def _sanitizer_step_compiled(cfg: SystemConfig, st, req: AllocRequest):
    from . import sanitizer

    return sanitizer.step(cfg, st, req, _step_pim)


@heap.register("sanitizer")
def _step_sanitizer(cfg: SystemConfig, st, req: AllocRequest):
    """ASan-style shadow-heap wrapper over the hwsw design point.

    Classifies every FREE/REALLOC operand against a 16 B-granule shadow
    map, quarantines legitimate frees in a FIFO ring, and forwards only
    clean work to `_step_pim`; poisoned operands are answered with
    deterministic tagged reports. See `repro.core.sanitizer`.

    The step is jit-compiled as a single unit (cfg static): the shadow
    classification + forwarded hwsw round otherwise execute as dozens of
    separately compiled primitives per eager call, which both slows the
    KINDS-parametrized suites down and bloats XLA's per-process
    compilation footprint.
    """
    return _sanitizer_step_compiled(cfg, st, req)


@functools.partial(jax.jit, static_argnums=0)
def _arena_step_compiled(cfg: SystemConfig, st, req: AllocRequest):
    from . import arena

    return arena.step(cfg, st, req, _step_pim)


@heap.register("arena")
@heap.register("tlregion")
def _step_arena(cfg: SystemConfig, st, req: AllocRequest):
    """The layered design points: bump-pointer frontend over the pim stack.

    A pure-jnp arena pass (`repro.core.arena`) serves small allocs by
    bumping into a region carved out of the buddy heap at init, retires
    whole epochs with OP_EPOCH_RESET, and forwards everything else — big
    allocs, non-arena pointers, and spill-on-exhaustion — to the full
    hwsw stack (`_step_pim`). ``arena`` shares one region (bump
    adds serialize for cyc_bump_atomic each); ``tlregion`` gives each
    thread its own region and per-thread resets — no cross-thread atomic
    on the fast path. Jit-compiled as one unit for the same reason as the
    sanitizer step.
    """
    return _arena_step_compiled(cfg, st, req)


def _round_info(resp: AllocResponse) -> RoundInfo:
    return RoundInfo(latency_cyc=resp.latency_cyc, path=resp.path,
                     meta_hits=resp.meta_hits, meta_misses=resp.meta_misses,
                     dram_bytes=resp.dram_bytes, backend_cyc=resp.backend_cyc)


def fleet_accounting(req: AllocRequest, resp: AllocResponse) -> dict:
    """Cost-model accounting of one batched protocol round.

    Works on any leading batch shape; with [R, C, T] leaves (a ShardedHeap
    round) the `per_rank` lists break totals down by rank — the fleet-level
    numbers a router reports per round. Fleet totals are exact sums of the
    per-rank entries (pinned in tests/test_sharded_heap.py).
    """
    import numpy as np
    op = np.asarray(req.op)
    active = op != OP_NOOP
    lat = np.asarray(resp.latency_cyc)
    out = {
        "ops": int(active.sum()),
        "ok": int(np.asarray(resp.ok).sum()),
        "latency_cyc": float(lat.sum()),
        "max_latency_cyc": float(lat.max()) if lat.size else 0.0,
        "backend_cyc": float(np.asarray(resp.backend_cyc).sum()),
        "meta_hits": int(np.asarray(resp.meta_hits).sum()),
        "meta_misses": int(np.asarray(resp.meta_misses).sum()),
        "dram_bytes": int(np.asarray(resp.dram_bytes).sum()),
    }
    if op.ndim >= 3:  # [R, ...]: per-rank breakdown over the leading axis
        rest = tuple(range(1, op.ndim))
        out["per_rank"] = {
            "ops": active.sum(axis=rest).tolist(),
            "latency_cyc": lat.sum(axis=rest).tolist(),
            "dram_bytes": np.asarray(resp.dram_bytes).sum(axis=rest).tolist(),
        }
    return out


def malloc_round(cfg: SystemConfig, st: SystemState, sizes, active=None):
    """One all-MALLOC round: sizes int32[T]. Returns (state, ptrs, RoundInfo)."""
    st, resp = heap.step(cfg, st, heap.malloc_request(sizes, active))
    return st, resp.ptr, _round_info(resp)


def free_round(cfg: SystemConfig, st: SystemState, ptrs, active=None):
    """One all-FREE round: ptrs int32[T]. Returns (state, RoundInfo)."""
    st, resp = heap.step(cfg, st, heap.free_request(ptrs, active))
    return st, _round_info(resp)


def run_alloc_rounds(cfg: SystemConfig, st: SystemState, sizes_rounds):
    """scan over [R, T] request rounds; returns (state, ptrs [R,T], infos [R,...])."""

    def step(st, sizes):
        st, ptrs, info = malloc_round(cfg, st, sizes)
        return st, (ptrs, info)

    st, (ptrs, infos) = lax.scan(step, st, sizes_rounds)
    return st, ptrs, infos


def run_alloc_free_rounds(cfg: SystemConfig, st: SystemState, sizes_rounds):
    """Each round: alloc then immediately free (Fig 6's (de)allocation loop)."""

    def step(st, sizes):
        st, ptrs, info_a = malloc_round(cfg, st, sizes)
        st, info_f = free_round(cfg, st, ptrs)
        return st, (info_a, info_f)

    st, (infos_a, infos_f) = lax.scan(step, st, sizes_rounds)
    return st, infos_a, infos_f
