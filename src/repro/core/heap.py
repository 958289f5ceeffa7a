"""The transform-native allocator surface: one request/response protocol.

Every allocator design point in this repo (``strawman``, ``sw``, ``hwsw``,
``pallas`` — the fused-kernel fast path — and ``sanitizer``, the
shadow-heap misuse detector) serves the same typed protocol:

    state, response = heap.step(cfg, state, request)

``AllocRequest`` carries one op per hardware thread — MALLOC / FREE /
REALLOC / CALLOC / NOOP — as a fixed-shape pytree of int32[T] leaves, and
``AllocResponse`` returns pointers, result paths, and the DPU cost model's
per-thread latency / metadata-traffic accounting.  ``step`` is pure and
shape-stable, so the transforms compose the way the paper's scaling story
requires:

  * one PIM core      : ``jax.jit(partial(heap.step, cfg))``
  * C cores, one rank : ``jax.vmap`` — see :class:`MultiCoreHeap`
  * a mesh of ranks   : ``shard_map`` of the vmapped step — see
    :class:`ShardedHeap` (metadata never leaves a core OR a rank — the
    PIM-Metadata/PIM-Executed placement of Fig 5 at fleet scale)

Backends register through :func:`register`; the implementations live in
``repro.core.system`` (cost-model instrumented) on top of the functional
allocators in ``repro.core.pim_malloc`` / ``repro.core.buddy``.  The
paper-facing Table 2 names (``initAllocator`` / ``pimMalloc`` / ``pimFree``
/ ``pimRealloc`` / ``pimCalloc``) are a thin stateful facade over this
module — see ``repro.core.api``.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

# Per-thread op codes (int32). CALLOC is MALLOC + zero-fill cost; the request
# carries the total byte count (nmemb * size), see `calloc_request`.
# EPOCH_RESET is the arena frontend's bulk-free: every arena-resident block
# is retired in O(1) (non-arena backends treat it as an idle round).
OP_NOOP = 0
OP_MALLOC = 1
OP_FREE = 2
OP_REALLOC = 3
OP_CALLOC = 4
OP_EPOCH_RESET = 5

OP_NAMES = {OP_NOOP: "noop", OP_MALLOC: "malloc", OP_FREE: "free",
            OP_REALLOC: "realloc", OP_CALLOC: "calloc",
            OP_EPOCH_RESET: "epoch_reset"}

NULL_PTR = -1  # the protocol's NULL: free(-1) is benign, alloc failure returns it


class AllocRequest(NamedTuple):
    """One batched request round: one op per hardware thread.

    op   int32[T]  OP_* code
    size int32[T]  bytes (MALLOC/CALLOC/REALLOC); ignored for FREE/NOOP
    ptr  int32[T]  heap offset (FREE/REALLOC); ignored otherwise (-1)
    """

    op: jnp.ndarray
    size: jnp.ndarray
    ptr: jnp.ndarray


class AllocResponse(NamedTuple):
    """Per-thread results of one protocol round.

    ptr          int32[T]   resulting pointer: new block for MALLOC/CALLOC,
                            surviving block for REALLOC, -1 for FREE/NOOP/fail
    ok           bool[T]    op succeeded (NOOP -> False)
    path         int32[T]   legacy path code (0 hit / 1 refill / 2 bypass /
                            3 fail for allocs; 0 small / 1 big / 2 dropped
                            for frees; -1 idle)
    moved        bool[T]    REALLOC relocated the block (alloc+copy+free)
    latency_cyc  float32[T] DPU cycles incl. mutex queuing + copy/zero DMA
    backend_cyc  float32[T] buddy-backend service cycles (excl. queuing)
    meta_hits    int32[T]   metadata-cache hits charged to this thread
    meta_misses  int32[T]
    dram_bytes   int32[T]
    """

    ptr: jnp.ndarray
    ok: jnp.ndarray
    path: jnp.ndarray
    moved: jnp.ndarray
    latency_cyc: jnp.ndarray
    backend_cyc: jnp.ndarray
    meta_hits: jnp.ndarray
    meta_misses: jnp.ndarray
    dram_bytes: jnp.ndarray


# ---------------------------------------------------------------------------
# request builders
# ---------------------------------------------------------------------------
# Builders accept any leading batch shape — the thread axis is last, so a
# [T], [C, T] or [R, C, T] argument yields a same-shaped request (this is
# how FleetRouter / fig_fleet call them). An `active` mask broadcasts
# NumPy-style against the data (trailing axes align); pass it pre-shaped —
# the MultiCoreHeap/ShardedHeap wrappers instead vmap the builders so
# leading-axis ([C] / [R, C]) masks select cores/ranks.
def _mask(active, shape):
    if active is None:
        return jnp.ones(shape, bool)
    return jnp.broadcast_to(jnp.asarray(active, bool), shape)


def noop_request(num_threads: int) -> AllocRequest:
    z = jnp.zeros((num_threads,), jnp.int32)
    return AllocRequest(op=z, size=z, ptr=z - 1)


def malloc_request(sizes, active=None) -> AllocRequest:
    sizes = jnp.asarray(sizes, jnp.int32)
    on = _mask(active, sizes.shape) & (sizes > 0)
    return AllocRequest(op=jnp.where(on, OP_MALLOC, OP_NOOP).astype(jnp.int32),
                        size=jnp.where(on, sizes, 0),
                        ptr=jnp.full_like(sizes, -1))


def free_request(ptrs, active=None) -> AllocRequest:
    """free(ptr) with C semantics: NULL (== -1) frees are benign no-ops;
    every other pointer — including garbage negatives and out-of-heap
    offsets — is passed through so the backend can count it against
    `Stats.dropped_frees` (path 2) instead of silently vanishing."""
    ptrs = jnp.asarray(ptrs, jnp.int32)
    on = _mask(active, ptrs.shape) & (ptrs != NULL_PTR)
    return AllocRequest(op=jnp.where(on, OP_FREE, OP_NOOP).astype(jnp.int32),
                        size=jnp.zeros_like(ptrs),
                        ptr=jnp.where(on, ptrs, -1))


def realloc_request(ptrs, sizes, active=None) -> AllocRequest:
    """realloc(ptr, size) with C semantics, enforced for every backend:

      * ptr < 0, size > 0   -> plain malloc(size)   (realloc(NULL, n))
      * ptr >= 0, size == 0 -> free(ptr)            (realloc(p, 0))
      * ptr < 0, size == 0  -> NOOP                 (realloc(NULL, 0))
      * size < 0            -> failing request: size_t-negative means a
        huge allocation, so the op keeps REALLOC/MALLOC form with an
        unsatisfiable INT32_MAX size — it fails (path 3) and a live old
        block stays intact, exactly like C realloc on failure.
    """
    ptrs = jnp.asarray(ptrs, jnp.int32)
    sizes = jnp.asarray(sizes, jnp.int32)
    ptrs, sizes = jnp.broadcast_arrays(ptrs, sizes)
    on = _mask(active, ptrs.shape)
    eff = jnp.where(sizes < 0, jnp.int32(jnp.iinfo(jnp.int32).max), sizes)
    has_ptr = ptrs >= 0
    op = jnp.where(
        ~on, OP_NOOP,
        jnp.where(has_ptr & (eff > 0), OP_REALLOC,
                  jnp.where(has_ptr, OP_FREE,
                            jnp.where(eff > 0, OP_MALLOC, OP_NOOP))))
    keep_ptr = on & has_ptr
    return AllocRequest(op=op.astype(jnp.int32),
                        size=jnp.where(on & (eff > 0), eff, 0),
                        ptr=jnp.where(keep_ptr, ptrs, -1))


def epoch_reset_request(num_threads: int, active=None) -> AllocRequest:
    """EPOCH_RESET: bulk-retire the arena frontend's current epoch.

    On the shared ``arena`` kind one resetting thread suffices (the op is
    idempotent within a round); on ``tlregion`` each active thread resets its
    own region. Backends without an arena frontend serve it as an idle round
    (ok=False, path -1), so mixed-kind tapes replay everywhere.
    """
    z = jnp.zeros((num_threads,), jnp.int32)
    on = _mask(active, z.shape)
    return AllocRequest(
        op=jnp.where(on, OP_EPOCH_RESET, OP_NOOP).astype(jnp.int32),
        size=z, ptr=z - 1)


def calloc_request(nmemb, sizes, active=None) -> AllocRequest:
    """calloc(nmemb, size): total bytes with the C overflow guard — an
    overflowing product becomes a failing (INT32_MAX) request, never a small
    wrapped one."""
    from .pim_malloc import total_calloc_bytes
    sizes = jnp.asarray(sizes, jnp.int32)
    total = total_calloc_bytes(nmemb, sizes)
    on = _mask(active, total.shape) & (total > 0)
    return AllocRequest(op=jnp.where(on, OP_CALLOC, OP_NOOP).astype(jnp.int32),
                        size=jnp.where(on, total, 0),
                        ptr=jnp.full_like(total, -1))


# ---------------------------------------------------------------------------
# backend registry
# ---------------------------------------------------------------------------
REGISTRY: dict[str, Callable] = {}
_BACKENDS = REGISTRY  # legacy alias


def register(kind: str):
    """Register a backend step: fn(cfg, state, AllocRequest) -> (state, AllocResponse)."""

    def deco(fn):
        REGISTRY[kind] = fn
        return fn

    return deco


def kinds() -> tuple:
    _ensure_backends()
    return tuple(sorted(REGISTRY))


def _ensure_backends():
    if not REGISTRY:
        from . import system  # noqa: F401  (registers strawman/sw/hwsw/pallas)


def init(cfg, prepopulate: bool = True):
    """Fresh heap state for `cfg` (a `system.SystemConfig`)."""
    from . import system
    return system.system_init(cfg, prepopulate=prepopulate)


def step(cfg, state, request: AllocRequest):
    """Serve one batched request round on the backend named by `cfg.kind`."""
    _ensure_backends()
    return _BACKENDS[cfg.kind](cfg, state, request)


# ---------------------------------------------------------------------------
# scan / multi-core drivers
# ---------------------------------------------------------------------------
def run_rounds(cfg, state, requests: AllocRequest):
    """scan `step` over an [R, T]-leaved request tape.

    Returns (state, AllocResponse with [R, T] leaves).
    """

    def body(st, req):
        st, resp = step(cfg, st, req)
        return st, resp

    return lax.scan(body, state, requests)


def run_alloc_free_rounds(cfg, state, sizes_rounds):
    """Fig 6's (de)allocation loop: each round mallocs sizes[r] then frees
    the pointers it just received. Returns (state, alloc resp, free resp)."""

    def body(st, sizes):
        st, ra = step(cfg, st, malloc_request(sizes))
        st, rf = step(cfg, st, free_request(ra.ptr))
        return st, (ra, rf)

    state, (ra, rf) = lax.scan(body, state, sizes_rounds)
    return state, ra, rf


def multicore_init(cfg, num_cores: int, prepopulate: bool = True):
    """Stacked per-core states: every leaf gains a leading [C] axis."""
    st = init(cfg, prepopulate=prepopulate)
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (num_cores,) + x.shape), st)


def multicore_step(cfg, states, requests: AllocRequest):
    """vmap of `step` over the core axis: requests are [C, T]-leaved."""
    return jax.vmap(functools.partial(step, cfg))(states, requests)


class MultiCoreHeap:
    """C independent per-core heaps behind one `[C, T]` batched entry point.

    The whole PIM system is literally `jit(vmap(step))` — core i's requests
    can never perturb core j's state because the states are disjoint slices
    of one stacked pytree. A TPU-mesh deployment shard_maps this same step
    over a rank axis on top (see :class:`ShardedHeap` and
    `repro.launch.fleet`).
    """

    def __init__(self, cfg, num_cores: int, prepopulate: bool = True):
        self.cfg = cfg
        self.num_cores = num_cores
        self.state = multicore_init(cfg, num_cores, prepopulate=prepopulate)
        self._step = jax.jit(jax.vmap(functools.partial(step, cfg)))

    @property
    def num_threads(self) -> int:
        return self.cfg.num_threads

    def step(self, request: AllocRequest) -> AllocResponse:
        """Serve a [C, T] request batch; advances the stacked state."""
        self.state, resp = self._step(self.state, request)
        return resp

    # vmap (rather than relying on builder broadcasting) so a per-core
    # [C]-shaped active mask keeps masking whole cores, not thread slots —
    # the same contract for all four builders (pinned in tests/test_heap_api)
    def _core_mask(self, active):
        if active is None:
            return None
        return jnp.broadcast_to(jnp.asarray(active, bool), (self.num_cores,))

    def _v(self, build, *args, active=None):
        return self.step(jax.vmap(build)(*args, self._core_mask(active)))

    def malloc(self, sizes, active=None) -> AllocResponse:
        return self._v(malloc_request, jnp.asarray(sizes, jnp.int32),
                       active=active)

    def free(self, ptrs, active=None) -> AllocResponse:
        return self._v(free_request, jnp.asarray(ptrs, jnp.int32),
                       active=active)

    def realloc(self, ptrs, sizes, active=None) -> AllocResponse:
        return self._v(realloc_request, jnp.asarray(ptrs, jnp.int32),
                       jnp.asarray(sizes, jnp.int32), active=active)

    def calloc(self, nmemb, sizes, active=None) -> AllocResponse:
        return self._v(calloc_request, jnp.asarray(nmemb, jnp.int32),
                       jnp.asarray(sizes, jnp.int32), active=active)


# ---------------------------------------------------------------------------
# fleet tier: shard_map over a rank mesh
# ---------------------------------------------------------------------------
def sharded_init(cfg, num_ranks: int, num_cores: int, prepopulate: bool = True):
    """Stacked fleet state: every leaf gains leading [R, C] axes."""
    with jax.named_scope("init"):
        st = multicore_init(cfg, num_cores, prepopulate=prepopulate)
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (num_ranks,) + x.shape), st)


def sharded_step(cfg, states, requests: AllocRequest):
    """vmap of `multicore_step` over the rank axis: requests are [R, C, T].

    This is the per-device body a ShardedHeap shard_maps over the rank axis;
    on its own it is the single-device fallback (identical results)."""
    return jax.vmap(functools.partial(multicore_step, cfg))(states, requests)


def sharded_inner(cfg, num_ranks: int, mesh=None, axis_name: str = "ranks"):
    """Build the fleet-round step fn([R,C]-state, [R,C,T]-request).

    The one place the mesh plumbing lives: returns ``(fn, mesh)`` where `fn`
    is :func:`sharded_step` wrapped in ``shard_map`` over a 1-D rank mesh
    (``mesh=None`` builds one over the local devices; ``mesh=False`` skips
    shard_map — the pure-vmap fallback, with ``mesh`` returned as None).
    Shared by :class:`ShardedHeap` (one round per call) and the FleetServe
    scan driver (`repro.launch.serve_fleet`, many rounds per call), so both
    tiers serve bitwise-identical results from the same transform stack.
    """
    inner = functools.partial(sharded_step, cfg)
    if mesh is None:
        from repro.parallel.meshctx import make_rank_mesh
        mesh = make_rank_mesh(num_ranks, axis_name)
    if mesh is False:
        return inner, None
    from jax.sharding import PartitionSpec
    axis_name = mesh.axis_names[0]
    if num_ranks % mesh.shape[axis_name]:
        raise ValueError(
            f"num_ranks={num_ranks} not divisible by mesh axis "
            f"{axis_name}={mesh.shape[axis_name]}")
    spec = PartitionSpec(axis_name)
    return jax.shard_map(inner, mesh=mesh, in_specs=(spec, spec),
                         out_specs=(spec, spec), check_vma=False), mesh


class ShardedHeap:
    """R ranks x C cores of independent heaps behind one [R, C, T] entry point.

    The third tier of the transform stack: ``shard_map`` (over a 1-D
    ``jax.sharding.Mesh`` of ranks) of the vmapped :func:`step`. Rank shards
    hold disjoint slices of one stacked state pytree, so metadata never
    crosses a core OR a rank boundary — the paper's PIM-Metadata /
    PIM-Executed placement at fleet scale (2560-DPU claim, Fig 5). The heap
    state argument is donated to the jitted step, so per-round updates reuse
    the state buffers in place instead of an O(heap) copy per protocol round
    (backends without donation, e.g. CPU, silently fall back to copying).

    ``mesh=None`` builds a 1-D mesh over every device (1-device on CPU CI —
    the whole path still compiles through shard_map) and raises ValueError
    unless the device count divides ``num_ranks``; ``mesh=False`` skips
    shard_map entirely and runs the pure vmap fallback. Both must be
    bitwise-identical to :class:`MultiCoreHeap` per (rank, core) — pinned in
    tests/test_sharded_heap.py.
    """

    def __init__(self, cfg, num_ranks: int, num_cores: int, mesh=None,
                 axis_name: str = "ranks", prepopulate: bool = True,
                 donate: bool = True):
        self.cfg = cfg
        self.num_ranks = num_ranks
        self.num_cores = num_cores
        self.state = sharded_init(cfg, num_ranks, num_cores,
                                  prepopulate=prepopulate)
        inner, self.mesh = sharded_inner(cfg, num_ranks, mesh=mesh,
                                         axis_name=axis_name)
        self.donate = donate
        self._step = jax.jit(inner, donate_argnums=(0,) if donate else ())

    @property
    def num_threads(self) -> int:
        return self.cfg.num_threads

    @property
    def shape(self) -> tuple:
        """(R, C, T): one slot per hardware thread in the fleet."""
        return (self.num_ranks, self.num_cores, self.cfg.num_threads)

    def step(self, request: AllocRequest) -> AllocResponse:
        """Serve a [R, C, T] request batch; advances the sharded state."""
        self.state, resp = self._step(self.state, request)
        return resp

    # vmap twice (rather than relying on builder broadcasting) so [R]- or
    # [R, C]-shaped active masks keep masking ranks/cores, not thread slots
    # (an [R] mask broadcasts to [R, C] first — the double vmap needs the
    # mask pre-shaped to the grid)
    def _grid_mask(self, active):
        if active is None:
            return None
        m = jnp.asarray(active, bool)
        m = m.reshape(m.shape + (1,) * (2 - m.ndim))
        return jnp.broadcast_to(m, (self.num_ranks, self.num_cores))

    def _vv(self, build, *args, active=None):
        return self.step(jax.vmap(jax.vmap(build))(
            *args, self._grid_mask(active)))

    def malloc(self, sizes, active=None) -> AllocResponse:
        return self._vv(malloc_request, jnp.asarray(sizes, jnp.int32),
                        active=active)

    def free(self, ptrs, active=None) -> AllocResponse:
        return self._vv(free_request, jnp.asarray(ptrs, jnp.int32),
                        active=active)

    def realloc(self, ptrs, sizes, active=None) -> AllocResponse:
        return self._vv(realloc_request, jnp.asarray(ptrs, jnp.int32),
                        jnp.asarray(sizes, jnp.int32), active=active)

    def calloc(self, nmemb, sizes, active=None) -> AllocResponse:
        return self._vv(calloc_request, jnp.asarray(nmemb, jnp.int32),
                        jnp.asarray(sizes, jnp.int32), active=active)
