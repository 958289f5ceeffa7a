"""Allocator client surface: `HeapClient` + the paper-facing Table-2 facade.

`HeapClient` is the one stateful client object every consumer builds on —
`kvcache.PagePool`, the Table-2 facade below, and the serving engines in
`repro.launch` all drive a registered heap kind through the same surface:

  * ``malloc / calloc / realloc / free`` — single-op convenience (one
    hardware thread active per call),
  * ``malloc_batch / calloc_batch / realloc_batch / free_batch`` — one op
    per hardware thread, returning the full `AllocResponse`,
  * ``request()`` — the raw protocol entry point every method routes
    through (subclass hook: `repro.workloads.trace.RecordingAllocator`
    overrides it to tape every round),
  * ``stats`` / ``telemetry()`` / ``last_info`` — allocator counters, a
    heap-health snapshot (`repro.core.telemetry`), and the per-thread DPU
    latencies of the most recent round.

Every call builds one `AllocRequest` batching this call's per-thread ops
and runs a single jitted `heap.step` round — there is exactly one compiled
step per (kind, shape), shared by all methods, instead of one scan per
Python-level call. For performance-critical / distributed use, call
`heap.step` (or `heap.MultiCoreHeap` / `heap.ShardedHeap`) directly and
manage state explicitly.

`Allocator` is the paper-facing facade (Table 2): initAllocator /
pimMalloc / pimFree / pimRealloc / pimCalloc (+Batch variants) are aliases
over the client surface so the examples read like the paper's UPMEM
programs. `HeapClient.wrap` adapts legacy duck-typed handles (the
deprecated ``PagePool(alloc=)`` injection hook) onto this surface; see
docs/api.md for the migration note.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import heap, pim_malloc
from .heap import AllocRequest, AllocResponse
from .pim_malloc import PimMallocConfig
from .system import SystemConfig, SystemState


class HeapClient:
    """One registered heap kind behind malloc/free/realloc/calloc + telemetry.

    One client == one per-PIM-core heap serving T hardware threads. All
    methods route through `request()`, so a subclass that overrides it
    (e.g. to record a tape) sees every protocol round of every consumer.
    """

    def __init__(self, heap_bytes: int = 32 * 1024 * 1024,
                 size_classes=(16, 32, 64, 128, 256, 512, 1024, 2048),
                 num_threads: int = 16, prepopulate: bool = True,
                 kind: str = "sw"):
        pm = PimMallocConfig(
            heap_bytes=heap_bytes, size_classes=tuple(size_classes),
            num_threads=num_threads,
        )
        self.cfg = SystemConfig(kind=kind, heap_bytes=heap_bytes,
                                num_threads=num_threads, pm=pm)
        self.state: SystemState = heap.init(self.cfg, prepopulate)
        self._step = jax.jit(functools.partial(heap.step, self.cfg))
        self.last_info: AllocResponse | None = None

    @classmethod
    def wrap(cls, handle) -> "HeapClient":
        """Adapt a legacy allocator handle onto the client surface.

        Accepts a `HeapClient` (returned as-is), a zero-arg factory
        returning one, or any duck-typed object with ``cfg`` / ``request()``
        (the pre-PR-8 ``PagePool(alloc=)`` injection contract).
        """
        if isinstance(handle, HeapClient):
            return handle
        if callable(handle) and not hasattr(handle, "request"):
            return cls.wrap(handle())
        if not hasattr(handle, "request") or not hasattr(handle, "cfg"):
            raise TypeError(
                f"cannot adapt {type(handle).__name__!r} to HeapClient: "
                "need a HeapClient, a zero-arg factory returning one, or "
                "an object with .cfg and .request(AllocRequest)")
        return _HandleAdapter(handle)

    # -- protocol entry point ------------------------------------------------
    def request(self, req: AllocRequest) -> AllocResponse:
        """Serve one batched request round; advances the heap state."""
        self.state, resp = self._step(self.state, req)
        self.last_info = resp
        return resp

    def _one(self, build, thread: int) -> AllocResponse:
        T = self.cfg.num_threads
        active = jnp.zeros((T,), bool).at[thread].set(True)
        return self.request(build(active))

    # -- single-op convenience (one hardware thread active) ------------------
    def malloc(self, size: int, thread: int = 0) -> int:
        resp = self._one(lambda a: heap.malloc_request(
            jnp.full((self.cfg.num_threads,), size, jnp.int32), a), thread)
        return int(resp.ptr[thread])

    def free(self, ptr: int, thread: int = 0) -> None:
        self._one(lambda a: heap.free_request(
            jnp.full((self.cfg.num_threads,), ptr, jnp.int32), a), thread)

    def realloc(self, ptr: int, size: int, thread: int = 0) -> int:
        T = self.cfg.num_threads
        resp = self._one(lambda a: heap.realloc_request(
            jnp.full((T,), ptr, jnp.int32), jnp.full((T,), size, jnp.int32),
            a), thread)
        return int(resp.ptr[thread])

    def calloc(self, nmemb: int, size: int, thread: int = 0) -> int:
        T = self.cfg.num_threads
        resp = self._one(lambda a: heap.calloc_request(
            jnp.full((T,), nmemb, jnp.int32), jnp.full((T,), size, jnp.int32),
            a), thread)
        return int(resp.ptr[thread])

    # -- batched (one op per hardware thread, full response) -----------------
    def malloc_batch(self, sizes, active=None) -> AllocResponse:
        return self.request(heap.malloc_request(sizes, active))

    def free_batch(self, ptrs, active=None) -> AllocResponse:
        """Free one pointer per thread slot. NULL (-1) frees are benign
        no-ops; any other stale/garbage pointer reaches the backend so it
        counts against `Stats.dropped_frees` (and, on the ``sanitizer``
        kind, is tagged) instead of silently vanishing."""
        return self.request(heap.free_request(ptrs, active))

    def realloc_batch(self, ptrs, sizes, active=None) -> AllocResponse:
        return self.request(heap.realloc_request(ptrs, sizes, active))

    def calloc_batch(self, nmemb, sizes, active=None) -> AllocResponse:
        return self.request(heap.calloc_request(nmemb, sizes, active))

    def epoch_reset(self, active=None) -> AllocResponse:
        """Retire the current allocation epoch (``OP_EPOCH_RESET``).

        On the ``arena`` kind any active thread clears the whole shared
        bump region (idempotent across threads in one round); on
        ``tlregion`` each active thread clears only its own region. Every
        pointer the arena handed out this epoch is invalid afterwards —
        the caller must drop its references (the ``trace_lint`` rule).
        Backends without an arena frontend answer the round as idle, and
        the ``sanitizer`` retires every LIVE shadow start to STALE and
        tags later uses as ``epoch_stale``."""
        return self.request(heap.epoch_reset_request(
            self.cfg.num_threads, active))

    # -- maintenance / introspection -----------------------------------------
    def gc(self) -> None:
        """Merge fully-free thread-cache blocks back into the buddy.

        Works on every pim-style kind (sw/hwsw/sanitizer/arena/tlregion
        share the PimMallocState layout in `.alloc` — the
        sanitizer's shadow map and quarantine describe live allocations,
        which gc never moves, and the arena kinds' bump region lives
        outside the backend's thread caches entirely); strawman has no
        thread caches to merge."""
        if self.cfg.kind == "strawman":
            return
        # gc moves fully-free cached blocks back to the buddy: live bytes
        # are unchanged, so the telemetry counters carry over as-is
        self.state = self.state._replace(
            alloc=pim_malloc.gc(self.cfg.pm, self.state.alloc))

    @property
    def kind(self) -> str:
        return self.cfg.kind

    @property
    def num_threads(self) -> int:
        return self.cfg.num_threads

    @property
    def heap_bytes(self) -> int:
        return self.cfg.heap_bytes

    @property
    def stats(self) -> dict:
        if self.cfg.kind == "strawman":
            return {}
        return {k: int(v) for k, v in self.state.alloc.stats._asdict().items()}

    def telemetry(self) -> dict:
        """Heap-health snapshot: live/hwm/free bytes, external_frag, the
        conservation residual (see `repro.core.telemetry.snapshot`)."""
        from . import telemetry
        return telemetry.snapshot(self.cfg, self.state)


class _HandleAdapter(HeapClient):
    """`HeapClient.wrap` shim: forwards the protocol to a duck-typed handle
    while exposing the full client surface (deprecation path for the old
    ``PagePool(alloc=)`` hook)."""

    def __init__(self, handle):  # noqa: D401 — no heap of its own
        self._handle = handle
        self.cfg = handle.cfg
        self.last_info = getattr(handle, "last_info", None)

    def request(self, req: AllocRequest) -> AllocResponse:
        resp = self._handle.request(req)
        self.last_info = resp
        return resp

    @property
    def state(self):
        return self._handle.state

    def gc(self) -> None:
        if hasattr(self._handle, "gc"):
            self._handle.gc()


class Allocator(HeapClient):
    """Per-PIM-core allocator handle — the paper-facing Table 2 names
    (pimMalloc / pimFree / pimRealloc / pimCalloc and the Batch variants)
    as thin aliases over the `HeapClient` surface."""

    # -- Table 2 API ---------------------------------------------------------
    def pimMalloc(self, size: int, thread: int = 0) -> int:
        return self.malloc(size, thread=thread)

    def pimFree(self, ptr: int, thread: int = 0) -> None:
        self.free(ptr, thread=thread)

    def pimRealloc(self, ptr: int, size: int, thread: int = 0) -> int:
        return self.realloc(ptr, size, thread=thread)

    def pimCalloc(self, nmemb: int, size: int, thread: int = 0) -> int:
        return self.calloc(nmemb, size, thread=thread)

    # -- batched (one request per hardware thread) ----------------------------
    def pimMallocBatch(self, sizes) -> jnp.ndarray:
        return self.malloc_batch(sizes).ptr

    def pimFreeBatch(self, ptrs) -> None:
        self.free_batch(ptrs)

    def pimReallocBatch(self, ptrs, sizes) -> jnp.ndarray:
        return self.realloc_batch(ptrs, sizes).ptr

    def pimCallocBatch(self, nmemb, sizes) -> jnp.ndarray:
        return self.calloc_batch(nmemb, sizes).ptr


def initAllocator(heap_bytes: int, size_classes=None, **kw) -> Allocator:
    if size_classes is None:
        size_classes = (16, 32, 64, 128, 256, 512, 1024, 2048)
    return Allocator(heap_bytes=heap_bytes, size_classes=size_classes, **kw)
