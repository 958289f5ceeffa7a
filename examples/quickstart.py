"""Quickstart: the unified PIM-malloc allocator surface.

    PYTHONPATH=src python examples/quickstart.py

Three views of ONE protocol (`repro.core.heap`):
  1. the paper's Table-2 facade — initAllocator / pimMalloc / pimFree /
     pimRealloc / pimCalloc (stateful convenience, one jitted step inside),
  2. raw `heap.step` with a mixed-op `AllocRequest` (what jit/vmap/shard_map
     compose over),
  3. a `MultiCoreHeap` — the whole multi-core PIM system as one
     `jit(vmap(step))` over stacked per-core states — raced across the
     paper's three design points with the DPU cost model.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import heap
from repro.core import system as sysm
from repro.core.api import initAllocator
from repro.runtime.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    # --- 1. Table 2 facade --------------------------------------------------
    a = initAllocator(1 << 20)  # 1 MB per-core heap, PIM-malloc-SW kind
    p1 = a.pimMalloc(100)       # thread-cache hit (128 B class)
    p2 = a.pimMalloc(100)
    p3 = a.pimMalloc(8192)      # bypass -> buddy backend
    print(f"pimMalloc: {p1=} {p2=} {p3=}")
    a.pimFree(p2)
    p4 = a.pimMalloc(100)       # LIFO: reuses p2's sub-block
    print(f"after free+malloc: {p4=} (== {p2=}: {p4 == p2})")
    p5 = a.pimRealloc(p4, 120)  # same 128 B class -> grows in place
    p6 = a.pimRealloc(p5, 300)  # 512 B class -> relocates (alloc+copy+free)
    print(f"pimRealloc: in-place {p5 == p4}, then moved to {p6=}")
    p7 = a.pimCalloc(64, 16)    # 1 KB zeroed -> 1024 B class
    a.pimFree(p1), a.pimFree(p3), a.pimFree(p6), a.pimFree(p7)
    print("stats:", a.stats)

    # --- 2. one mixed-op protocol round -------------------------------------
    cfg = sysm.SystemConfig(kind="hwsw", heap_bytes=1 << 20, num_threads=4)
    st = heap.init(cfg)
    st, r0 = heap.step(cfg, st, heap.malloc_request(
        jnp.array([64, 256, 64, 8192], jnp.int32)))
    req = heap.AllocRequest(
        op=jnp.array([heap.OP_REALLOC, heap.OP_FREE, heap.OP_CALLOC,
                      heap.OP_NOOP], jnp.int32),
        size=jnp.array([512, 0, 96, 0], jnp.int32),
        ptr=jnp.array([int(r0.ptr[0]), int(r0.ptr[1]), -1, -1], jnp.int32))
    st, r1 = heap.step(cfg, st, req)
    print("mixed round ptrs:", np.asarray(r1.ptr), "paths:",
          np.asarray(r1.path), f"moved: {np.asarray(r1.moved)}")

    # --- 3. multi-core race: straw-man vs SW vs HW/SW -----------------------
    C, R = 8, 64
    print(f"\n{R} rounds x {C} cores x 16 threads x 32 B (DPU cost model):")
    for kind in sysm.KINDS:
        cfg = sysm.SystemConfig(kind=kind, heap_bytes=1 << 22)
        mch = heap.MultiCoreHeap(cfg, num_cores=C)
        run = jax.jit(jax.vmap(functools.partial(
            heap.run_rounds, cfg), in_axes=(0, 1), out_axes=(0, 1)))
        reqs = jax.vmap(jax.vmap(heap.malloc_request))(
            jnp.full((R, C, 16), 32, jnp.int32))
        mch.state, resp = run(mch.state, reqs)
        us = np.asarray(resp.latency_cyc) / cfg.dpu.freq_hz * 1e6
        print(f"  {kind:9s}: mean {us.mean():8.3f} us   p99 "
              f"{np.percentile(us, 99):8.3f} us")


if __name__ == "__main__":
    main()
