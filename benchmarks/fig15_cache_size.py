"""Fig 15: buddy-cache size sensitivity — speedup over PIM-malloc-SW and hit
rate vs cache capacity (16 B ... 256 B); 16 threads, 4 KB requests."""
import numpy as np

import jax
import jax.numpy as jnp

from repro.core import buddy_cache, system as sysm

from .common import emit, micro_alloc


def _cache_cell(kind, cache_bytes, rounds):
    """One (kind, cache size) cell of the buddy-cache sweep."""
    cfg = sysm.SystemConfig(
        kind=kind, heap_bytes=1 << 25,
        bc=buddy_cache.BuddyCacheConfig(n_entries=cache_bytes // 4))
    st = sysm.system_init(cfg)
    sz = jnp.tile(jnp.full((16,), 4096, jnp.int32)[None], (rounds, 1))
    run_fn = jax.jit(lambda s, z: sysm.run_alloc_rounds(cfg, s, z))
    st, ptrs, infos = run_fn(st, sz)
    us = float(np.asarray(infos.latency_cyc).mean() / 350e6 * 1e6)
    hits = int(np.asarray(infos.meta_hits).sum())
    misses = int(np.asarray(infos.meta_misses).sum())
    dram = int(np.asarray(infos.dram_bytes).sum())
    return us, hits / max(hits + misses, 1), dram / (rounds * 16)


def bench(smoke: bool = False):
    recs = []
    rounds = 8 if smoke else 96
    cache_sizes = (16, 64) if smoke else (16, 32, 64, 128, 256)
    sw = micro_alloc("sw", 4096, nthreads=16, rounds=rounds)
    recs.append(emit("fig15/sw_baseline", sw["mean_us"], "", backend="sw",
                     allocs_per_sec=sw["allocs_per_sec"]))
    for cache_bytes in cache_sizes:
        us, hr, meta = _cache_cell("hwsw", cache_bytes, rounds)
        recs.append(emit(
            f"fig15/cache={cache_bytes}B", us,
            f"speedup_vs_sw={sw['mean_us'] / us:.2f}x;hit_rate={hr:.2f}",
            backend="hwsw", hit_rate=hr, speedup_vs_sw=sw["mean_us"] / us,
            metadata_bytes_per_op=meta))
    recs.append(emit(
        "fig15/claim", 0.0,
        "paper: speedup and hit rate saturate at 64B (=256 nodes at 2b)"))
    return recs


def run():
    bench()
