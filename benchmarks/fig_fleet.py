"""fig_fleet: fleet scaling sweep over the three-tier transform stack.

ShardedHeap (shard_map of the vmapped `heap.step` over a rank mesh) swept
over 1->R ranks x 1->C cores, three request mixes:

  * alloc_free : every thread mallocs 256 B then frees it (Fig 6's loop)
  * mixed      : malloc / realloc-half / free rounds through the
                 FleetRouter (the REALLOC path at fleet scale)
  * contention : strawman's shared mutex vs PIM-malloc-SW at the largest
                 fleet (Fig 7's scenario, per-core metadata never crossing
                 cores — the paper's x66-at-2560-DPUs scaling claim)

Per cell: modeled us/alloc (threads concurrent, rounds serialized), fleet
allocs/sec, metadata bytes/op, wall-clock us per jitted fleet step, and
scaling efficiency vs the 1x1 cell (flat = the paper's claim).
"""
import time

import numpy as np
import jax.numpy as jnp

from repro.core import heap as heap_api
from repro.core import system as sysm
from repro.launch.fleet import FleetRouter

from .common import emit

SIZES = (32, 256, 128, 4096, 64, 256, 32, 1024, 32, 256, 128, 2048, 64, 32,
         256, 512)


def _sizes(R, C, T, seed=0):
    pattern = np.asarray(SIZES[:T] if T <= len(SIZES)
                         else SIZES * (T // len(SIZES) + 1), np.int32)[:T]
    return jnp.asarray(np.broadcast_to(pattern, (R, C, T)).copy())


def _alloc_free(router, sizes, rounds):
    """Fig-6 loop at fleet scale; returns per-round fleet max latencies."""
    round_max = []
    for _ in range(rounds):
        ra = router.route(heap_api.malloc_request(sizes))
        rf = router.route(heap_api.free_request(ra.ptr))
        round_max.append(float(np.asarray(ra.latency_cyc).max())
                         + float(np.asarray(rf.latency_cyc).max()))
    return round_max


def _mixed(router, sizes, rounds):
    """malloc -> realloc half the fleet -> free: the full protocol."""
    round_max = []
    half = (jnp.arange(sizes.shape[-1]) % 2) == 0
    for r in range(rounds):
        ra = router.route(heap_api.malloc_request(sizes))
        rr = router.route(heap_api.realloc_request(
            ra.ptr, jnp.roll(sizes, r + 1, axis=-1),
            active=jnp.broadcast_to(half, sizes.shape)))
        live = jnp.where(rr.ptr >= 0, rr.ptr, ra.ptr)
        rf = router.route(heap_api.free_request(live))
        round_max.append(float(np.asarray(ra.latency_cyc).max())
                         + float(np.asarray(rr.latency_cyc).max())
                         + float(np.asarray(rf.latency_cyc).max()))
    return round_max


def _cell(kind, R, C, T, rounds, mix="alloc_free"):
    cfg = sysm.SystemConfig(kind=kind, heap_bytes=1 << 20, num_threads=T)
    sh = heap_api.ShardedHeap(cfg, num_ranks=R, num_cores=C)
    sizes = _sizes(R, C, T)
    run = _mixed if mix == "mixed" else _alloc_free
    run(FleetRouter(sh), sizes, 1)             # compile outside the clock
    router = FleetRouter(sh)                   # fresh accounting for the clock
    t0 = time.time()
    round_max = run(router, sizes, rounds)
    wall_us = (time.time() - t0) / router.rounds * 1e6
    st = router.stats
    freq = cfg.dpu.freq_hz
    modeled_s = sum(round_max) / freq
    return {
        "us_per_call": st["us_per_op"],
        "allocs_per_sec": st["ops"] / max(modeled_s, 1e-12),
        "metadata_bytes_per_op": st["dram_bytes_per_op"],
        "wall_us_per_step": wall_us,
        "ops": st["ops"],
    }


def bench(smoke: bool = False):
    recs = []
    if smoke:
        ranks_list, cores_list, T, rounds = (1, 2), (1, 2), 4, 3
    else:
        ranks_list, cores_list, T, rounds = (1, 2, 4), (1, 4, 16), 16, 12

    base = None
    for R in ranks_list:
        for C in cores_list:
            r = _cell("sw", R, C, T, rounds)
            if base is None:
                base = r
            sw_top = r                         # last cell = largest fleet
            # scaling efficiency: fleet throughput vs (R*C) x the 1x1 cell
            eff = r["allocs_per_sec"] / (R * C * base["allocs_per_sec"])
            flat = r["us_per_call"] / base["us_per_call"]
            recs.append(emit(
                f"fig_fleet/sw/ranks={R}/cores={C}", r["us_per_call"],
                f"eff={eff:.2f};lat_ratio={flat:.2f};"
                f"wall_step={r['wall_us_per_step']:.0f}us", backend="sw",
                allocs_per_sec=r["allocs_per_sec"],
                metadata_bytes_per_op=r["metadata_bytes_per_op"],
                scaling_efficiency=eff, latency_ratio_vs_1x1=flat,
                wall_us_per_step=r["wall_us_per_step"]))
    top = recs[-1]
    recs.append(emit(
        "fig_fleet/claim_flat_scaling", top["us_per_call"],
        f"per-core latency ratio at max fleet={top['latency_ratio_vs_1x1']:.2f}"
        " (flat=1.0; paper: x66 sustained across 2560 DPUs)",
        latency_ratio=top["latency_ratio_vs_1x1"]))

    # mixed-op fleet round (REALLOC path under shard_map)
    R, C = ranks_list[-1], cores_list[-1]
    r = _cell("sw", R, C, T, rounds, mix="mixed")
    recs.append(emit(
        f"fig_fleet/sw_mixed/ranks={R}/cores={C}", r["us_per_call"],
        f"allocs_per_sec={r['allocs_per_sec']:.0f}", backend="sw",
        allocs_per_sec=r["allocs_per_sec"],
        metadata_bytes_per_op=r["metadata_bytes_per_op"]))

    # Fig-7 contention at the largest fleet: shared-mutex strawman vs sw on
    # the SAME alloc_free mix (sw_top is the sweep's largest cell)
    straw = _cell("strawman", R, C, T, rounds)
    slow = straw["us_per_call"] / sw_top["us_per_call"]
    recs.append(emit(
        f"fig_fleet/contention/ranks={R}/cores={C}", straw["us_per_call"],
        f"strawman_vs_sw={slow:.1f}x (shared mutex vs per-thread caches)",
        backend="strawman", slowdown_vs_sw=slow))

    return recs


def run():
    bench()
