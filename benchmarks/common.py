"""Shared benchmark helpers: compiled microbench loops + CSV/JSON records.

Microbenchmarks drive the allocator through the `repro.core.heap` protocol
(`run_rounds` / `run_alloc_free_rounds` — the same `step` that serves every
backend kind), so figures measure exactly the public surface.

Every figure module exposes ``bench(smoke=False) -> [record]``; a record is
one emitted row plus its structured metrics (the JSON trajectory's unit —
see benchmarks/README.md for the schema). ``emit`` prints the CSV row and
returns the record, so modules stay single-sourced.
"""
from __future__ import annotations

import numbers

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import heap as heap_api
from repro.core import system as sysm

ROWS = []


def emit(name: str, us_per_call: float, derived: str = "",
         backend: str = None, **metrics) -> dict:
    """Print one `name,us_per_call,derived` CSV row; return the record.

    Extra keyword metrics land in the record as numbers (allocs_per_sec,
    metadata_bytes_per_op, ...) for the JSON artifact. Every record is
    stamped with the jax version, and — when the row measures a specific
    allocator design point — with its ``backend`` name
    (strawman/sw/hwsw/...), so baseline diffs stay attributable across
    environments and backend axes.
    """
    row = f"{name},{us_per_call:.4f},{derived}"
    ROWS.append(row)
    print(row, flush=True)
    rec = {"name": name, "us_per_call": float(us_per_call),
           "derived": str(derived), "jax": jax.__version__}
    if backend is not None:
        rec["backend"] = str(backend)
    for k, v in metrics.items():
        rec[k] = float(v) if isinstance(v, numbers.Number) else v
    return rec


def micro_alloc(kind: str, size: int, nthreads: int, rounds: int = 128,
                heap: int = 1 << 25, T: int = 16, alloc_free: bool = False):
    """Fig 14-style microbenchmark: per-thread latency stats (us).

    Also derives the JSON schema's throughput metrics: threads within a
    round run concurrently (mutex queuing is inside the cost model), rounds
    serialize, so modeled wall time is the sum of per-round maxima.
    """
    cfg = sysm.SystemConfig(kind=kind, heap_bytes=heap, num_threads=T)
    st = heap_api.init(cfg)
    sizes = jnp.where(jnp.arange(T) < nthreads, size, 0).astype(jnp.int32)
    sz = jnp.tile(sizes[None, :], (rounds, 1))
    if alloc_free:
        run = jax.jit(lambda s, z: heap_api.run_alloc_free_rounds(cfg, s, z))
        st, resp_a, resp_f = run(st, sz)
        lat_a = np.asarray(resp_a.latency_cyc)[:, :nthreads]
        lat_f = np.asarray(resp_f.latency_cyc)[:, :nthreads]
        lat = lat_a + lat_f
        # alloc and free are two serialized protocol rounds: wall = sum of
        # each subround's slowest thread (matches fig_fleet._alloc_free)
        wall_cyc = lat_a.max(axis=1).sum() + lat_f.max(axis=1).sum()
        dram = (np.asarray(resp_a.dram_bytes).sum()
                + np.asarray(resp_f.dram_bytes).sum())
    else:
        run = jax.jit(lambda s, z: heap_api.run_rounds(
            cfg, s, jax.vmap(heap_api.malloc_request)(z)))
        st, resp = run(st, sz)
        lat = np.asarray(resp.latency_cyc)[:, :nthreads]
        wall_cyc = lat.max(axis=1).sum()
        dram = np.asarray(resp.dram_bytes).sum()
    us = lat / cfg.dpu.freq_hz * 1e6
    ops = rounds * nthreads * (2 if alloc_free else 1)
    modeled_s = float(wall_cyc) / cfg.dpu.freq_hz
    return {
        "mean_us": float(us.mean()),
        "p95_us": float(np.percentile(us, 95)),
        "max_us": float(us.max()),
        "series_us": us.mean(axis=1),
        "dram_bytes": int(dram),
        "ops": ops,
        "allocs_per_sec": ops / max(modeled_s, 1e-12),
        "metadata_bytes_per_op": dram / max(ops, 1),
    }
