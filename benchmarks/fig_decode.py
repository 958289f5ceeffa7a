"""fig_decode: closed-loop paged-KV LLM decode on the fleet mesh.

Runs `repro.launch.serve_decode.DecodeServe` sessions — Poisson session
arrivals, Zipf tenant popularity, prefill bursts, per-token page appends,
eviction — on the shard_mapped rank mesh (``mesh=None``), for the hwsw
backend. One row per backend:

  * ``us_per_call``     — modeled us per dispatched allocator op (the
                          perf-gated trajectory number)
  * ``tokens_per_sec``  — decode tokens over modeled wall time, the
                          serving-side throughput the gate tracks
  * ``alloc_p99_cyc``   — p99 allocator service latency under the decode
                          mix (frontend pages + bypass prefill bursts)
  * ``ttft_p50_cyc``    — arrival -> first token through round barriers

All metrics are modeled (deterministic in seed + cost model), so rows are
machine-stable; ``wall_s`` is the only wall-clock field (never gated).
Per-core conservation is asserted after every scan — a decode session that
leaks pages fails the bench before it ever reaches the gate.
"""
import time

from repro.core import system as sysm
from repro.launch.serve_decode import DecodeTraffic, serve_decode_session

from .common import emit

KINDS = ("hwsw",)


def bench(smoke: bool = False):
    if smoke:
        R, C, T, rounds, rate = 2, 2, 4, 32, 1.5
    else:
        R, C, T, rounds, rate = 2, 4, 16, 96, 6.0
    tc = DecodeTraffic(seed=29, rounds=rounds, session_rate=rate,
                       num_tenants=4 * R * C, max_context=576,
                       queue_cap=4 * R * C)
    recs = []
    for kind in KINDS:
        cfg = sysm.SystemConfig(kind=kind, heap_bytes=1 << 20,
                                num_threads=T)
        t0 = time.time()
        rep = serve_decode_session(cfg, R, C, traffic=tc, mesh=None)
        wall = time.time() - t0
        assert rep["conservation_residual"] == 0, \
            f"{kind}: per-core conservation broken after decode scan"
        recs.append(emit(
            f"fig_decode/{kind}/mesh", rep["us_per_op"],
            f"tok/s={rep['tokens_per_sec']:.0f};"
            f"p99={rep['alloc_p99_cyc']:.0f}cyc;"
            f"ttft={rep['ttft_p50_cyc']:.0f}cyc", backend=kind,
            tokens_per_sec=rep["tokens_per_sec"],
            alloc_p50_cyc=rep["alloc_p50_cyc"],
            alloc_p99_cyc=rep["alloc_p99_cyc"],
            ttft_p50_cyc=rep["ttft_p50_cyc"],
            ttft_p99_cyc=rep["ttft_p99_cyc"],
            decode_tokens=rep["decode_tokens"],
            prefill_tokens=rep["prefill_tokens"],
            sessions_prefilled=rep["sessions_prefilled"],
            sessions_dropped=rep["sessions_dropped"],
            decode_stalls=rep["decode_stalls"],
            hwm_bytes_max=rep["hwm_bytes_max"],
            external_frag_mean=rep["external_frag_mean"],
            failed_allocs=rep["failed_allocs"],
            dropped_frees=rep["dropped_frees"],
            ops_per_sec=rep["ops_per_sec"], wall_s=wall))
    return recs


def run():
    bench()
