"""Fig 14: the headline microbenchmark — straw-man vs PIM-malloc-SW vs
PIM-malloc-HW/SW at {32 B, 256 B, 4 KB} x {1, 16 threads}; 128 allocs/thread.

Overall speedups use the workload-weighted mean with the paper-cited
allocation-size distribution (>90% of real requests are small: 98% <= 1 KB
datacenter [63,68,131], 93% <= 512 B serverless [123])."""
import numpy as np

from .common import emit, micro_alloc

# datacenter allocation-size mix (98% <= 1 KB [63,68,131]): small requests
# dominate, large (backend/bypass) requests are the 2% tail
WEIGHTS = {32: 0.60, 256: 0.38, 4096: 0.02}


def bench(smoke: bool = False):
    recs = []
    rounds = 8 if smoke else 128
    res = {}
    for nt in (1, 16):
        for size in (32, 256, 4096):
            for kind in ("strawman", "sw", "hwsw"):
                r = micro_alloc(kind, size, nthreads=nt, rounds=rounds)
                res[(kind, size, nt)] = r["mean_us"]
                recs.append(emit(
                    f"fig14/{kind}/size={size}/threads={nt}", r["mean_us"],
                    f"p95={r['p95_us']:.3f}us", backend=kind,
                    allocs_per_sec=r["allocs_per_sec"],
                    metadata_bytes_per_op=r["metadata_bytes_per_op"]))

    for nt in (1, 16):
        w = {z: WEIGHTS[z] for z in WEIGHTS}
        straw = sum(w[z] * res[("strawman", z, nt)] for z in w)
        sw = sum(w[z] * res[("sw", z, nt)] for z in w)
        hw = sum(w[z] * res[("hwsw", z, nt)] for z in w)
        recs.append(emit(
            f"fig14/overall_sw_speedup/threads={nt}", sw,
            f"{straw / sw:.0f}x_vs_strawman (paper: 66x)",
            speedup_vs_strawman=straw / sw))
        recs.append(emit(
            f"fig14/overall_hwsw_gain/threads={nt}", hw,
            f"+{(sw / hw - 1) * 100:.0f}%_vs_sw (paper: +31%)",
            gain_vs_sw=sw / hw - 1))
    g4k = np.mean([res[("sw", 4096, nt)] / res[("hwsw", 4096, nt)]
                   for nt in (1, 16)])
    recs.append(emit(
        "fig14/hwsw_4kb_latency_reduction", res[("hwsw", 4096, 16)],
        f"-{(1 - 1 / g4k) * 100:.0f}% vs sw (paper: -39%)"))
    # bracketing range: pure small-size cells (the thread-cache fast path)
    for nt in (1, 16):
        r32 = res[("strawman", 32, nt)] / res[("sw", 32, nt)]
        recs.append(emit(
            f"fig14/small_size_speedup/threads={nt}", res[("sw", 32, nt)],
            f"{r32:.0f}x at 32B (brackets the paper's 66x from above)",
            speedup_32b=r32))
    return recs


def run():
    bench()
