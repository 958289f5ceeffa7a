"""FleetServe demo: steady-state multi-tenant traffic over the PIM fleet.

    PYTHONPATH=src python examples/serve_fleet.py \
        [--ranks 2] [--cores 2] [--threads 4] [--rounds 48] [--rate 12] \
        [--placement round_robin|least_loaded|chunked] [--kind sw] \
        [--seed 0] [--queue-cap 64] [--export-trace PATH] [--chaos]

Plans a Poisson/Zipf tenant session, drives it through the donated
`lax.scan` round driver, and prints the serving report: admission /
backpressure counters, end-to-end latency percentiles in modeled DPU
cycles, queue-depth trace, and the fleet cost accounting. ``--export-trace``
writes rank 0 / core 0's slice as a ``pim-malloc-trace/v1`` tape replayable
with ``python -m repro.workloads.replay``.

``--chaos`` serves the same session through `ElasticFleetServe` instead:
a seed-derived `FaultPlan` (core kill, one-round stall, dropped round)
plus heap-pressure tenant migration, with the extra elastic counters
(migrations, kills, pressure checks) appended to the report. The chaos
session still pins dropped_frees == 0 and conservation_residual == 0.
"""
import argparse

from repro.core import system as sysm
from repro.launch.elastic import ElasticFleetServe, FaultPlan, MigrationConfig
from repro.launch.serve_fleet import FleetServe, TrafficConfig
from repro.runtime.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--ranks", type=int, default=2)
    ap.add_argument("--cores", type=int, default=2)
    ap.add_argument("--threads", type=int, default=4)
    ap.add_argument("--rounds", type=int, default=48)
    ap.add_argument("--rate", type=float, default=12.0,
                    help="mean external arrivals per round (Poisson)")
    ap.add_argument("--placement", default="round_robin",
                    choices=("chunked", "round_robin", "least_loaded"))
    ap.add_argument("--kind", default="sw",
                    choices=("strawman", "sw", "hwsw"))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--queue-cap", type=int, default=64)
    ap.add_argument("--tenants", type=int, default=16)
    ap.add_argument("--export-trace", default=None, metavar="PATH")
    ap.add_argument("--chaos", action="store_true",
                    help="elastic session: seed-derived fault plan + "
                         "heap-pressure tenant migration")
    args = ap.parse_args()

    cfg = sysm.SystemConfig(kind=args.kind, heap_bytes=1 << 19,
                            num_threads=args.threads)
    traffic = TrafficConfig(seed=args.seed, rounds=args.rounds,
                            arrival_rate=args.rate, num_tenants=args.tenants,
                            queue_cap=args.queue_cap)
    if args.chaos:
        faults = FaultPlan.generate(seed=args.seed + 1, rounds=args.rounds,
                                    shape=(args.ranks, args.cores,
                                           args.threads))
        engine = ElasticFleetServe(
            cfg, args.ranks, args.cores, traffic=traffic,
            placement=args.placement, faults=faults,
            migration=MigrationConfig(ratio=1.3, min_bytes=1 << 10,
                                      drain="interval", check_rounds=8))
    else:
        engine = FleetServe(cfg, args.ranks, args.cores, traffic=traffic,
                            placement=args.placement)
    plan, rep = engine.serve()

    R, C, T = plan.shape
    print(f"fleet [{R} ranks x {C} cores x {T} threads] kind={args.kind} "
          f"placement={args.placement} capacity={rep['capacity_per_round']}/round")
    print(f"offered={rep['offered']} dropped={rep['dropped']} "
          f"(drop_rate={rep['drop_rate']:.2f}) "
          f"dispatched={rep['external_dispatched']} external "
          f"+ {rep['expiry_frees_dispatched']} expiry frees "
          f"backlog_end={rep['backlog_end']}")
    print(f"latency e2e cyc: p50={rep['e2e_p50_cyc']:.0f} "
          f"p95={rep['e2e_p95_cyc']:.0f} p99={rep['e2e_p99_cyc']:.0f}  "
          f"service p99={rep['service_p99_cyc']:.0f}  "
          f"us/op={rep['us_per_op']:.3f}")
    print(f"queue depth mean={rep['queue_depth_mean']:.1f} "
          f"max={rep['queue_depth_max']}  modeled wall "
          f"{rep['modeled_wall_us']:.0f}us  "
          f"{rep['ops_per_sec']:.0f} ops/s")
    print(f"heap: live={rep['live_bytes']}B failed_allocs="
          f"{rep['failed_allocs']} dropped_frees={rep['dropped_frees']} "
          f"conservation_residual={rep['conservation_residual']}")
    print("per-rank ops:", rep["accounting"]["per_rank"]["ops"])
    if args.chaos:
        faults = ", ".join(f"r{ev['round']} {ev['kind']}"
                           + (f"@({ev['rank']},{ev['core']})"
                              if ev["kind"] != "drop" else "")
                           for ev in rep["faults"]) or "none"
        print(f"chaos: faults=[{faults}] kills={len(rep['kills'])} "
              f"migrations={len(rep['migrations'])} "
              f"(+{rep['migration_ops_dispatched']} migration ops) "
              f"killed_cores={rep['killed_cores']}")
        for ev in rep["migrations"]:
            src = tuple(ev["src"]) if ev["src"] else "?"
            print(f"  round {ev['round']:4d} migrate tenant {ev['tenant']} "
                  f"{src} -> {tuple(ev['dst'])} ({ev['bytes']}B live)")
    depths = rep["queue_depth"]
    peak = max(max(depths), 1)
    for r0 in range(0, len(depths), max(len(depths) // 12, 1)):
        bar = "#" * int(depths[r0] / peak * 40)
        print(f"  round {r0:4d} queue {depths[r0]:4d} |{bar}")

    if args.export_trace:
        tr = engine.trace(plan, 0, 0)
        tr.save(args.export_trace)
        print(f"wrote rank0/core0 tape ({tr.ops} ops) -> "
              f"{args.export_trace}")


if __name__ == "__main__":
    main()
