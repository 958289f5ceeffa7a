"""Bring-up smoke run of the fleet serving path on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # rank-sharded fleet over four chips

One chip: serves the paper's UPMEM deployment (`repro.configs.paper_upmem`:
8 ranks x 64 cores = 512 cores, 16 tasklets, 32 MiB heaps) through the public
`FleetServe` API with the paper's `hwsw` design point: host planning, one
donated `lax.scan` on the device, then the host report. It checks that no
free was dropped, that every core's heap conserves its bytes, that sampled
(rank, core) tapes agree op for op with the plain-Python `PyPimMalloc`
oracle, and that the final state lies on the TPU. Then one shorter session
runs per other kind at the same geometry. Every session is served
twice: the second serve times the compiled scan alone and must answer as
the first did.

``--chips 4`` runs only the rank-sharded session: the same fleet over a 1-D
"ranks" mesh of four chips (2 ranks per chip), compared with the same plan
served unsharded (``mesh=False``); responses and reports must be equal. It
prints each device's share of the state.

The phase lines are bring-up observations, not benchmark numbers. The last
line of stdout is one JSON object naming the device. Any failed check exits
non-zero, and without a TPU nothing runs.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax
import numpy as np

from repro.configs.paper_upmem import CONFIG as PAPER
from repro.core import system as sysm
from repro.core.heap import OP_NOOP, OP_REALLOC
from repro.core.oracle import PyPimMalloc
from repro.launch.serve_fleet import FleetServe, TrafficConfig
from repro.launch.serving import response_host
from repro.parallel.meshctx import make_rank_mesh
from repro.runtime.compile_cache import enable_compile_cache

RANKS = 8                                 # UPMEM ranks of 64 DPUs each
CORES = PAPER.n_cores // RANKS            # 64
# 4 tenants per core, so every core gets work; 2048 arrivals per round offer
# a quarter of the 8192 thread slots, though the Zipf tenant skew piles them
# onto the hot tenants' cores
MAIN_TRAFFIC = TrafficConfig(seed=0, rounds=64, num_tenants=2048,
                             arrival_rate=2048, queue_cap=8192)
KIND_ROUNDS = 16
OTHER_KINDS = ("sw", "strawman", "sanitizer", "arena", "tlregion")
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class SmokeFailure(SystemExit):
    """A failed check: exits the process non-zero with its message."""


def check(ok: bool, what: str):
    if not ok:
        raise SmokeFailure(f"chip_smoke FAILED: {what}")


def require_tpu():
    """Refuse to run anything unless JAX's first device is a TPU."""
    dev = jax.devices()[0]
    check(dev.platform == "tpu",
          f"no TPU: JAX's first device is {dev.platform!r}; nothing was run")
    return dev


@contextlib.contextmanager
def compile_log():
    """Counts XLA backend compiles (and their seconds) inside the block."""
    log = {"count": 0, "seconds": 0.0}

    def on_event(event, duration, **_):
        if event == BACKEND_COMPILE_EVENT:
            log["count"] += 1
            log["seconds"] += duration

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        yield log
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)


def serve(engine, plan):
    """Run one planned session; the time ends at `block_until_ready`."""
    t0 = time.perf_counter()
    state, resps = engine.run(plan)
    jax.block_until_ready((state, resps))
    return state, resps, time.perf_counter() - t0


def peak_bytes(dev) -> str:
    stats = dev.memory_stats()
    if not stats or "peak_bytes_in_use" not in stats:
        return "peak_bytes_in_use not reported"
    return f"peak_bytes_in_use={stats['peak_bytes_in_use']}"


def check_report(tag: str, rep: dict):
    print(f"{tag}: offered={rep['offered']} dropped={rep['dropped']} "
          f"dispatched={rep['dispatched']} ok_ops={rep['ok_ops']} "
          f"failed_allocs={rep['failed_allocs']} "
          f"dropped_frees={rep['dropped_frees']} "
          f"conservation_residual={rep['conservation_residual']} "
          f"live_bytes={rep['live_bytes']}", flush=True)
    check(rep["dispatched"] > 0, f"{tag}: no op was dispatched")
    check(rep["dropped_frees"] == 0, f"{tag}: dropped_frees != 0")
    check(rep["conservation_residual"] == 0,
          f"{tag}: conservation_residual != 0")


def check_on_device(tag: str, state, dev):
    """Every leaf of the final state lies on `dev` alone."""
    for leaf in jax.tree.leaves(state):
        check(leaf.devices() == {dev},
              f"{tag}: a state leaf lies on {leaf.devices()}, not {dev}")
    print(f"{tag}: final state on {dev} ({dev.platform})", flush=True)


def oracle_cores(R: int, C: int) -> list:
    """Four spread (rank, core) slices: the corners and two interior cores."""
    picks = [(0, 0), (R // 3, C // 3), ((2 * R) // 3, (2 * C) // 3),
             (R - 1, C - 1)]
    return list(dict.fromkeys(picks))


def check_oracle(engine, plan, host: dict, rank: int, core: int) -> int:
    """Replay (rank, core)'s tape through `PyPimMalloc`, op for op.

    Pointer operands resolve against the oracle's own returned pointers
    with the scan's slot rule (a failed relocating realloc keeps the old
    block), so the replay is closed-loop on the oracle's side. Returns the
    number of ops compared.
    """
    pm = engine.cfg.pm
    tape = engine.trace(plan, rank, core)
    py = PyPimMalloc(heap_bytes=pm.heap_bytes, num_threads=pm.num_threads,
                     size_classes=pm.size_classes, block_bytes=pm.block_bytes,
                     cap=pm.cap)
    T = tape.num_threads
    slots = np.full((tape.rounds * T,), -1, np.int64)
    for r in range(tape.rounds):
        ref = tape.ptr_ref[r]
        ptr = np.where(ref >= 0, slots[np.clip(ref, 0, slots.shape[0] - 1)],
                       tape.ptr_raw[r])
        want = py.request(tape.op[r].tolist(), tape.size[r].tolist(),
                          ptr.tolist())
        for f in ("ptr", "ok", "path", "moved"):
            got = host[f][r, rank, core]
            check(np.array_equal(got, np.asarray(want[f], got.dtype)),
                  f"oracle: rank {rank} core {core} round {r} field {f}: "
                  f"served {got.tolist()} != oracle {want[f]}")
        out = np.asarray(want["ptr"])
        survived = ((tape.op[r] == OP_REALLOC) & (tape.size[r] > 0)
                    & (out < 0) & (ptr >= 0))
        slots[r * T:(r + 1) * T] = np.where(survived, ptr, out)
    return int((tape.op != OP_NOOP).sum())


def serve_again(tag: str, engine, plan, host: dict) -> str:
    """Serve `plan` once more, which times the compiled scan alone; it must
    answer as `host` did. Returns the timing text."""
    with compile_log() as warm:
        state, resps, seconds = serve(engine, plan)
    again = response_host(resps)
    del state, resps
    gc.collect()
    check(all(np.array_equal(again[f], host[f]) for f in host),
          f"{tag}: a second serve of the same plan answered differently")
    return f"{seconds:.3f}s ({warm['count']} compiles)"


def session(tag: str, cfg, R: int, C: int, traffic: TrafficConfig, dev):
    """Plan, serve and report one session on `dev`, checking the state's
    placement and the report, then serve the plan again. Prints the
    timings; returns (engine, plan, host responses, report)."""
    engine = FleetServe(cfg, R, C, traffic=traffic)
    t0 = time.perf_counter()
    plan = engine.plan()
    plan_s = time.perf_counter() - t0
    with compile_log() as first:
        state, resps, first_s = serve(engine, plan)
    check_on_device(tag, state, dev)
    t0 = time.perf_counter()
    rep = engine.report(plan, resps, state)
    report_s = time.perf_counter() - t0
    check_report(tag, rep)
    host = response_host(resps)
    del state, resps
    gc.collect()
    again = serve_again(tag, engine, plan, host)
    print(f"{tag} rounds={traffic.rounds}: plan {plan_s:.3f}s | first call "
          f"{first_s:.3f}s ({first['count']} compiles, "
          f"{first['seconds']:.3f}s compiling) | report {report_s:.3f}s | "
          f"scan again {again} | {peak_bytes(dev)}", flush=True)
    return engine, plan, host, rep


def main_phase(cfg, R: int, C: int, traffic: TrafficConfig, dev) -> dict:
    """The paper's fleet through FleetServe, checked against the oracle."""
    tag = f"main {cfg.kind} {R}x{C}x{cfg.num_threads} heap={cfg.heap_bytes}"
    engine, plan, host, rep = session(tag, cfg, R, C, traffic, dev)
    compared = {(rk, ck): check_oracle(engine, plan, host, rk, ck)
                for rk, ck in oracle_cores(R, C)}
    print(f"{tag}: PyPimMalloc oracle agrees op for op on "
          + ", ".join(f"r{rk}c{ck} ({n} ops)"
                      for (rk, ck), n in compared.items()), flush=True)
    return rep


def kind_phase(cfg, R: int, C: int, traffic: TrafficConfig, dev) -> dict:
    """One shorter session of another kind at the same geometry."""
    tag = f"kind {cfg.kind} {R}x{C}x{cfg.num_threads} heap={cfg.heap_bytes}"
    return session(tag, cfg, R, C, traffic, dev)[3]


def device_shares(state) -> dict:
    """{device: bytes of the state held there}."""
    shares = {}
    for leaf in jax.tree.leaves(state):
        for shard in leaf.addressable_shards:
            shares[shard.device] = shares.get(shard.device, 0) + shard.data.nbytes
    return shares


def sharded_phase(cfg, R: int, C: int, traffic: TrafficConfig) -> dict:
    """The fleet rank-sharded over every device vs mesh=False, each served
    twice (the second serve times the compiled scan alone)."""
    mesh = make_rank_mesh(R)
    tag = (f"sharded {cfg.kind} {R}x{C}x{cfg.num_threads} "
           f"heap={cfg.heap_bytes} over {mesh.devices.size} devices")
    sharded = FleetServe(cfg, R, C, traffic=traffic, mesh=mesh)
    t0 = time.perf_counter()
    plan = sharded.plan()
    plan_s = time.perf_counter() - t0
    with compile_log() as first:
        state, resps, sharded_s = serve(sharded, plan)
    shares = device_shares(state)
    total = sum(shares.values())
    for d in mesh.devices.flat:
        print(f"{tag}: {d} holds {shares.get(d, 0)} of {total} state bytes",
              flush=True)
    check(set(shares) == set(mesh.devices.flat),
          f"{tag}: state lies on {sorted(map(str, shares))}, not the mesh")
    check(len(set(shares.values())) == 1,
          f"{tag}: the state is not spread evenly: {shares}")
    rep_sharded = sharded.report(plan, resps, state)
    host_sharded = response_host(resps)
    del state, resps
    gc.collect()
    sharded_again = serve_again(tag, sharded, plan, host_sharded)

    flat = FleetServe(cfg, R, C, traffic=traffic, mesh=False)
    with compile_log() as flat_log:
        state, resps, flat_s = serve(flat, plan)
    rep_flat = flat.report(plan, resps, state)
    host_flat = response_host(resps)
    del state, resps
    gc.collect()
    flat_again = serve_again(tag, flat, plan, host_flat)
    check_report(tag, rep_sharded)
    check(all(np.array_equal(host_sharded[f], host_flat[f])
              for f in host_flat),
          f"{tag}: responses differ from the mesh=False session")
    check(rep_sharded == rep_flat,
          f"{tag}: report differs from the mesh=False session")
    print(f"{tag}: responses and report equal to mesh=False "
          f"({rep_flat['dispatched']} ops)", flush=True)
    peaks = ", ".join(f"{d}: {peak_bytes(d)}" for d in mesh.devices.flat)
    print(f"{tag} rounds={traffic.rounds}: plan {plan_s:.3f}s | sharded "
          f"first call {sharded_s:.3f}s ({first['count']} compiles, "
          f"{first['seconds']:.3f}s compiling), again {sharded_again} | "
          f"mesh=False first call {flat_s:.3f}s ({flat_log['count']} "
          f"compiles, {flat_log['seconds']:.3f}s compiling), again "
          f"{flat_again} | {peaks}", flush=True)
    return rep_sharded


def main(argv=None):
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the rank-sharded four-chip phase")
    args = ap.parse_args(argv)
    cache = enable_compile_cache()
    dev = require_tpu()
    warm = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    print(f"device: {dev.device_kind} x{len(jax.devices())}, "
          f"jax {jax.__version__}, compile cache {cache} "
          f"({warm} entries before this run)", flush=True)

    # SystemConfig's defaults are the paper's Table 3
    table3 = dict(heap_bytes=PAPER.heap_bytes, num_threads=PAPER.num_threads)
    hwsw = sysm.SystemConfig(kind="hwsw", **table3)
    if args.chips == 4:
        check(len(jax.devices()) == 4,
              f"--chips 4 needs four devices, JAX has {len(jax.devices())}")
        sharded_phase(hwsw, RANKS, CORES, MAIN_TRAFFIC)
    else:
        main_phase(hwsw, RANKS, CORES, MAIN_TRAFFIC, dev)
        short = dataclasses.replace(MAIN_TRAFFIC, rounds=KIND_ROUNDS)
        for kind in OTHER_KINDS:
            kind_phase(sysm.SystemConfig(kind=kind, **table3), RANKS, CORES,
                       short, dev)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
