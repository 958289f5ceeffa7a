"""Closed-loop tape replay through any `heap.REGISTRY` backend.

    PYTHONPATH=src python -m repro.workloads.replay benchmarks/tapes/*.json \
        [--kinds all|sw,hwsw,...] [--check] [--json PATH]

Replays a recorded `Trace` as one `lax.scan` of `heap.step` over the tape:
each round's pointer operands are resolved from a *slot file* of the
pointers THIS backend returned earlier in the replay (see
`repro.workloads.trace` for the ref encoding), so the tape is a real
workload on every design point, not a transplant of foreign pointers.

Every replay emits a heap-health report: op/ok/fail counts, dropped frees
(allocator misuse can no longer vanish silently), modeled latency stats,
and the fragmentation/utilization telemetry of `repro.core.telemetry`
(live bytes, high-water mark, per-buddy-level free-block histogram,
external fragmentation, conservation residual).

``--check`` verifies the committed cross-backend contract on each tape:

  * every kind's response stream matches its committed ``expect`` digest
    bitwise (determinism across machines/runs),
  * ``sw`` == ``hwsw`` on the semantic fields (ptr/ok/path/moved — the
    metadata cache may only change latencies/counters),
  * the conservation residual is zero for every kind.

Exit code 1 on any violation — this is the CI ``workload-smoke`` step.
"""
from __future__ import annotations

import argparse
import functools
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import heap, system as sysm, telemetry
from repro.core.heap import AllocRequest
from repro.workloads.trace import Trace, response_digest, trace_lint

PARITY_PAIRS = (("sw", "hwsw", "semantic"),)


def _make_cfg(trace: Trace, kind: str) -> sysm.SystemConfig:
    return sysm.SystemConfig(kind=kind, heap_bytes=trace.heap_bytes,
                             num_threads=trace.num_threads)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _replay_scan(cfg, state, op, size, ptr_ref, ptr_raw):
    """scan heap.step over the tape, resolving refs from the slot file."""
    R, T = op.shape
    slots0 = jnp.full((R * T,), -1, jnp.int32)

    def body(carry, x):
        st, slots = carry
        r, op_r, size_r, ref_r, raw_r = x
        ptr = jnp.where(ref_r >= 0,
                        slots[jnp.clip(ref_r, 0, R * T - 1)], raw_r)
        st, resp = heap.step(cfg, st, AllocRequest(op=op_r, size=size_r,
                                                   ptr=ptr))
        # a slot records the op's SURVIVING pointer: a failed relocating
        # realloc leaves the old block intact (C contract), so later refs
        # to the realloc slot must resolve to the still-live old pointer,
        # not NULL. (Recorded tapes never ref failed-realloc slots — the
        # recorder keeps the old producing slot — so this only changes
        # resolution for planner-generated sessions, e.g. FleetServe.)
        survived = ((op_r == heap.OP_REALLOC) & (size_r > 0)
                    & (resp.ptr < 0) & (ptr >= 0))
        slots = lax.dynamic_update_slice(
            slots, jnp.where(survived, ptr, resp.ptr), (r * T,))
        return (st, slots), resp

    (state, _), resps = lax.scan(
        body, (state, slots0),
        (jnp.arange(R, dtype=jnp.int32), jnp.asarray(op), jnp.asarray(size),
         jnp.asarray(ptr_ref), jnp.asarray(ptr_raw)))
    return state, resps


def replay(trace: Trace, kind: str):
    """Replay one tape on one backend.

    Returns (resps, state, report): the stacked [R, T] AllocResponse, the
    final SystemState, and the heap-health report dict.
    """
    cfg = _make_cfg(trace, kind)
    state = heap.init(cfg)
    state, resps = _replay_scan(cfg, state, trace.op, trace.size,
                                trace.ptr_ref, trace.ptr_raw)

    op = trace.op
    path = np.asarray(resps.path)
    ok = np.asarray(resps.ok)
    lat = np.asarray(resps.latency_cyc)
    is_alloc = np.isin(op, (heap.OP_MALLOC, heap.OP_CALLOC))
    is_re = op == heap.OP_REALLOC
    re_free0 = is_re & (trace.size <= 0) & (trace.ptr_raw >= 0)
    freeish = (op == heap.OP_FREE) | re_free0
    active = op != heap.OP_NOOP
    freq = cfg.dpu.freq_hz
    round_max_cyc = lat.max(axis=1) if lat.size else np.zeros((0,))
    report = {
        "name": trace.name,
        "kind": kind,
        "rounds": trace.rounds,
        "ops": int(active.sum()),
        "ok_ops": int(ok.sum()),
        "malloc_ops": int((op == heap.OP_MALLOC).sum()),
        "calloc_ops": int((op == heap.OP_CALLOC).sum()),
        "realloc_ops": int(is_re.sum()),
        "free_ops": int((op == heap.OP_FREE).sum()),
        "failed_allocs": int(((is_alloc | is_re) & active & ~ok).sum()),
        "dropped_frees": int((freeish & (path == 2)).sum()),
        "moved_reallocs": int(np.asarray(resps.moved).sum()),
        "us_per_op": float(lat[active].mean() / freq * 1e6)
        if active.any() else 0.0,
        "max_us": float(lat.max() / freq * 1e6) if lat.size else 0.0,
        "modeled_wall_us": float(round_max_cyc.sum() / freq * 1e6),
        "meta_dram_bytes": int(np.asarray(resps.dram_bytes).sum()),
        "digest_full": response_digest(resps),
        "digest_sem": response_digest(resps, semantic_only=True),
        "telemetry": telemetry.snapshot(cfg, state),
    }
    if cfg.kind != "strawman":
        report["stats_dropped_frees"] = int(state.alloc.stats.dropped_frees)
    return resps, state, report


def replay_all_kinds(trace: Trace, kinds=None) -> dict:
    """{kind: (resps, report)} over the registry (or an explicit subset)."""
    out = {}
    for kind in (kinds or heap.kinds()):
        resps, _, report = replay(trace, kind)
        out[kind] = (resps, report)
    return out


def check_trace(trace: Trace, kinds=None, results=None) -> list:
    """Verify the cross-backend contract; returns error strings.

    ``results`` reuses a prior `replay_all_kinds` output (else replays)."""
    errs = list(trace_lint(trace))
    if results is None:
        results = replay_all_kinds(trace, kinds)
    for kind, (_, rep) in results.items():
        exp = trace.expect.get(kind)
        if exp is None:
            errs.append(f"{trace.name}/{kind}: no committed expectation "
                        "(regenerate the tape)")
        else:
            for key in ("digest_full", "digest_sem"):
                if exp.get(key) != rep[key]:
                    errs.append(f"{trace.name}/{kind}: {key} mismatch "
                                f"(expected {exp.get(key)!r:.20}..., "
                                f"got {rep[key]!r:.20}...)")
            for key in ("ok_ops", "dropped_frees"):
                if exp.get(key) != rep[key]:
                    errs.append(f"{trace.name}/{kind}: {key} "
                                f"{exp.get(key)} != {rep[key]}")
            for key in ("live_bytes", "hwm_bytes"):
                if exp.get(key) != rep["telemetry"][key]:
                    errs.append(f"{trace.name}/{kind}: telemetry {key} "
                                f"{exp.get(key)} != "
                                f"{rep['telemetry'][key]}")
        if rep["telemetry"]["conservation_residual"] != 0:
            errs.append(f"{trace.name}/{kind}: conservation residual "
                        f"{rep['telemetry']['conservation_residual']}")
    for a, b, level in PARITY_PAIRS:
        if a not in results or b not in results:
            continue
        ra, rb = results[a][1], results[b][1]
        key = "digest_full" if level == "full" else "digest_sem"
        if ra[key] != rb[key]:
            errs.append(f"{trace.name}: {a} != {b} on {level} response "
                        "stream")
    return errs


def attach_expectations(trace: Trace, kinds=None) -> dict:
    """Replay on all kinds and write the expect block; returns the reports."""
    reports = {}
    trace.expect = {}
    for kind, (_, rep) in replay_all_kinds(trace, kinds).items():
        trace.expect[kind] = {
            "digest_full": rep["digest_full"],
            "digest_sem": rep["digest_sem"],
            "ok_ops": rep["ok_ops"],
            "dropped_frees": rep["dropped_frees"],
            "live_bytes": rep["telemetry"]["live_bytes"],
            "hwm_bytes": rep["telemetry"]["hwm_bytes"],
        }
        reports[kind] = rep
    return reports


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("tapes", nargs="+", help="trace JSON files")
    ap.add_argument("--kinds", default="all",
                    help="comma-separated backend subset (default: all)")
    ap.add_argument("--check", action="store_true",
                    help="verify committed digests + cross-backend parity; "
                         "exit 1 on any mismatch")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write all reports as JSON")
    args = ap.parse_args(argv)
    kinds = None if args.kinds == "all" else tuple(args.kinds.split(","))

    all_reports, failures = {}, []
    for path in args.tapes:
        trace = Trace.load(path)
        results = replay_all_kinds(trace, kinds)
        if args.check:
            errs = check_trace(trace, kinds, results=results)
            failures.extend(errs)
            status = "OK" if not errs else f"{len(errs)} MISMATCH(ES)"
            print(f"[{status}] {path}: {trace.rounds} rounds, "
                  f"{trace.ops} ops")
            for e in errs:
                print(f"  !! {e}")
        reports = {k: rep for k, (_, rep) in results.items()}
        all_reports[trace.name] = reports
        for kind, rep in reports.items():
            tel = rep["telemetry"]
            print(f"  {trace.name}/{kind}: ok={rep['ok_ops']}/{rep['ops']} "
                  f"dropped={rep['dropped_frees']} "
                  f"us/op={rep['us_per_op']:.3f} "
                  f"live={tel['live_bytes']} hwm={tel['hwm_bytes']} "
                  f"frag={tel['external_frag']:.2f}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(all_reports, f, indent=1)
    if failures:
        print(f"{len(failures)} workload-replay check failure(s)")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
