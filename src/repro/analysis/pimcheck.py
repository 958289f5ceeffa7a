"""pimcheck: static verifier for the allocator backends + tape linter.

Traces every registered backend step (`heap.REGISTRY`) with
`jax.make_jaxpr` — at the single-core tier, the vmapped multi-core tier,
and the shard_map-body fleet tier — and runs the checker passes from
`repro.analysis.passes` over the closed jaxprs. Also lints trace tapes
(`workloads.trace.trace_lint`) and self-tests the passes against the
seeded-bug fixtures.

CLI (the CI `analysis` lane):

    python -m repro.analysis.pimcheck --all-kinds --tapes
    python -m repro.analysis.pimcheck --fixtures
    python -m repro.analysis.pimcheck --kinds sw,hwsw --tiers single

Exit code is non-zero on any unsuppressed finding, tape-lint error, or
fixture the passes fail to flag. Findings are printed per target and,
when `$GITHUB_STEP_SUMMARY` is set, appended there as a markdown table
(same convention as `benchmarks/perf_gate.py`).
"""
from __future__ import annotations

import argparse
import functools
import glob
import json
import os
import sys

import jax
import jax.numpy as jnp

from repro.core import heap, system as sysm
from repro.workloads.trace import Trace, trace_lint

from .fixtures import FIXTURES, fix_init, fix_request
from .passes import PASS_NAMES, TracedStep, run_passes

TIERS = ("single", "vmap", "sharded")
DEFAULT_TAPES = os.path.join(
    os.path.dirname(__file__), os.pardir, os.pardir, os.pardir,
    "benchmarks", "tapes", "*.json")


def _mixed_request(num_threads: int) -> heap.AllocRequest:
    """A representative round exercising every op class, so tracing
    covers the malloc, free, realloc and calloc paths at once."""
    ops = [heap.OP_MALLOC, heap.OP_FREE, heap.OP_REALLOC, heap.OP_CALLOC,
           heap.OP_NOOP]
    mk = [64, 0, 256, 16, 0]
    pt = [-1, 4096, 8192, -1, -1]
    reps = (num_threads + len(ops) - 1) // len(ops)
    return heap.AllocRequest(
        op=jnp.array((ops * reps)[:num_threads], jnp.int32),
        size=jnp.array((mk * reps)[:num_threads], jnp.int32),
        ptr=jnp.array((pt * reps)[:num_threads], jnp.int32))


def _traced(fn, args, target, tier) -> TracedStep:
    out_shape = jax.eval_shape(fn, *args)
    closed = jax.make_jaxpr(fn)(*args)
    return TracedStep(
        target=target, tier=tier, closed_jaxpr=closed,
        n_state_in=len(jax.tree.leaves(args[0])),
        n_state_out=len(jax.tree.leaves(out_shape[0])))


def trace_kind(kind: str, tier: str = "single", heap_bytes: int = 1 << 18,
               num_threads: int = 4) -> TracedStep:
    """Trace one backend step at one deployment tier."""
    cfg = sysm.SystemConfig(kind=kind, heap_bytes=heap_bytes,
                            num_threads=num_threads)
    req = _mixed_request(num_threads)
    if tier == "single":
        fn = functools.partial(heap.step, cfg)
        args = (heap.init(cfg), req)
    elif tier == "vmap":
        fn = functools.partial(heap.multicore_step, cfg)
        args = (heap.multicore_init(cfg, 2),
                jax.tree.map(lambda x: jnp.stack([x, x]), req))
    elif tier == "sharded":
        # the shard_map body of a fleet round: vmap over ranks of the
        # multi-core step (heap.sharded_step)
        fn = functools.partial(heap.sharded_step, cfg)
        args = (heap.sharded_init(cfg, 2, 2),
                jax.tree.map(lambda x: jnp.stack([jnp.stack([x, x])] * 2),
                             req))
    else:
        raise ValueError(f"unknown tier {tier!r} (want one of {TIERS})")
    return _traced(fn, args, kind, tier)


def trace_fixture(name: str) -> TracedStep:
    fn, _expect = FIXTURES[name]
    return _traced(fn, (fix_init(), fix_request()), f"fixture:{name}",
                   "single")


def check_kinds(kinds, tiers, passes=None, heap_bytes=1 << 18,
                num_threads=4):
    """Run the passes over (kind, tier) pairs; returns (rows, active,
    suppressed) where rows summarize per-target results."""
    rows, active, suppressed = [], [], []
    for kind in kinds:
        for tier in tiers:
            tr = trace_kind(kind, tier, heap_bytes, num_threads)
            act, sup = run_passes(tr, passes)
            active.extend(act)
            suppressed.extend(sup)
            rows.append({
                "target": kind, "tier": tier,
                "eqns": len(tr.jaxpr.eqns),
                "findings": len(act), "suppressed": len(sup),
            })
    return rows, active, suppressed


def check_fixtures(passes=None):
    """Self-test: every seeded-bug fixture must be flagged by its pass.

    Returns (rows, failures) — a failure is a fixture the passes missed.
    """
    rows, failures = [], []
    for name, (_fn, expect_pass) in FIXTURES.items():
        tr = trace_fixture(name)
        act, _sup = run_passes(tr, passes)
        hit = [f for f in act if f.pass_name == expect_pass]
        if not hit:
            failures.append(f"fixture {name}: expected a {expect_pass} "
                            "finding, got "
                            f"{[f.pass_name for f in act] or 'none'}")
        rows.append({"target": f"fixture:{name}", "tier": "single",
                     "eqns": len(tr.jaxpr.eqns),
                     "findings": len(act),
                     "flagged_by_expected": bool(hit)})
    return rows, failures


def lint_tapes(paths):
    """trace_lint every tape; returns (rows, errors)."""
    rows, errors = [], []
    for path in paths:
        try:
            trace = Trace.load(path)
            errs = trace_lint(trace)
        except (ValueError, KeyError, OSError) as e:
            errs = [f"unreadable tape: {e}"]
            trace = None
        errors.extend(f"{os.path.basename(path)}: {e}" for e in errs)
        rows.append({"target": f"tape:{os.path.basename(path)}",
                     "tier": "-",
                     "rounds": trace.rounds if trace else 0,
                     "findings": len(errs)})
    return rows, errors


def _step_summary(rows, active, suppressed, tape_errors, fixture_failures):
    lines = ["## pimcheck", "",
             "| target | tier | findings | suppressed |",
             "|---|---|---:|---:|"]
    for r in rows:
        lines.append(f"| {r['target']} | {r['tier']} | {r['findings']} | "
                     f"{r.get('suppressed', 0)} |")
    lines.append("")
    for f in active:
        lines.append(f"- ❌ {f.fmt()}")
    for f, reason in suppressed:
        lines.append(f"- ⚠️ suppressed: {f.fmt()} — {reason}")
    for e in tape_errors:
        lines.append(f"- ❌ tape lint: {e}")
    for e in fixture_failures:
        lines.append(f"- ❌ {e}")
    if not (active or tape_errors or fixture_failures):
        lines.append("- ✅ all passes green")
    return "\n".join(lines) + "\n"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="pimcheck", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--all-kinds", action="store_true",
                    help="verify every kind in heap.REGISTRY")
    ap.add_argument("--kinds", default=None,
                    help="comma-separated backend subset")
    ap.add_argument("--tiers", default=",".join(TIERS),
                    help=f"comma-separated tiers (default {','.join(TIERS)})")
    ap.add_argument("--passes", default=None,
                    help=f"comma-separated pass subset of {PASS_NAMES}")
    ap.add_argument("--tapes", nargs="*", default=None, metavar="PATH",
                    help="lint trace tapes (no paths: benchmarks/tapes/*)")
    ap.add_argument("--fixtures", action="store_true",
                    help="self-test the passes on the seeded-bug fixtures")
    ap.add_argument("--heap-bytes", type=int, default=1 << 18)
    ap.add_argument("--threads", type=int, default=4)
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write the full report as JSON")
    args = ap.parse_args(argv)

    kinds = ()
    if args.all_kinds:
        kinds = heap.kinds()
    elif args.kinds:
        kinds = tuple(args.kinds.split(","))
    tiers = tuple(args.tiers.split(","))
    passes = tuple(args.passes.split(",")) if args.passes else None

    rows, active, suppressed = check_kinds(
        kinds, tiers, passes, args.heap_bytes, args.threads)
    for f in active:
        print(f"FINDING {f.fmt()}")
    for f, reason in suppressed:
        print(f"suppressed {f.fmt()}\n  reason: {reason}")

    tape_rows, tape_errors = [], []
    if args.tapes is not None:
        paths = args.tapes or sorted(glob.glob(DEFAULT_TAPES))
        tape_rows, tape_errors = lint_tapes(paths)
        for e in tape_errors:
            print(f"TAPE LINT {e}")
    rows += tape_rows

    fixture_failures = []
    if args.fixtures:
        fx_rows, fixture_failures = check_fixtures(passes)
        rows += fx_rows
        for e in fixture_failures:
            print(f"FIXTURE MISS {e}")

    for r in rows:
        print(f"  {r['target']:<28} {r['tier']:<8} "
              f"findings={r['findings']} suppressed={r.get('suppressed', 0)}")

    report = {
        "rows": rows,
        "findings": [f.fmt() for f in active],
        "suppressed": [{"finding": f.fmt(), "reason": r}
                       for f, r in suppressed],
        "tape_errors": tape_errors,
        "fixture_failures": fixture_failures,
    }
    if args.json:
        with open(args.json, "w") as fp:
            json.dump(report, fp, indent=1)
    summary_path = os.environ.get("GITHUB_STEP_SUMMARY")
    if summary_path:
        with open(summary_path, "a") as fp:
            fp.write(_step_summary(rows, active, suppressed, tape_errors,
                                   fixture_failures))

    bad = len(active) + len(tape_errors) + len(fixture_failures)
    print(f"pimcheck: {len(rows)} target(s), {bad} failure(s), "
          f"{len(suppressed)} suppressed")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
