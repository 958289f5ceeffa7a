"""Benchmark harness: one module per paper table/figure, CSV + JSON out.

    PYTHONPATH=src python -m benchmarks.run [fig5 fig6 ...] \
        [--smoke] [--json BENCH_out.json]

Prints ``name,us_per_call,derived`` CSV rows (any failure becomes a
``<fig>/ERROR`` row, and the process exits non-zero once every figure has
run and the JSON is written), and with ``--json`` also writes
the schema'd artifact CI uploads for the perf trajectory (schema documented
in benchmarks/README.md, validated here before writing). ``--smoke``
shrinks every sweep to seconds for the CI bench-smoke job. `roofline` reads
the dry-run artifacts (run repro.launch.dryrun first for that section).
"""
from __future__ import annotations

import argparse
import datetime
import importlib
import json
import numbers
import os
import platform
import subprocess
import sys
import time

from repro.runtime.compile_cache import enable_compile_cache

ALL = ("fig5", "fig6", "fig7", "fig14", "fig15", "fig16",
       "fig_fleet", "fleet_serve", "fig_decode", "workloads", "fig_arena",
       "fig_elastic", "roofline")
SCHEMA = "pim-malloc-bench/v1"
# per-record attribution stamps (the only non-numeric record fields besides
# name/derived): allocator design point and jax version
STRING_FIELDS = ("backend", "jax")

_MODULES = {
    "fig5": "fig5_design_space",
    "fig6": "fig6_heap_sweep",
    "fig7": "fig7_contention",
    "fig14": "fig14_micro",
    "fig15": "fig15_cache_size",
    "fig16": "fig16_graph",
    "fig_fleet": "fig_fleet",
    "fleet_serve": "fig_serve",
    "fig_decode": "fig_decode",
    "workloads": "fig_workloads",
    "fig_arena": "fig_arena",
    "fig_elastic": "fig_elastic",
    "roofline": "roofline",
}


def env_stamp(smoke: bool, root: str = None) -> dict:
    import jax
    if root is None:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        commit = subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            cwd=root, timeout=10).stdout.strip() or "unknown"
        # a baseline generated from an uncommitted tree must say so: the
        # stamped revision alone could not reproduce its rows. Tracked
        # files only — stray __pycache__/ dirs or editor droppings must
        # not mark a clean checkout's baseline as irreproducible.
        dirty = subprocess.run(
            ["git", "status", "--porcelain", "--untracked-files=no"],
            capture_output=True, text=True,
            cwd=root, timeout=10).stdout.strip()
        if commit != "unknown" and dirty:
            commit += "-dirty"
    except Exception:
        commit = "unknown"
    return {
        "python": sys.version.split()[0],
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "platform": platform.platform(),
        "commit": commit,
        "smoke": bool(smoke),
    }


def validate(doc: dict) -> list:
    """Schema check for the JSON artifact; returns a list of error strings."""
    errs = []
    if doc.get("schema") != SCHEMA:
        errs.append(f"schema != {SCHEMA}")
    env = doc.get("env")
    if not isinstance(env, dict):
        errs.append("env missing")
    else:
        for k in ("python", "jax", "backend", "device_count", "commit",
                  "smoke"):
            if k not in env:
                errs.append(f"env.{k} missing")
    figs = doc.get("figs")
    if not isinstance(figs, dict) or not figs:
        errs.append("figs missing/empty")
        return errs
    for fig, cell in figs.items():
        if cell.get("status") not in ("ok", "error"):
            errs.append(f"figs.{fig}.status invalid")
        if not isinstance(cell.get("wall_s"), numbers.Number):
            errs.append(f"figs.{fig}.wall_s missing")
        recs = cell.get("records")
        if not isinstance(recs, list):
            errs.append(f"figs.{fig}.records not a list")
            continue
        names = [r.get("name") for r in recs]
        for dup in sorted({n for n in names if names.count(n) > 1}):
            errs.append(f"figs.{fig} duplicate record name {dup!r}")
        for i, r in enumerate(recs):
            if not isinstance(r.get("name"), str):
                errs.append(f"figs.{fig}.records[{i}].name missing")
            if not isinstance(r.get("us_per_call"), numbers.Number):
                errs.append(f"figs.{fig}.records[{i}].us_per_call missing")
            if not isinstance(r.get("derived", ""), str):
                errs.append(f"figs.{fig}.records[{i}].derived not a string")
            for k, v in r.items():
                if k in ("name", "derived"):
                    continue
                if k in STRING_FIELDS:  # attribution stamps
                    if not isinstance(v, str):
                        errs.append(f"figs.{fig}.records[{i}].{k} not a string")
                    continue
                if not isinstance(v, numbers.Number):
                    errs.append(f"figs.{fig}.records[{i}].{k} not numeric")
    return errs


def run_fig(name: str, smoke: bool) -> dict:
    t0 = time.time()
    try:
        m = importlib.import_module(f".{_MODULES[name]}", package=__package__)
        records = m.bench(smoke=smoke)
        status, error = "ok", None
    except Exception as e:  # keep the harness going; report the failure
        print(f"{name}/ERROR,0,{type(e).__name__}: {e}", flush=True)
        records, status, error = [], "error", f"{type(e).__name__}: {e}"
    cell = {"status": status, "wall_s": round(time.time() - t0, 2),
            "records": records}
    if error:
        cell["error"] = error
    print(f"# {name} done in {cell['wall_s']:.1f}s", flush=True)
    return cell


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("figs", nargs="*", help=f"subset of {ALL}")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sweeps for CI (seconds, not minutes)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write the schema'd BENCH_*.json artifact here")
    args = ap.parse_args(argv)
    enable_compile_cache()
    which = list(dict.fromkeys(args.figs)) or list(ALL)
    for name in which:
        if name not in _MODULES:
            raise SystemExit(f"unknown benchmark {name} (have {ALL})")

    print("name,us_per_call,derived")
    figs = {name: run_fig(name, args.smoke) for name in which}

    doc = {
        "schema": SCHEMA,
        "generated_at": datetime.datetime.now(
            datetime.timezone.utc).isoformat(timespec="seconds"),
        "env": env_stamp(args.smoke),
        "figs": figs,
    }
    errs = validate(doc)
    if errs:
        raise SystemExit("schema-invalid bench doc: " + "; ".join(errs))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=1)
        print(f"# wrote {args.json}", flush=True)
    failed = [name for name, cell in figs.items() if cell["status"] == "error"]
    if failed:
        raise SystemExit(f"benchmark figures failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
