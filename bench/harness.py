"""One run of one cell: set-up, the measured window (traced or not), the
checks, and the result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the cell's entry and runs its warm-up (`entry.warm`, else one
whole session of the cell), which compiles (or loads from the compile
cache) every program the window uses. The window then serves sessions
back to back (see `window`). With `--trace 1` the window runs under the
profiler, and the per-layer metrics are printed in place of the
end-to-end ones. After that the answers of every session served, the
warm-up's too, are checked (see `checks`).
"""
from __future__ import annotations

import contextlib
import json
import os
import shutil
import sys
import time
import types

from bench import chip, checks, faults, spec, xplane
from bench.window import Spans, run_window, session_seed

SPAN_NAMES = ("plan", "run", "report", "readback")


def system_config(config: dict):
    """The program's SystemConfig for a configuration file."""
    from repro.core import system as sysm
    from repro.core.buddy_cache import BuddyCacheConfig, SWBufferConfig
    from repro.core.pim_malloc import PimMallocConfig
    pm = PimMallocConfig(heap_bytes=config["heap_bytes"],
                         num_threads=config["num_threads"],
                         size_classes=tuple(config["size_classes"]),
                         block_bytes=config["block_bytes"],
                         cap=config["freelist_cap"])
    kw = dict(kind=config["kind"], heap_bytes=config["heap_bytes"],
              num_threads=config["num_threads"], pm=pm)
    if "buddy_cache_entries" in config:
        kw["bc"] = BuddyCacheConfig(n_entries=config["buddy_cache_entries"])
    if "sw_buffer_bytes" in config:
        kw["sw_buf"] = SWBufferConfig(
            buf_bytes=config["sw_buffer_bytes"],
            line_bytes=config["sw_buffer_line_bytes"])
    return sysm.SystemConfig(**kw)


def check_sessions(cell, entry, records) -> dict:
    """Every guarantee and every core's answers against the reference,
    over the whole fleet of every session, and the entry's own report
    readings."""
    reference = cell.module("reference", cell.config["reference"])
    totals = {"overlapping_blocks": 0, "dropped_frees": 0,
              "unanswered_ops": 0, "reference_mismatches": 0}
    for rec in records:
        host = entry.host_answers(rec)
        g = checks.guarantees(cell.config, rec["grids"], host)
        for k in ("overlapping_blocks", "dropped_frees", "unanswered_ops"):
            totals[k] += g[k]
        R, C = rec["grids"]["op"].shape[1:3]
        bad, _ = checks.reference_mismatches(reference, cell.config,
                                             rec["grids"], host, range(R * C))
        totals["reference_mismatches"] += bad
    totals.update(entry.report_numbers(records))
    return totals


@contextlib.contextmanager
def _profiled(trace_dir, log):
    """The block under `jax.profiler`, tracing into `trace_dir`; nothing
    where `trace_dir` is None."""
    if trace_dir is None:
        yield
        return
    import jax
    shutil.rmtree(trace_dir, ignore_errors=True)
    # host spans and device ops only: the Python tracer would slow the
    # host-bound layers it is meant to attribute
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        yield
    finally:
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        print(f"trace written in {time.perf_counter() - t0:.3f}s", file=log,
              flush=True)


def run_cell(root, workload: str, seed: int, seconds: float, trace: bool,
             t_start: float, check_chip: bool = True, fault: str = None,
             log=sys.stderr) -> dict:
    """Runs the cell and returns its result line as a dict."""
    cell = spec.load_cell(root, workload)
    chip.use_compile_cache(root)
    import jax
    devices = (chip.require_tpu(cell.chips) if check_chip
               else jax.devices()[:cell.chips])
    cfg = system_config(cell.config)
    entry = cell.module("entries", cell.traffic["entry"]).Entry(
        cfg, cell.config, cell.traffic)
    if fault:
        faults.plant(entry.engine, fault, min(cell.config["size_classes"]))

    t0 = time.perf_counter()
    warm = getattr(entry, "warm", entry.session)(session_seed(seed, 0),
                                                 Spans())
    warm_s = time.perf_counter() - t0
    setup_s = time.perf_counter() - t_start
    print(f"set-up {setup_s:.3f}s (warm-up {warm_s:.3f}s)", file=log,
          flush=True)

    spans = Spans()
    trace_dir = os.path.join(root, "results", "bench_trace", workload)
    with chip.compile_log() as compiles, _profiled(trace_dir if trace
                                                   else None, log):
        win = run_window(lambda i: entry.session(session_seed(seed, i), spans),
                         seconds, first_index=1)
    print(f"window {win.wall_s:.3f}s: {len(win.sessions)} sessions, "
          f"{win.ops} ops, {compiles['count']} compiles, {compiles['loads']} "
          "programs loaded from the compile cache", file=log,
          flush=True)
    records = [warm.record] + [s.record for s in win.sessions]

    summary = None
    if trace:
        t0 = time.perf_counter()
        path = xplane.latest_xplane(trace_dir)
        summary = path and xplane.summarize(xplane.extract(path, SPAN_NAMES))
        shutil.rmtree(trace_dir, ignore_errors=True)
        print(f"trace reduced in {time.perf_counter() - t0:.3f}s", file=log,
              flush=True)

    peak = chip.peak_bytes(devices)
    numbers = check_sessions(cell, entry, records)
    del entry, records

    ctx = types.SimpleNamespace(
        window=win, setup_s=setup_s, spans=dict(spans.totals),
        compiles_in_window=compiles["count"],
        cache_loads_in_window=compiles["loads"], trace=summary,
        traced_rounds=win.rounds if trace else 0, peak_bytes=peak)
    metrics = {}
    for m in cell.metrics:
        if m.end_to_end == bool(trace):
            continue
        value = cell.module("metrics", m.name).read(ctx)
        if value is not None:
            metrics[m.name] = {"value": value, "unit": m.unit}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {"correct": all(v <= checks.LIMITS[k]
                             for k, v in numbers.items()),
              "attempted": win.attempted, "failed": win.failed,
              "metrics": metrics, "device": device}
    if summary:
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    result["checks"] = {k: {"value": v, "limit": checks.LIMITS[k]}
                        for k, v in numbers.items()}
    return result


def main(argv=None, root=None, t_start=None):
    import argparse
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = root or os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))
    result = run_cell(root, args.workload, args.seed, args.seconds,
                      bool(args.trace), t_start)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
