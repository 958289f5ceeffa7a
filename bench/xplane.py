"""Reduces a profiler trace of one session to device busy time, the scan
program's device time, the top device operations and the idle gaps.

`extract` reads the `.xplane.pb` that `jax.profiler` writes into plain
intervals; `summarize` does the arithmetic on those, so it can be checked
on intervals made by hand.

- The traced window runs from the first host span's start to the last
  one's end (the benchmark's spans: plan, run, report, readback).
- Busy time is the union of the device's program (XLA module) intervals
  inside the window, averaged over the devices traced.
- The scan program is the module with the most device time inside the
  `run` spans; its busy time is the union of its intervals in the window.
- An idle gap is a stretch of the window in which no program runs on the
  device, named by the host span open at its middle ("host" where none is).
- The top operations sum the time of each HLO instruction ("%while.492",
  not the whole instruction text the TPU trace gives) over the first
  `MAX_OPS` operations of each device: a session of the fleet step runs
  about 117k device operations a round, and reading every one of them
  from Python would take minutes.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import itertools
import os

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10
MAX_OPS = 2_000_000


@dataclasses.dataclass
class Extract:
    host_spans: list          # [(name, start_ns, end_ns)]
    device_ops: dict          # plane name -> [(op name, start_ns, end_ns)],
                              # the first MAX_OPS of the plane
    modules: dict             # plane name -> [(module name, start, end)]


def latest_xplane(trace_dir) -> str | None:
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


def op_name(text: str) -> str:
    """`%fusion.3 = f32[8]{0} fusion(...)` -> `%fusion.3`."""
    return text.split(" = ", 1)[0]


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name


def extract(path, span_names, max_ops: int = MAX_OPS) -> Extract:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    spans, ops, modules = [], {}, {}
    for plane in data.planes:
        device = _is_device_plane(plane.name)
        for line in plane.lines:
            if device and line.name in (OPS_LINE, MODULES_LINE):
                events = line.events
                if line.name == OPS_LINE:
                    out, name = ops, op_name
                    events = itertools.islice(events, max_ops)
                else:
                    out, name = modules, str
                out[plane.name] = [
                    (name(ev.name), ev.start_ns, ev.start_ns + ev.duration_ns)
                    for ev in events]
            elif plane.name.startswith("/host:"):
                spans.extend(
                    (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    for ev in line.events if ev.name in span_names)
    return Extract(host_spans=sorted(spans, key=lambda s: s[1]),
                   device_ops=ops, modules=modules)


def union(intervals) -> list:
    """Merged, sorted [(start, end)] covering the same points."""
    out = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _length(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def _inside(ops, windows) -> list:
    """Parts of the op intervals that lie inside any of `windows`."""
    out = []
    for lo, hi in union(windows):
        out.extend(_clip(ops, lo, hi))
    return out


def scan_module(ex: Extract) -> str | None:
    """The module with the most device time inside the `run` spans."""
    runs = [(s, e) for n, s, e in ex.host_spans if n == "run"]
    total = collections.Counter()
    for mods in ex.modules.values():
        for name, s, e in mods:
            total[name] += _length(_inside([(s, e)], runs))
    best = total.most_common(1)
    return best[0][0] if best and best[0][1] > 0 else None


def _merged(starts, ends):
    """Union of intervals as sorted, disjoint (starts, ends) arrays."""
    keep = ends > starts
    starts, ends = starts[keep], ends[keep]
    order = np.argsort(starts, kind="stable")
    starts, ends = starts[order], ends[order]
    if not starts.size:
        return starts, ends
    reach = np.maximum.accumulate(ends)
    new = np.ones(starts.size, bool)
    new[1:] = starts[1:] > reach[:-1]
    first = np.flatnonzero(new)
    last = np.append(first[1:] - 1, starts.size - 1)
    return starts[first], reach[last]


def summarize(ex: Extract) -> dict | None:
    """Seconds of window, busy and scan-busy time, top ops and idle gaps;
    None where the trace holds no host span or no device program."""
    if not ex.host_spans or not any(ex.modules.values()):
        return None
    lo = min(s for _, s, _ in ex.host_spans)
    hi = max(e for _, _, e in ex.host_spans)
    module = scan_module(ex)
    busy, scan_busy, op_time = [], [], collections.Counter()
    gaps = []
    for plane, mods in sorted(ex.modules.items()):
        starts = np.clip(np.array([s for _, s, _ in mods], np.float64), lo, hi)
        ends = np.clip(np.array([e for _, _, e in mods], np.float64), lo, hi)
        ms, me = _merged(starts, ends)
        busy.append(float((me - ms).sum()))
        scan = np.array([n == module for n, _, _ in mods], bool)
        cs, ce = _merged(starts[scan], ends[scan])
        scan_busy.append(float((ce - cs).sum()))
        for name, s, e in ex.device_ops.get(plane, ()):
            op_time[name] += max(0, min(e, hi) - max(s, lo))
        edges = np.concatenate([[lo], np.stack([ms, me], 1).ravel(), [hi]])
        gaps.extend((float(edges[i]), float(edges[i + 1]))
                    for i in range(0, edges.size, 2)
                    if edges[i + 1] > edges[i])
    n = len(busy)
    top_gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / n * 1e-9,
        "scan_module": module,
        "scan_busy_s": sum(scan_busy) / n * 1e-9,
        "device_ops": [[name, t / n * 1e-9]
                       for name, t in op_time.most_common(TOP) if t > 0],
        "idle_gaps": [[_label(ex.host_spans, (s + e) / 2), (e - s) * 1e-9]
                      for s, e in top_gaps],
    }


def _label(spans, t) -> str:
    for name, s, e in spans:
        if s <= t <= e:
            return name
    return "host"
