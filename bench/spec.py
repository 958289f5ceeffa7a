"""Finds a cell's parts by name, from BENCHMARK.json and the files beside it.

A cell (an entry of `workloads`) names a configuration and a traffic mix.
The configuration's file is the `file` of its entry in `configs`; the
traffic mix is `bench/traffic/<traffic>.json`, whose `entry` names the
session code in `bench/entries/<entry>.py`; the configuration's `reference`
names `bench/reference/<reference>.py`; each metric is read by
`bench/metrics/<name>.py`. Adding any of them is adding a file: nothing
here lists them.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib

BENCH_DIR = "bench"


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    end_to_end: bool


@dataclasses.dataclass(frozen=True)
class Cell:
    root: pathlib.Path
    name: str
    chips: int
    config: dict
    traffic: dict
    metrics: tuple            # every Metric the cell reports, e2e first

    def module(self, kind: str, name: str):
        return load_module(self.root, kind, name)


def load_module(root: pathlib.Path, kind: str, name: str):
    """Import `<root>/bench/<kind>/<name>.py` by its path."""
    path = pathlib.Path(root) / BENCH_DIR / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"_bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _reports(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(root, workload: str) -> Cell:
    """The cell `workload` of `<root>/BENCHMARK.json`, with its files read."""
    root = pathlib.Path(root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic_file = root / BENCH_DIR / "traffic" / f"{w['traffic']}.json"
    traffic = json.loads(traffic_file.read_text())
    metrics = tuple(
        Metric(m["name"], m["unit"], e2e)
        for key, e2e in (("end_to_end", True), ("per_layer", False))
        for m in bench[key] if _reports(m, workload))
    return Cell(root=root, name=workload, chips=int(w["chips"]),
                config=config, traffic=traffic, metrics=metrics)
