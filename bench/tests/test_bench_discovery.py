"""A configuration, a traffic mix and a metric are added as files and found
by their names, with no edit to any file of the harness; and the cells of
the committed BENCHMARK.json resolve to their files."""
import filecmp
import json
import time

import pytest

from bench_tiny import REPO, tiny_root
from bench import harness, spec


def test_committed_cells_resolve():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = spec.load_cell(REPO, w["name"])
        assert cell.config["name"] == w["config"]
        cell.module("entries", cell.traffic["entry"])
        cell.module("reference", cell.config["reference"])
        for m in cell.metrics:
            assert callable(cell.module("metrics", m.name).read)
        e2e = [m.name for m in cell.metrics if m.end_to_end]
        assert e2e == ["ops_per_s", "setup_s"]
        assert len(cell.metrics) > len(e2e)


def test_added_files_are_found_by_name(tmp_path):
    root = tiny_root(tmp_path)
    # the harness itself is unchanged: only data and new files were added
    cmp = filecmp.dircmp(REPO / "bench", root / "bench",
                         ignore=["tests", "__pycache__"])
    assert not cmp.diff_files and not cmp.left_only
    for sub in ("metrics", "entries", "reference", "configs", "traffic"):
        assert not filecmp.dircmp(REPO / "bench" / sub,
                                  root / "bench" / sub).diff_files
    (root / "bench/metrics/sessions_in_window.py").write_text(
        '"""Sessions the window served."""\n\n\n'
        'def read(ctx):\n    return len(ctx.window.sessions)\n')
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": "sessions_in_window", "unit": "count", "better": "higher",
        "source": "host_clock", "layer": "scan driver",
        "moves": "ops_per_s"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.load_cell(root, "tiny_hwsw_micro_fig14")
    assert cell.config["num_ranks"] == 2 and cell.traffic["alloc_rounds"] == 3
    assert "sessions_in_window" in [m.name for m in cell.metrics]

    r = harness.run_cell(root, "tiny_hwsw_micro_fig14", 2**31 + 99, 0.5,
                         True, time.perf_counter(), check_chip=False)
    assert r["correct"]
    assert r["metrics"]["sessions_in_window"]["value"] >= 1
    assert r["metrics"]["readback_ms_per_round"]["value"] > 0
    # nothing to read on a CPU trace: the device metrics are left out
    assert "device_idle_share" not in r["metrics"]
    assert "plan_ms_per_round" not in r["metrics"]


def test_unknown_names_are_refused(tmp_path):
    root = tiny_root(tmp_path)
    with pytest.raises(KeyError):
        spec.load_cell(root, "no_such_cell")
    with pytest.raises(FileNotFoundError):
        spec.load_module(root, "metrics", "no_such_metric")
