"""The CI perf-regression gate must catch injected regressions and tolerate
noise-level drift, missing rows, and new rows (see benchmarks/perf_gate.py)."""
import copy
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))
from benchmarks import perf_gate  # noqa: E402


def _doc(rows):
    return {
        "schema": "pim-malloc-bench/v1",
        "env": {"python": "3", "jax": "0", "backend": "cpu",
                "device_count": 1, "commit": "x", "smoke": True},
        "figs": {"fig14": {"status": "ok", "wall_s": 1.0, "records": [
            {"name": n, "us_per_call": v, "derived": ""}
            for n, v in rows.items()]}},
    }


def _write(tmp_path, name, doc):
    p = tmp_path / name
    p.write_text(json.dumps(doc))
    return str(p)


BASE = {"fig14/sw/size=32": 0.10, "fig14/hwsw/size=32": 0.08,
        "fig14/pallas/size=32": 0.08, "fig14/claim": 0.0}


def test_gate_passes_on_identical_doc(tmp_path):
    b = _write(tmp_path, "base.json", _doc(BASE))
    c = _write(tmp_path, "cur.json", _doc(BASE))
    assert perf_gate.run_gate(c, b, 0.20, 0.05) == 0


def test_gate_fails_on_injected_regression(tmp_path):
    """Acceptance: an injected >20% us_per_call regression exits non-zero."""
    cur = dict(BASE)
    cur["fig14/hwsw/size=32"] = BASE["fig14/hwsw/size=32"] * 1.5  # +50%
    b = _write(tmp_path, "base.json", _doc(BASE))
    c = _write(tmp_path, "cur.json", _doc(cur))
    assert perf_gate.run_gate(c, b, 0.20, 0.05) == 1


def test_gate_warns_but_passes_between_thresholds(tmp_path, capsys):
    cur = dict(BASE)
    cur["fig14/sw/size=32"] = BASE["fig14/sw/size=32"] * 1.10  # +10%
    b = _write(tmp_path, "base.json", _doc(BASE))
    c = _write(tmp_path, "cur.json", _doc(cur))
    assert perf_gate.run_gate(c, b, 0.20, 0.05) == 0
    out = capsys.readouterr().out
    assert "Warnings" in out and "+10.0%" in out


def test_gate_fails_on_missing_tracked_row(tmp_path, capsys):
    """A tracked baseline row that disappears is a hard failure — silent
    coverage loss must refresh the committed baseline explicitly."""
    cur = dict(BASE)
    del cur["fig14/pallas/size=32"]                 # tracked row vanished
    b = _write(tmp_path, "base.json", _doc(BASE))
    c = _write(tmp_path, "cur.json", _doc(cur))
    assert perf_gate.run_gate(c, b, 0.20, 0.05) == 1
    out = capsys.readouterr().out
    assert "disappeared" in out and "MISSING" in out


def test_gate_tolerates_new_rows(tmp_path, capsys):
    cur = dict(BASE)
    cur["fig14/newrow"] = 0.5                       # new row appeared
    b = _write(tmp_path, "base.json", _doc(BASE))
    c = _write(tmp_path, "cur.json", _doc(cur))
    assert perf_gate.run_gate(c, b, 0.20, 0.05) == 0
    out = capsys.readouterr().out
    assert "newrow" in out


def test_gate_fails_when_current_figure_errored(tmp_path, capsys):
    """A figure that crashed in the current run must FAIL the gate — its
    tracked rows would otherwise degrade into 'missing' warnings."""
    cur_doc = _doc({})  # fig14 rows gone...
    cur_doc["figs"]["fig14"] = {"status": "error", "wall_s": 0.1,
                                "records": [], "error": "AssertionError: x"}
    b = _write(tmp_path, "base.json", _doc(BASE))
    c = _write(tmp_path, "cur.json", cur_doc)
    assert perf_gate.run_gate(c, b, 0.20, 0.05) == 1
    out = capsys.readouterr().out
    assert "errored in the current run" in out


def test_gate_ignores_zero_and_error_rows(tmp_path):
    """us_per_call == 0 rows (claims/summaries) and error figs are untracked."""
    base_doc = _doc(BASE)
    base_doc["figs"]["broken"] = {"status": "error", "wall_s": 0.0,
                                  "records": [{"name": "broken/r",
                                               "us_per_call": 1.0}]}
    cur = dict(BASE)
    cur["fig14/claim"] = 99.0  # zero-baseline row may change freely
    b = _write(tmp_path, "base.json", base_doc)
    c = _write(tmp_path, "cur.json", _doc(cur))
    assert perf_gate.run_gate(c, b, 0.20, 0.05) == 0


def test_gate_writes_github_step_summary(tmp_path):
    cur = dict(BASE)
    cur["fig14/hwsw/size=32"] = 1.0
    b = _write(tmp_path, "base.json", _doc(BASE))
    c = _write(tmp_path, "cur.json", _doc(cur))
    summary = tmp_path / "summary.md"
    assert perf_gate.run_gate(c, b, 0.20, 0.05,
                              summary_path=str(summary)) == 1
    text = summary.read_text()
    assert "Perf gate FAILED" in text and "| row |" in text


def test_gate_new_untracked_rows_pass_with_notice(tmp_path, capsys):
    """Rows that exist only in the current run (e.g. a freshly added
    fleet_serve figure) must pass the gate and surface as a 'new' notice,
    never as failures."""
    cur = dict(BASE)
    cur["fleet_serve/sw/placement=round_robin"] = 1.23
    cur["fleet_serve/sw/placement=least_loaded"] = 1.11
    b = _write(tmp_path, "base.json", _doc(BASE))
    c = _write(tmp_path, "cur.json", _doc(cur))
    assert perf_gate.run_gate(c, b, 0.20, 0.05) == 0
    out = capsys.readouterr().out
    assert "fleet_serve/sw/placement=round_robin" in out
    assert "new" in out and "FAIL" not in out


def test_gate_zero_metric_baseline_row_no_divide_by_zero(tmp_path, capsys):
    """A baseline row whose us_per_call is exactly 0.0 is untracked: the
    gate must neither divide by zero nor fail when the current value moves
    (summary/claim rows are free to change)."""
    base = dict(BASE)
    base["fig14/zero_row"] = 0.0
    cur = dict(base)
    cur["fig14/zero_row"] = 7.5            # any movement is fine
    b = _write(tmp_path, "base.json", _doc(base))
    c = _write(tmp_path, "cur.json", _doc(cur))
    assert perf_gate.run_gate(c, b, 0.20, 0.05) == 0
    out = capsys.readouterr().out
    assert "ZeroDivisionError" not in out
    # zero-baseline rows are not in the tracked count
    assert "4 tracked rows" not in out.split("\n")[0]


def test_gate_zero_metric_current_row_is_improvement(tmp_path):
    """A tracked row dropping TO 0.0 (e.g. a path became free) is a -100%
    improvement, not an error."""
    cur = dict(BASE)
    cur["fig14/sw/size=32"] = 0.0
    b = _write(tmp_path, "base.json", _doc(BASE))
    c = _write(tmp_path, "cur.json", _doc(cur))
    assert perf_gate.run_gate(c, b, 0.20, 0.05) == 0


def test_gate_rejects_wrong_schema(tmp_path):
    doc = _doc(BASE)
    bad = copy.deepcopy(doc)
    bad["schema"] = "other/v0"
    b = _write(tmp_path, "base.json", bad)
    c = _write(tmp_path, "cur.json", doc)
    with pytest.raises(SystemExit):
        perf_gate.run_gate(c, b, 0.20, 0.05)


def test_repo_baseline_is_schema_valid():
    """The committed BENCH_BASELINE.json must load and contain tracked rows."""
    root = os.path.join(os.path.dirname(__file__), os.pardir)
    path = os.path.join(root, "BENCH_BASELINE.json")
    rows = perf_gate.load_rows(path)
    tracked = [n for n, r in rows.items() if r.get("us_per_call", 0) > 0]
    assert len(tracked) >= 10
    # the baseline must cover the backend axis
    assert {"strawman", "sw", "hwsw"} <= {r.get("backend")
                                         for r in rows.values()}


# ---------------------------------------------------------------------------
# wall-clock row family
# ---------------------------------------------------------------------------

ENV = "linux-x86_64-cpu-interpret"


def _wall_doc(modeled, wall, env_key=ENV):
    doc = _doc(modeled)
    doc["figs"]["fig14_wall"] = {"status": "ok", "wall_s": 1.0, "records": [
        {"name": n, "us_per_call": v, "derived": "", "lane": "wall",
         "env_key": env_key} for n, v in wall.items()]}
    return doc


WALL = {"fig14_wall/pallas/size=32/threads=16": 10.0,
        "fig14_wall/kernel_batch_speedup": 200.0}


def test_wall_rows_use_wall_thresholds_not_modeled(tmp_path):
    """A +40% wall drift passes (generous wall threshold) while the same
    +40% on a modeled row fails — the two families never share thresholds."""
    wall_cur = {n: v * 1.4 for n, v in WALL.items()}
    b = _write(tmp_path, "base.json", _wall_doc(BASE, WALL))
    c = _write(tmp_path, "cur.json", _wall_doc(BASE, wall_cur))
    assert perf_gate.run_gate(c, b, 0.20, 0.05) == 0
    mod_cur = dict(BASE)
    mod_cur["fig14/hwsw/size=32"] = BASE["fig14/hwsw/size=32"] * 1.4
    c2 = _write(tmp_path, "cur2.json", _wall_doc(mod_cur, WALL))
    assert perf_gate.run_gate(c2, b, 0.20, 0.05) == 1


def test_injected_wall_regression_fails(tmp_path, capsys):
    """Acceptance: a wall regression past --fail-over-wall exits non-zero."""
    wall_cur = {n: v * 3.0 for n, v in WALL.items()}  # +200% > +150%
    b = _write(tmp_path, "base.json", _wall_doc(BASE, WALL))
    c = _write(tmp_path, "cur.json", _wall_doc(BASE, wall_cur))
    assert perf_gate.run_gate(c, b, 0.20, 0.05) == 1
    out = capsys.readouterr().out
    assert "wall" in out and "FAIL" in out


def test_wall_rows_only_gated_against_same_env(tmp_path, capsys):
    """A wall baseline from a different runner class (env_key mismatch) is
    skipped informationally — compiled-device and CPU-interpret numbers
    must never cross-gate."""
    wall_cur = {n: v * 10.0 for n, v in WALL.items()}  # huge, but other env
    b = _write(tmp_path, "base.json", _wall_doc(BASE, WALL))
    c = _write(tmp_path, "cur.json",
               _wall_doc(BASE, wall_cur, env_key="linux-x86_64-tpu-compiled"))
    assert perf_gate.run_gate(c, b, 0.20, 0.05) == 0
    assert "env-skip" in capsys.readouterr().out


def test_missing_wall_row_warns_not_fails(tmp_path, capsys):
    """A wall row absent from the current run is a warning — wall coverage
    loss must not hard-fail the way modeled coverage loss does."""
    b = _write(tmp_path, "base.json", _wall_doc(BASE, WALL))
    c = _write(tmp_path, "cur.json", _doc(BASE))  # no wall rows at all
    assert perf_gate.run_gate(c, b, 0.20, 0.05) == 0
    out = capsys.readouterr().out
    assert "wall row missing" in out and "no-wall" in out


def test_lane_filter_restricts_gate(tmp_path):
    """--lane wall ignores modeled rows entirely (a wall-only artifact must
    not trip 'tracked row disappeared'), and --lane modeled ignores wall."""
    wall_only = _wall_doc({}, WALL)
    del wall_only["figs"]["fig14"]
    b = _write(tmp_path, "base.json", _wall_doc(BASE, WALL))
    c = _write(tmp_path, "wall_only.json", wall_only)
    assert perf_gate.run_gate(c, b, 0.20, 0.05, lane="wall") == 0
    assert perf_gate.run_gate(c, b, 0.20, 0.05, lane="all") == 1
    mod_only = _write(tmp_path, "mod_only.json", _doc(BASE))
    assert perf_gate.run_gate(mod_only, b, 0.20, 0.05, lane="modeled") == 0


def test_delta_table_groups_by_lane_with_subtotals(tmp_path, capsys):
    """The delta table renders the modeled group first, then wall, each
    closed by a subtotal row (summed us, aggregate delta, verdict counts)."""
    b = _write(tmp_path, "base.json", _wall_doc(BASE, WALL))
    c = _write(tmp_path, "cur.json", _wall_doc(BASE, WALL))
    assert perf_gate.run_gate(c, b, 0.20, 0.05) == 0
    out = capsys.readouterr().out
    assert "**modeled lane**" in out and "**wall lane**" in out
    assert "_modeled subtotal" in out and "_wall subtotal" in out
    assert out.index("**modeled lane**") < out.index("**wall lane**")
    assert "ok=3" in out          # three tracked modeled rows all ok


def test_custom_wall_threshold_cli(tmp_path):
    """--fail-over-wall from the CLI overrides the default wall threshold."""
    wall_cur = {n: v * 1.4 for n, v in WALL.items()}
    b = _write(tmp_path, "base.json", _wall_doc(BASE, WALL))
    c = _write(tmp_path, "cur.json", _wall_doc(BASE, wall_cur))
    assert perf_gate.main([c, "--baseline", b,
                           "--fail-over-wall", "0.30"]) == 1
    assert perf_gate.main([c, "--baseline", b,
                           "--fail-over-wall", "3.0"]) == 0


# ---------------------------------------------------------------------------
# env_stamp dirty-check (benchmarks/run.py)
# ---------------------------------------------------------------------------

def _git(tmp, *args):
    import subprocess
    return subprocess.run(
        ["git", "-c", "user.email=t@t", "-c", "user.name=t", *args],
        cwd=tmp, capture_output=True, text=True, check=True)


def test_env_stamp_ignores_untracked_pycache(tmp_path):
    """A clean checkout with stray __pycache__ dirs must NOT stamp -dirty:
    the committed revision fully reproduces the rows."""
    from benchmarks import run as bench_run
    _git(tmp_path, "init", "-q")
    (tmp_path / "f.py").write_text("x = 1\n")
    _git(tmp_path, "add", "f.py")
    _git(tmp_path, "commit", "-qm", "init")
    cache = tmp_path / "__pycache__"
    cache.mkdir()
    (cache / "f.cpython-311.pyc").write_bytes(b"\x00")
    stamp = bench_run.env_stamp(True, root=str(tmp_path))
    assert not stamp["commit"].endswith("-dirty")
    # ... but a modified *tracked* file still must
    (tmp_path / "f.py").write_text("x = 2\n")
    stamp = bench_run.env_stamp(True, root=str(tmp_path))
    assert stamp["commit"].endswith("-dirty")


def test_run_exits_nonzero_after_writing_error_rows(tmp_path, monkeypatch,
                                                    capsys):
    """A figure that raises still gets its /ERROR row and JSON cell, and
    then the harness exits non-zero."""
    from benchmarks import run as bench_run

    def broken(smoke):
        raise RuntimeError("boom")

    monkeypatch.setattr(bench_run, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(bench_run.importlib, "import_module",
                        lambda *a, **k: type("M", (), {"bench": broken}))
    out = tmp_path / "b.json"
    with pytest.raises(SystemExit) as e:
        bench_run.main(["fig5", "--smoke", "--json", str(out)])
    assert e.value.code not in (0, None)
    assert "fig5/ERROR" in capsys.readouterr().out
    doc = json.loads(out.read_text())
    assert doc["figs"]["fig5"]["status"] == "error"
