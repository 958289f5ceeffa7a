"""The scan driver's metrics read from the program's own span log: values on
a log made by hand, nothing without `serve/session` spans or without the
log, and a traced run's four numbers that add up to `scan_ms_per_round`."""
import collections
import sys
import time
import types

import pytest

import repro.runtime
from bench_tiny import REPO, tiny_root
from bench import harness, spec
from repro.runtime import spans

NAMES = ("serve_init_ms_per_round", "serve_h2d_ms_per_round",
         "serve_dispatch_ms_per_round", "scan_wait_ms_per_round")


def _read(name, ctx):
    return spec.load_module(REPO, "metrics", name).read(ctx)


def _session(first_id, t0, rounds, init, h2d, dispatch):
    """One served session's records, laid out as `ScanEngine.run` opens
    them, from `t0` on."""
    sid = first_id
    t_init, t_h2d = t0 + init, t0 + init + h2d
    end = t_h2d + dispatch
    return [
        spans.Record(sid, "serve/session", t0, end, None, sid,
                     {"rounds": rounds, "h2d_bytes": 1 << 20}),
        spans.Record(sid + 1, "serve/init", t0, t_init, sid, sid, {}),
        spans.Record(sid + 2, "serve/h2d", t_init, t_h2d, sid, sid, {}),
        spans.Record(sid + 3, "serve/dispatch", t_h2d, end, sid, sid, {}),
    ]


@pytest.fixture
def log(monkeypatch):
    records = collections.deque(maxlen=spans.MAX_RECORDS)
    monkeypatch.setattr(spans, "_log", records)
    return records


def test_metrics_divide_span_time_by_the_sessions_rounds(log):
    log.extend(_session(0, 10.0, rounds=100, init=0.2, h2d=0.05,
                        dispatch=0.01))
    log.extend(_session(4, 30.0, rounds=300, init=0.6, h2d=0.15,
                        dispatch=0.03))
    # the harness counts other rounds (an untraced session): not read
    ctx = types.SimpleNamespace(spans={"run": 41.0},
                                window=types.SimpleNamespace(rounds=999))
    got = {n: _read(n, ctx) for n in NAMES}
    assert got["serve_init_ms_per_round"] == pytest.approx(2.0)
    assert got["serve_h2d_ms_per_round"] == pytest.approx(0.5)
    assert got["serve_dispatch_ms_per_round"] == pytest.approx(0.1)
    # 41 s in run spans, 1.04 s of it inside serve/session
    assert got["scan_wait_ms_per_round"] == pytest.approx(
        1e3 * (41.0 - 1.04) / 400)
    assert sum(got.values()) == pytest.approx(1e3 * 41.0 / 400)


def test_nothing_without_session_spans(log):
    ctx = types.SimpleNamespace(spans={"run": 4.0})
    assert all(_read(n, ctx) is None for n in NAMES)
    log.append(spans.Record(0, "serve/h2d", 0.0, 1.0, None, 0, {}))
    assert all(_read(n, ctx) is None for n in NAMES)


def test_nothing_without_a_run_span(log):
    log.extend(_session(0, 0.0, rounds=4, init=1.0, h2d=1.0, dispatch=1.0))
    assert _read("scan_wait_ms_per_round",
                 types.SimpleNamespace(spans={})) is None
    assert _read("serve_h2d_ms_per_round",
                 types.SimpleNamespace(spans={})) == pytest.approx(250.0)


def test_nothing_from_a_program_without_the_log(log, monkeypatch):
    log.extend(_session(0, 0.0, rounds=4, init=1.0, h2d=1.0, dispatch=1.0))
    # what a program older than the span log gives: the import fails
    monkeypatch.setitem(sys.modules, "repro.runtime.spans", None)
    monkeypatch.delattr(repro.runtime, "spans")
    ctx = types.SimpleNamespace(spans={"run": 4.0})
    assert all(_read(n, ctx) is None for n in NAMES)


def test_traced_run_reports_the_four_and_they_add_up(tmp_path):
    root = tiny_root(tmp_path)
    r = harness.run_cell(root, "tiny_hwsw_micro_fig14", 2**31 + 7, 0.5,
                         True, time.perf_counter(), check_chip=False)
    assert r["correct"]
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert all(m[n] > 0 for n in NAMES)
    parts = sum(m[n] for n in NAMES)
    assert parts <= m["scan_ms_per_round"]
    assert parts >= 0.99 * m["scan_ms_per_round"]
    # an untraced run reads no per-layer metric at all
    r = harness.run_cell(root, "tiny_hwsw_micro_fig14", 2**31 + 7, 0.1,
                         False, time.perf_counter(), check_chip=False)
    assert not set(NAMES) & set(r["metrics"])
