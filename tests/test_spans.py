"""The serving path's span log (`repro.runtime.spans`) and the fleet round's
phase scopes.

Spans are recorded only while a profiler capture runs, hold the latest
capture only, and nest under one session id; the scanned round carries its
phases as named scopes and no host callback, so the persistent compile
cache keeps serving it.
"""
import collections
import re
import types

import jax
import numpy as np
import pytest

from repro.core import heap, system as sysm
from repro.launch.serving import ScanEngine
from repro.runtime import spans

T = 4
ROUNDS = 4
PHASES = ("realloc_meta", "malloc", "free", "cache_pass", "price", "slots")


@pytest.fixture(autouse=True)
def fresh_log(monkeypatch):
    """Each test starts from an empty log and no capture seen."""
    monkeypatch.setattr(spans, "_log",
                        collections.deque(maxlen=spans.MAX_RECORDS))
    monkeypatch.setattr(spans, "_was_enabled", False)


@pytest.fixture(scope="module")
def engine():
    cfg = sysm.SystemConfig(kind="hwsw", heap_bytes=1 << 19, num_threads=T)
    return ScanEngine(cfg, 1, 2, mesh=False)


def _plan(engine):
    """Two malloc rounds, then the same blocks freed by slot reference."""
    shape = (ROUNDS,) + engine.shape
    op = np.zeros(shape, np.int32)
    half = ROUNDS // 2
    op[:half] = heap.OP_MALLOC
    op[half:] = heap.OP_FREE
    cap = engine.capacity
    ref = np.full(shape, -1, np.int32)
    ref[half:] = (np.arange(half)[:, None] * cap
                  + np.arange(cap)[None, :]).reshape((half,) + engine.shape)
    return types.SimpleNamespace(op=op, size=np.full(shape, 48, np.int32),
                                 ptr_ref=ref,
                                 ptr_raw=np.full(shape, -1, np.int32))


def _serve(engine, plan):
    state, resps = engine.run(plan)
    jax.block_until_ready((state, resps))
    return resps


def _capture(path):
    """A capture with the benchmark harness's own profiler options."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(path), profiler_options=options)


def _captured(engine, plan, path):
    _capture(path)
    try:
        return _serve(engine, plan)
    finally:
        jax.profiler.stop_trace()


def test_no_capture_records_nothing(engine):
    _serve(engine, _plan(engine))
    assert not jax.profiler.TraceAnnotation.is_enabled()
    assert spans.records() == []
    assert spans.totals("serve/session") == 0
    assert spans.count("serve/session", "rounds") == 0


def test_no_capture_computes_no_count(engine, monkeypatch):
    from repro.launch import serving

    def counted(op):
        raise AssertionError("a count computed with no capture running")

    monkeypatch.setattr(serving, "_reallocs", counted)
    serving.response_host(_serve(engine, _plan(engine)))
    with spans.span("serve/readback") as counts:
        assert counts is None
    assert spans.records() == []


def test_session_spans_under_a_capture(engine, tmp_path):
    plan = _plan(engine)
    _captured(engine, plan, tmp_path)
    recs = spans.records()
    assert [r.name for r in recs] == ["serve/session", "serve/init",
                                      "serve/h2d", "serve/dispatch"]
    session = recs[0]
    assert session.parent is None
    assert session.counts == {"rounds": ROUNDS,
                              "h2d_bytes": 4 * plan.op.nbytes,
                              "reallocs": 0}
    for child in recs[1:]:
        assert child.parent == session.id
        assert child.session == session.session == session.id
        assert session.start <= child.start <= child.end <= session.end
    # the children run one after another
    assert all(a.end <= b.start for a, b in zip(recs[1:], recs[2:]))
    assert spans.count("serve/session", "rounds") == ROUNDS


def test_answers_do_not_depend_on_the_capture(engine, tmp_path):
    plan = _plan(engine)
    plain = _serve(engine, plan)
    traced = _captured(engine, plan, tmp_path)
    for f in plain._fields:
        np.testing.assert_array_equal(np.asarray(getattr(plain, f)),
                                      np.asarray(getattr(traced, f)))


def test_a_second_capture_starts_a_fresh_log(engine, tmp_path):
    plan = _plan(engine)
    _captured(engine, plan, tmp_path / "a")
    first = spans.records()
    # between captures the server goes on serving: nothing is recorded, and
    # the first capture's log stays readable
    _serve(engine, plan)
    assert spans.records() == first
    _captured(engine, plan, tmp_path / "b")
    second = spans.records()
    assert len(second) == len(first) == 4
    assert {r.session for r in second}.isdisjoint({r.session for r in first})
    assert len({r.session for r in second}) == 1


def test_segment_spans(engine, tmp_path):
    plan = _plan(engine)
    grids = (plan.op, plan.size, plan.ptr_ref, plan.ptr_raw)
    state = heap.sharded_init(engine.cfg, 1, 2)
    slots = jax.numpy.full((ROUNDS * engine.capacity,), -1, jax.numpy.int32)
    _capture(tmp_path)
    try:
        out = engine.run_segment(state, slots, 0, [g[:2] for g in grids])
        jax.block_until_ready(out)
    finally:
        jax.profiler.stop_trace()
    recs = spans.records()
    assert [r.name for r in recs] == ["serve/segment", "serve/h2d",
                                      "serve/dispatch"]
    assert recs[0].counts == {"rounds": 2,
                              "h2d_bytes": 4 * plan.op[:2].nbytes,
                              "reallocs": 0}
    assert {r.session for r in recs} == {recs[0].id}


def test_self_time_leaves_out_covered_child_time(tmp_path, monkeypatch):
    now = [0.0]
    monkeypatch.setattr(spans, "time",
                        types.SimpleNamespace(perf_counter=lambda: now[0]))
    _capture(tmp_path)
    try:
        with spans.span("outer", rounds=3):
            now[0] += 1.0
            with spans.span("inner"):
                now[0] += 2.0
            with spans.span("inner"):
                now[0] += 0.5
            now[0] += 0.25
        with spans.span("outer", rounds=5):
            now[0] += 4.0
    finally:
        jax.profiler.stop_trace()
    assert spans.totals("outer") == pytest.approx(3.75 + 4.0)
    assert spans.self_time("outer") == pytest.approx(1.25 + 4.0)
    assert spans.self_time("inner") == pytest.approx(2.5)
    assert spans.count("outer", "rounds") == 8
    # each outermost span starts a session of its own
    outer = [r for r in spans.records() if r.name == "outer"]
    assert len({r.session for r in outer}) == 2


@pytest.mark.parametrize("kind", ["sw", "hwsw"])
def test_scan_carries_phase_scopes_and_no_host_callback(kind):
    cfg = sysm.SystemConfig(kind=kind, heap_bytes=1 << 19, num_threads=T)
    eng = ScanEngine(cfg, 1, 2, mesh=False)
    plan = _plan(eng)
    state = jax.eval_shape(lambda: heap.sharded_init(cfg, 1, 2))
    grids = [jax.ShapeDtypeStruct(g.shape, g.dtype)
             for g in (plan.op, plan.size, plan.ptr_ref, plan.ptr_raw)]
    text = eng._scan.lower(state, *grids).as_text(debug_info=True)
    locs = set(re.findall(r'loc\("([^"]*)"', text))
    for phase in PHASES:
        # a scope reads `round/<phase>/op`, or `round/vmap(vmap(<phase>))/op`
        # under the fleet's vmaps
        assert any(re.search(rf"[/(]{phase}[)/]", n) for n in locs), phase
    assert all(n.startswith("round/") for n in locs
               if re.search(r"[/(](malloc|free|price)[)/]", n))
    targets = re.findall(r"custom_call @([\w.]+)", text)
    assert not [t for t in targets if "callback" in t], targets
