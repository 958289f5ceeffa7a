"""The trace reduction: busy union of the device's programs, scan program
time, top ops and idle gaps named by the host span open in them, on
intervals made by hand and on a trace recorded on the CPU."""
import time

import pytest

import bench_tiny  # noqa: F401  (puts the repository on sys.path)
from bench import xplane

MS = 1_000_000


def _extract():
    spans = [("plan", 0, 10 * MS), ("run", 10 * MS, 30 * MS),
             ("report", 30 * MS, 50 * MS)]
    ops = [("init", 11 * MS, 12 * MS),
           ("while", 13 * MS, 20 * MS), ("fusion", 15 * MS, 22 * MS),
           ("while", 24 * MS, 28 * MS),
           ("slice", 40 * MS, 41 * MS),
           ("late", 49 * MS, 55 * MS)]          # ends after the window
    modules = [("jit_init", 11 * MS, 12 * MS), ("jit_scan", 13 * MS, 28 * MS),
               ("jit_slice", 40 * MS, 41 * MS),
               ("jit_late", 49 * MS, 55 * MS)]   # ends after the window
    return xplane.Extract(host_spans=spans,
                          device_ops={"/device:TPU:0": ops},
                          modules={"/device:TPU:0": modules})


def test_union_merges_overlaps():
    assert xplane.union([(5, 9), (0, 2), (1, 3), (9, 10), (12, 12)]) == [
        (0, 3), (5, 10)]


def test_busy_is_the_union_inside_the_window():
    s = xplane.summarize(_extract())
    assert s["window_s"] == pytest.approx(0.050)
    # programs at 11-12, 13-28, 40-41, 49-50 ms
    assert s["busy_s"] == pytest.approx(0.018)
    assert s["scan_module"] == "jit_scan"
    assert s["scan_busy_s"] == pytest.approx(0.015)


def test_idle_gaps_are_named_by_the_open_host_span():
    s = xplane.summarize(_extract())
    gaps = {(name, round(sec * 1e3, 6)) for name, sec in s["idle_gaps"]}
    # no program runs from 28 to 40 ms: one gap, mostly under `report`
    assert gaps == {("plan", 11.0), ("run", 1.0), ("report", 12.0),
                    ("report", 8.0)}
    assert [round(g[1] * 1e3, 6) for g in s["idle_gaps"]][:2] == [12.0, 11.0]


def test_top_ops_sum_their_time():
    s = xplane.summarize(_extract())
    top = dict(s["device_ops"])
    assert top["while"] == pytest.approx(0.011)
    assert top["late"] == pytest.approx(0.001)


def test_nothing_to_read_gives_nothing():
    ex = _extract()
    assert xplane.summarize(xplane.Extract(ex.host_spans, {}, {})) is None
    assert xplane.summarize(xplane.Extract(ex.host_spans, ex.device_ops,
                                           {})) is None
    assert xplane.summarize(xplane.Extract([], ex.device_ops,
                                           ex.modules)) is None


def test_host_spans_come_back_from_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("plan"):
            time.sleep(0.005)
        with jax.profiler.TraceAnnotation("run"):
            f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    ex = xplane.extract(xplane.latest_xplane(str(tmp_path)),
                        ("plan", "run", "report"))
    names = [s[0] for s in ex.host_spans]
    assert names == ["plan", "run"]
    plan = ex.host_spans[0]
    assert plan[2] - plan[1] >= 5 * MS
