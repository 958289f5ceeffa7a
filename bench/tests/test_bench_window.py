"""The window arithmetic: all work over all time, the session in flight at
the end counted, and session seeds fixed by the run's seed."""
import pytest

import bench_tiny  # noqa: F401  (puts the repository on sys.path)
from bench.window import Session, Spans, run_window, session_seed


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def test_overrun_session_is_counted_and_rate_is_all_over_all():
    clock = Clock()
    durations = [4.0, 3.0, 5.0, 9.0, 9.0]
    ops = [100, 10, 50, 7, 7]
    seen = []

    def session(i):
        seen.append(i)
        clock.t += durations[len(seen) - 1]
        return Session(ops=ops[len(seen) - 1], rounds=16, attempted=200,
                       failed=3)

    win = run_window(session, seconds=10.0, first_index=1, clock=clock)
    # 4 + 3 = 7 s < 10 s, so a third session starts and runs to 12 s
    assert seen == [1, 2, 3]
    assert win.wall_s == pytest.approx(12.0)
    assert win.ops == 160 and win.rounds == 48
    assert win.attempted == 600 and win.failed == 9
    assert win.ops_per_s == pytest.approx(160 / 12.0)
    # not a mean of per-session rates
    assert win.ops_per_s != pytest.approx(sum(
        o / d for o, d in zip(ops[:3], durations[:3])) / 3)


def test_one_long_session_fills_a_short_window():
    clock = Clock()

    def session(i):
        clock.t += 30.0
        return Session(ops=90, rounds=16, attempted=90, failed=0)

    win = run_window(session, seconds=5.0, first_index=1, clock=clock)
    assert len(win.sessions) == 1 and win.ops_per_s == pytest.approx(3.0)


def test_spans_total_per_name():
    clock = Clock()
    spans = Spans(clock=clock)
    for dt in (1.0, 2.0):
        with spans("plan"):
            clock.t += dt
    with spans("run"):
        clock.t += 0.5
    assert dict(spans.totals) == {"plan": 3.0, "run": 0.5}


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 12345, 2**40, -3])
def test_session_seeds_are_fixed_and_distinct(seed):
    a = [session_seed(seed, i) for i in range(5)]
    assert a == [session_seed(seed, i) for i in range(5)]
    assert len(set(a)) == 5
    assert session_seed(seed, 0) != session_seed(seed + 1, 0)
    assert all(0 <= s < 2**32 for s in a)
