"""The measured window: whole sessions back to back, and the host spans
around the calls into each layer.

A window runs sessions until `seconds` have passed and finishes the one in
flight. Its rate is all the work of those sessions over all their time,
from the first session's start to the last one's end, the overrun
included: no session is left out and none is weighted.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import time

import numpy as np


def session_seed(seed: int, *keys: int) -> int:
    """The seed of session `keys[0]` of a run started with `seed` (more
    keys give streams of their own, such as a session's check sample)."""
    ss = np.random.SeedSequence([seed % (1 << 64), *keys])
    return int(ss.generate_state(1, np.uint32)[0])


@dataclasses.dataclass
class Session:
    """What one served session did, and what the checks need of it."""
    ops: int                  # protocol ops served (non-NOOP grid entries)
    rounds: int
    attempted: int
    failed: int
    record: object = None     # entry-specific: read by the entry's check


class Spans:
    """Host-clock totals per span name. Each span is also a
    `jax.profiler.TraceAnnotation`, so a traced session carries the same
    names on the profiler's clock."""

    def __init__(self, clock=time.perf_counter):
        self.clock = clock
        self.totals = collections.defaultdict(float)

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax
        with jax.profiler.TraceAnnotation(name):
            t0 = self.clock()
            try:
                yield
            finally:
                self.totals[name] += self.clock() - t0


@dataclasses.dataclass
class Window:
    sessions: list
    wall_s: float

    @property
    def ops(self) -> int:
        return sum(s.ops for s in self.sessions)

    @property
    def rounds(self) -> int:
        return sum(s.rounds for s in self.sessions)

    @property
    def attempted(self) -> int:
        return sum(s.attempted for s in self.sessions)

    @property
    def failed(self) -> int:
        return sum(s.failed for s in self.sessions)

    @property
    def ops_per_s(self) -> float:
        return self.ops / self.wall_s


def run_window(session_fn, seconds: float, first_index: int,
               clock=time.perf_counter) -> Window:
    """Call `session_fn(index)` from `first_index` on until `seconds` have
    passed since the first call began; the session in flight finishes."""
    t0 = clock()
    sessions = []
    index = first_index
    while True:
        sessions.append(session_fn(index))
        index += 1
        if clock() - t0 >= seconds:
            break
    return Window(sessions=sessions, wall_s=clock() - t0)
