"""Host spans of the serving path, on the profiler's clock.

`span(name, **counts)` always opens a `jax.profiler.TraceAnnotation`, so a
profiler capture (`jax.profiler.start_trace`, or an xprof capture through
`jax.profiler.start_server`) holds the span on its host plane, on the same
clock as the device ops. There is no switch of its own: while a capture
runs (`TraceAnnotation.is_enabled()`), each span is also appended to a
bounded in-memory log on `time.perf_counter`:

    with jax.profiler.trace(trace_dir):
        engine.run(plan)
    spans.records()                # serve/session, serve/init, ...
    spans.self_time("serve/init")  # seconds, its child spans left out

A record names the span open around it on the same thread (`parent`, that
span's `id`) and the session it belongs to: the `id` of the outermost span
around it, so every span of one served session shares it. The log holds the
latest capture only: the first span that opens during a capture, after one
that opened with none running, empties it. With no capture running a span
costs one annotation and one `is_enabled()` check, a few microseconds of
host time; the serving path opens four a session, and one more where its
answers are read back (`serving.response_host`).
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import itertools
import threading
import time

import jax

MAX_RECORDS = 1 << 16


@dataclasses.dataclass(frozen=True)
class Record:
    id: int
    name: str
    start: float              # time.perf_counter seconds
    end: float
    parent: int | None        # id of the span open around it on its thread
    session: int              # id of the outermost span around it
    counts: dict

    @property
    def seconds(self) -> float:
        return self.end - self.start


_log: collections.deque = collections.deque(maxlen=MAX_RECORDS)
_lock = threading.Lock()
_ids = itertools.count()
_open = threading.local()     # .stack: the recorded spans open on a thread
_was_enabled = False


@contextlib.contextmanager
def span(name: str, **counts):
    """A host span named `name`; `counts` are recorded with it. While a
    capture runs it yields the counts dict, so a count known only inside
    the span can be added to it; with none running it yields None, so a
    count that costs work is computed only where it is recorded."""
    global _was_enabled
    with jax.profiler.TraceAnnotation(name):
        enabled = jax.profiler.TraceAnnotation.is_enabled()
        if not enabled:
            _was_enabled = False
            yield None
            return
        with _lock:
            if not _was_enabled:
                _log.clear()
                _was_enabled = True
        stack = _open.__dict__.setdefault("stack", [])
        sid = next(_ids)
        parent, session = stack[-1] if stack else (None, sid)
        stack.append((sid, session))
        start = time.perf_counter()
        try:
            yield counts
        finally:
            end = time.perf_counter()
            stack.pop()
            _log.append(Record(sid, name, start, end, parent, session,
                               dict(counts)))


def records() -> list:
    """The latest capture's records, in the order they opened."""
    return sorted(_log, key=lambda r: r.start)


def totals(name: str) -> float:
    """Seconds inside spans named `name`."""
    return sum(r.seconds for r in _log if r.name == name)


def self_time(name: str) -> float:
    """Seconds inside spans named `name` that none of their child spans
    covers. Children run one after another on their parent's thread, so
    the part they cover is the sum of their durations."""
    recs = list(_log)
    ids = {r.id for r in recs if r.name == name}
    return (sum(r.seconds for r in recs if r.id in ids)
            - sum(r.seconds for r in recs if r.parent in ids))


def count(name: str, key: str) -> int:
    """The count `key` summed over spans named `name`."""
    return sum(r.counts.get(key, 0) for r in _log if r.name == name)
