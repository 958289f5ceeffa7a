"""A key-value store's load and updates through the segmented scan.

Each thread owns a few records: a load of one MALLOC per record, then
updates that REALLOC a record to a new size through a slot reference to its
latest answer, crossing size classes both ways. Run as two
`ScanEngine.run_segment` calls on copies of the loaded state and slot file,
the tape answers as one `ScanEngine.run` over the whole tape does, as the
plain-Python oracle does, and leaves each record in a block of its last
size; the serving path's span counts match the tape.
"""
import collections
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import heap, system as sysm
from repro.core.oracle import PyPimMalloc
from repro.launch.serving import ScanEngine, response_host
from repro.runtime import spans

T = 4
RANKS, CORES = 1, 2
HEAP = 1 << 19
RECORDS = 6
UPDATES = 24
CLASSES = (16, 32, 64, 128, 256, 512, 1024, 2048)


def _class_of(size: int) -> int:
    return next(c for c in CLASSES if size <= c)


def _tape(seed: int):
    """Load rounds then update rounds, grids [rounds, R, C, T]; record
    (thread, k) is inserted in round k. `key` is each update's record."""
    rng = np.random.default_rng(seed)
    n = RANKS * CORES * T
    rounds = RECORDS + UPDATES
    op = np.zeros((rounds, n), np.int32)
    size = np.zeros((rounds, n), np.int32)
    ref = np.full((rounds, n), -1, np.int32)
    op[:RECORDS] = heap.OP_MALLOC
    size[:RECORDS] = rng.integers(200, 1100, (RECORDS, n))
    op[RECORDS:] = heap.OP_REALLOC
    size[RECORDS:] = rng.integers(200, 1100, (UPDATES, n))
    key = rng.integers(0, RECORDS, (UPDATES, n))
    thread = np.arange(n)
    last = np.arange(RECORDS)[None, :] * n + thread[:, None]
    for u in range(UPDATES):
        ref[RECORDS + u] = last[thread, key[u]]
        last[thread, key[u]] = (RECORDS + u) * n + thread
    grid = (rounds, RANKS, CORES, T)
    return types.SimpleNamespace(
        op=op.reshape(grid), size=size.reshape(grid),
        ptr_ref=ref.reshape(grid), ptr_raw=np.full(grid, -1, np.int32),
        key=key)


def _grids(tape, rounds=slice(None)):
    return tuple(g[rounds] for g in (tape.op, tape.size, tape.ptr_ref,
                                     tape.ptr_raw))


def _segmented(engine, tape):
    """The load as segment [0, RECORDS) on a fresh fleet, then the updates
    on copies of the loaded state and slot file, as the store's sessions
    run them."""
    state = heap.sharded_init(engine.cfg, RANKS, CORES)
    slots = jnp.full(((RECORDS + UPDATES) * engine.capacity,), -1, jnp.int32)
    state, slots, load = engine.run_segment(
        state, slots, 0, _grids(tape, slice(0, RECORDS)))
    loaded = (state, slots)
    state, slots, upd = engine.run_segment(
        jax.tree.map(jnp.copy, state), jnp.copy(slots), RECORDS,
        _grids(tape, slice(RECORDS, None)))
    jax.block_until_ready((loaded, state, upd))
    answers = {f: np.concatenate([a, b])
               for (f, a), b in zip(response_host(load).items(),
                                    response_host(upd).values())}
    return state, answers


@pytest.fixture(scope="module", params=["sw", "hwsw"])
def served(request):
    cfg = sysm.SystemConfig(kind=request.param, heap_bytes=HEAP,
                            num_threads=T)
    engine = ScanEngine(cfg, RANKS, CORES, mesh=False)
    tape = _tape(11)
    state, answers = _segmented(engine, tape)
    return engine, tape, state, answers


def test_segments_equal_one_scan_bit_for_bit(served):
    engine, tape, state, answers = served
    whole_state, whole = engine.run(tape)
    whole = response_host(whole)
    for f, v in whole.items():
        np.testing.assert_array_equal(answers[f], v, err_msg=f)
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(whole_state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_answers_equal_the_oracle_with_classes_crossed_both_ways(served):
    engine, tape, _, answers = served
    rounds = tape.op.shape[0]
    n = engine.capacity
    ups = downs = 0
    for c in range(CORES):
        py = PyPimMalloc(heap_bytes=HEAP, num_threads=T)
        slots = np.full(rounds * T, -1, np.int64)
        for r in range(rounds):
            refs = tape.ptr_ref[r, 0, c]
            local = (refs // n) * T + refs % n - c * T
            ptr = np.where(refs >= 0, slots[np.clip(local, 0, None)], -1)
            sizes = tape.size[r, 0, c]
            if r >= RECORDS:
                old = [py._realloc_meta(int(p), 1)[2] for p in ptr]
                new = [_class_of(int(s)) for s in sizes]
                ups += sum(b > a for a, b in zip(old, new))
                downs += sum(b < a for a, b in zip(old, new))
            want = py.request(tape.op[r, 0, c].tolist(), sizes.tolist(),
                              ptr.tolist())
            for f in ("ptr", "ok", "path", "moved"):
                np.testing.assert_array_equal(
                    answers[f][r, 0, c], np.asarray(want[f]),
                    err_msg=f"{f} round {r} core {c}")
            slots[r * T:(r + 1) * T] = want["ptr"]
    assert np.asarray(answers["ok"]).all()
    assert ups > 0 and downs > 0
    assert 0 < answers["moved"][RECORDS:].sum() < UPDATES * n


def test_each_record_ends_in_a_block_of_its_last_size(served):
    engine, tape, state, answers = served
    n = engine.capacity
    ptr = answers["ptr"].reshape(-1, n)
    size = tape.size.reshape(-1, n)
    last_size, last_ptr = {}, {}
    for r in range(RECORDS):
        for t in range(n):
            last_size[t, r] = int(size[r, t])
            last_ptr[t, r] = int(ptr[r, t])
    for u in range(UPDATES):
        for t in range(n):
            k = int(tape.key[u, t])
            last_size[t, k] = int(size[RECORDS + u, t])
            last_ptr[t, k] = int(ptr[RECORDS + u, t])
    block_cls = np.asarray(state.alloc.block_cls).reshape(CORES, -1)
    held = collections.defaultdict(set)
    for (t, k), s in last_size.items():
        core = t // T
        p = last_ptr[t, k]
        assert p >= 0
        assert CLASSES[block_cls[core, p // 4096]] == _class_of(s), (t, k)
        assert p not in held[core]
        held[core].add(p)


def test_span_counts_equal_the_tape(served, tmp_path, monkeypatch):
    engine, tape, _, answers = served
    monkeypatch.setattr(spans, "_log",
                        collections.deque(maxlen=spans.MAX_RECORDS))
    monkeypatch.setattr(spans, "_was_enabled", False)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        _segmented(engine, tape)
        _, resps = engine.run(tape)
        response_host(resps)
    finally:
        jax.profiler.stop_trace()
    reallocs = int((tape.op == heap.OP_REALLOC).sum())
    moved = int(answers["moved"].sum())
    assert reallocs == UPDATES * engine.capacity and moved > 0
    assert spans.count("serve/segment", "reallocs") == reallocs
    assert spans.count("serve/session", "reallocs") == reallocs
    # two segments' readbacks, then the whole session's
    assert [r.counts for r in spans.records()
            if r.name == "serve/readback"] == [
        {"moved": 0}, {"moved": moved}, {"moved": moved}]
