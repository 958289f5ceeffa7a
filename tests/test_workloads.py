"""The allocation-trace workload engine: recorder, tapes, replay, parity.

Acceptance for the workloads subsystem: the three committed tapes replay
bitwise-deterministically on every registered backend, with sw/hwsw
agreeing on the semantic response stream and heap-telemetry conservation
holding on every kind; misuse (invalid frees) surfaces in the replayer's
report instead of vanishing.
"""
import glob
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import heap
from repro.workloads.hashtable import HashTableConfig, HashTableWorkload
from repro.workloads.replay import (check_trace, replay, replay_all_kinds)
from repro.workloads.trace import RecordingAllocator, Trace

TAPES_DIR = os.path.join(os.path.dirname(__file__), os.pardir,
                         "benchmarks", "tapes")
TAPES = sorted(glob.glob(os.path.join(TAPES_DIR, "*.json")))


# ------------------------------------------------------------- the recorder
def _tiny_recording(kind="hwsw"):
    rec = RecordingAllocator(heap_bytes=1 << 19, num_threads=4, kind=kind)
    r0 = rec.request(heap.malloc_request(
        jnp.array([16, 100, 2048, 8192], jnp.int32)))
    rec.request(heap.realloc_request(
        r0.ptr, jnp.array([300, 100, 0, 16384], jnp.int32)))
    rec.request(heap.free_request(
        jnp.array([-1, int(r0.ptr[1]), -1, -1], jnp.int32)))
    return rec, r0


def test_recorder_slot_refs_point_at_producers():
    rec, r0 = _tiny_recording()
    trace = rec.finish("tiny", "unit")
    T = 4
    # round 1 realloc'd round-0 pointers: refs name slot 0*T + t
    assert trace.ptr_ref[1, 0] == 0        # thread 0 realloc(ptr from r0)
    assert trace.ptr_ref[1, 2] == 2        # realloc(p, 0) == free ref
    assert trace.op[1, 2] == heap.OP_FREE  # builder normalized it
    # round 2 freed thread 1's ORIGINAL pointer (realloc was in-place for
    # t=1: same class) -> ref points at the round-1 realloc slot (latest
    # producer of that pointer value)
    assert trace.ptr_ref[2, 1] == 1 * T + 1
    # NULL frees carry no ref and stay NOOP
    assert trace.ptr_ref[2, 0] == -1 and trace.op[2, 0] == heap.OP_NOOP


def test_trace_json_roundtrip(tmp_path):
    rec, _ = _tiny_recording()
    trace = rec.finish("tiny", "unit", meta={"x": 1})
    p = str(tmp_path / "t.json")
    trace.save(p)
    back = Trace.load(p)
    # finish() stamps max_size_class so trace_lint's epoch rule knows the
    # small/big boundary without the recording config
    assert back.name == trace.name
    assert back.meta == {"x": 1, "max_size_class": 2048}
    for f in ("op", "size", "ptr_ref", "ptr_raw"):
        np.testing.assert_array_equal(getattr(back, f), getattr(trace, f))


def test_replay_reproduces_recording_bitwise():
    """Closed-loop replay on the recorded kind returns the recorded
    pointers (slot refs resolve to the same stream)."""
    rec, r0 = _tiny_recording()
    trace = rec.finish("tiny", "unit")
    resps, _, report = replay(trace, "hwsw")
    np.testing.assert_array_equal(np.asarray(resps.ptr[0]),
                                  np.asarray(r0.ptr))
    assert report["ops"] == trace.ops
    # determinism: an identical second replay gives an identical stream
    _, _, report2 = replay(trace, "hwsw")
    assert report2["digest_full"] == report["digest_full"]


# ------------------------------------------------- committed-tape acceptance
def test_committed_tapes_exist():
    assert len(TAPES) >= 4, TAPES
    names = {os.path.basename(p) for p in TAPES}
    assert {"graph_churn.json", "kv_paged.json", "hashtable.json",
            "decode_serve.json"} <= names


@pytest.mark.parametrize("path", TAPES, ids=os.path.basename)
def test_committed_tape_cross_backend_contract(path):
    """Acceptance: every backend replays the tape to its committed digest,
    sw == hwsw on semantics, conservation holds."""
    trace = Trace.load(path)
    assert set(trace.expect) == set(heap.kinds())
    errs = check_trace(trace)
    assert errs == []


@pytest.mark.parametrize("path", TAPES, ids=os.path.basename)
def test_replay_reports_carry_telemetry(path):
    trace = Trace.load(path)
    _, _, rep = replay(trace, "sw")
    tel = rep["telemetry"]
    assert tel["conservation_residual"] == 0
    assert tel["hwm_bytes"] >= tel["live_bytes"] > 0
    assert 0.0 <= tel["utilization"] <= 1.0
    assert len(tel["free_blocks_per_level"]) >= 1
    assert rep["us_per_op"] > 0 and rep["dropped_frees"] == 0


# ------------------------------------------------------- misuse visibility
def test_replay_surfaces_invalid_frees():
    """A tape carrying garbage frees reports them as dropped on every kind
    (the free_request/-Stats.dropped_frees bugfix, end to end)."""
    rec = RecordingAllocator(heap_bytes=1 << 19, num_threads=4, kind="hwsw")
    r0 = rec.request(heap.malloc_request(jnp.full((4,), 64, jnp.int32)))
    rec.request(heap.free_request(r0.ptr))
    # garbage negative, out-of-heap, and an in-range pointer in a block no
    # allocator structure tracks (past the 32 prepopulated blocks); NULL (-1)
    # stays benign
    rec.request(heap.free_request(
        jnp.array([-7, 1 << 20, 500000, -1], jnp.int32)))
    trace = rec.finish("misuse", "unit")
    for kind in heap.kinds():
        _, _, rep = replay(trace, kind)
        assert rep["dropped_frees"] == 3, kind
        if kind != "strawman":
            assert rep["stats_dropped_frees"] == 3, kind


# ------------------------------------------------------ workload functional
def test_hashtable_workload_is_functionally_real():
    cfg = HashTableConfig(num_threads=8, heap_bytes=1 << 19, n_inserts=48,
                          delete_every=4, seed=5)
    rec = RecordingAllocator(heap_bytes=cfg.heap_bytes,
                             num_threads=cfg.num_threads, kind="sw")
    wl = HashTableWorkload(cfg, rec)
    stats = wl.run()
    wl.verify()
    assert stats["grow_rounds"] >= 1          # realloc pressure happened
    assert all(c > cfg.init_capacity for c in stats["capacities"])
    assert rec.recorded_rounds > 10
    # and the recorded tape replays with full parity
    trace = rec.finish("ht_unit", "unit")
    from repro.workloads.replay import attach_expectations
    attach_expectations(trace)
    assert check_trace(trace) == []


def test_kv_paged_pool_records_through_injection():
    from repro.kvcache.paged import PAGE_UNIT, PagePool

    rec = RecordingAllocator(heap_bytes=(1 << 16) * PAGE_UNIT,
                             num_threads=8, kind="hwsw")
    pool = PagePool(n_pages=1 << 16, num_threads=8, client=rec)
    ext = pool.alloc_pages(512)
    singles, _ = pool.alloc_page_batch([True] * 4 + [False] * 4)
    pool.free_page_batch(jnp.where(jnp.asarray(singles) >= 0,
                                   jnp.asarray(singles), -1))
    pool.free_extent(int(ext[0]))
    assert rec.recorded_rounds == 4
    trace = rec.finish("kv_unit", "unit")
    results = replay_all_kinds(trace, kinds=("sw", "hwsw"))
    assert (results["sw"][1]["digest_sem"]
            == results["hwsw"][1]["digest_sem"])
    again = replay_all_kinds(trace, kinds=("hwsw",))
    assert (again["hwsw"][1]["digest_full"]
            == results["hwsw"][1]["digest_full"])


def test_kv_paged_pool_deprecated_alloc_hook_warns_but_works():
    """The PR-4 bare-handle hook keeps working through HeapClient.wrap,
    but only behind a DeprecationWarning; a handle that satisfies neither
    contract is rejected outright."""
    import pytest

    from repro.core.api import HeapClient
    from repro.kvcache.paged import PAGE_UNIT, PagePool

    rec = RecordingAllocator(heap_bytes=(1 << 16) * PAGE_UNIT,
                             num_threads=8, kind="hwsw")
    with pytest.warns(DeprecationWarning, match="client=HeapClient"):
        pool = PagePool(n_pages=1 << 16, num_threads=8, alloc=rec)
    assert pool.client is rec                 # a HeapClient passes through
    assert pool.alloc_pages(4).shape == (4,)  # and still serves pages

    # a zero-arg factory (the truly bare callable) adapts with the warning
    with pytest.warns(DeprecationWarning):
        pool2 = PagePool(
            n_pages=1 << 16, num_threads=8,
            alloc=lambda: HeapClient(heap_bytes=(1 << 16) * PAGE_UNIT,
                                     num_threads=8, kind="sw"))
    assert pool2.alloc_pages(2).shape == (2,)

    with pytest.raises(TypeError):
        HeapClient.wrap(object())


def test_deprecated_alloc_hooks_warn_exactly_once():
    """One deprecated ``alloc=`` construction emits exactly ONE
    DeprecationWarning — no duplicates from the wrap/adapter layers — for
    both remaining carriers of the hook (PagePool and DynamicGraph)."""
    import warnings

    from repro.core.api import HeapClient
    from repro.graphupd.workload import DynamicGraph, GraphConfig
    from repro.kvcache.paged import PAGE_UNIT, PagePool

    client = HeapClient(heap_bytes=(1 << 16) * PAGE_UNIT, num_threads=8,
                        kind="sw")
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        PagePool(n_pages=1 << 16, num_threads=8, alloc=client)
    dep = [x for x in w if issubclass(x.category, DeprecationWarning)]
    assert len(dep) == 1, [str(x.message) for x in dep]

    gcfg = GraphConfig(n_nodes=8, n_edges_pre=0, n_edges_new=0,
                       num_threads=4, heap_bytes=1 << 19)
    gclient = HeapClient(heap_bytes=gcfg.heap_bytes, num_threads=4,
                         kind="sw")
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        g = DynamicGraph(gcfg, alloc=gclient)
    dep = [x for x in w if issubclass(x.category, DeprecationWarning)]
    assert len(dep) == 1, [str(x.message) for x in dep]
    assert g.client is gclient

    # the supported client= path is warning-free
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        DynamicGraph(gcfg, client=gclient)
        PagePool(n_pages=1 << 16, num_threads=8, client=client)
    assert not [x for x in w if issubclass(x.category, DeprecationWarning)]


def test_graph_insert_delete_matches_reference():
    from repro.graphupd.workload import DynamicGraph, GraphConfig

    cfg = GraphConfig(n_nodes=24, n_edges_pre=0, n_edges_new=0,
                      num_threads=4, heap_bytes=1 << 19)
    g = DynamicGraph(cfg, kind="sw")
    edges = [(1, 2), (1, 3), (2, 3), (1, 4), (3, 1), (1, 2)]
    for i in range(0, len(edges), 4):
        batch = edges[i:i + 4]
        g.insert_round([u for u, _ in batch], [v for _, v in batch])
    assert g.neighbors(1) == [2, 4, 3, 2]     # LIFO adjacency
    resp = g.delete_round([1, 2], [3, 3])     # remove (1,3) and (2,3)
    assert int(resp.path[0]) == 0 and int(resp.path[1]) == 0  # small frees
    assert g.neighbors(1) == [2, 4, 2]
    assert g.neighbors(2) == []
    # deleting a non-existent edge frees nothing (NULL round slot)
    resp = g.delete_round([5], [9])
    assert int(resp.path[0]) == -1
    # the freed cells return LIFO on the next inserts
    before = int(g.state.alloc.stats.frees_small)
    assert before >= 2
