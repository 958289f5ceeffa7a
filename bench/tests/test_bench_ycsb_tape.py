"""The YCSB-A tape is closed per thread and shaped as the workload says, its
seed draws only the updates, the two readers of its metrics read a span log
made by hand, and the cell runs through the harness at a tiny size."""
import collections
import json
import sys
import time
import types

import numpy as np
import pytest

import repro.runtime
from bench_tiny import REPO, TINY, tiny_root
from bench import checks, harness, spec
from bench.entries.ycsb_tape import (ITEM_COUNT, ZETAN, fnvhash64, load_tape,
                                     scrambled_zipfian, update_tape, zipfian)
from repro.runtime import spans

CONFIG = json.loads((REPO / "bench/configs/ycsb512_sw.json").read_text())
TRAFFIC = json.loads((REPO / "bench/traffic/ycsb_a.json").read_text())
SHAPE = (1, 2, 4)
N = 8
L = CONFIG["records_per_tasklet"]


def _updates(seed, rounds=TRAFFIC["update_rounds"], load_seed=1):
    lengths, load = load_tape(CONFIG, SHAPE, load_seed)
    traffic = dict(TRAFFIC, update_rounds=rounds)
    return lengths, load, update_tape(CONFIG, traffic, SHAPE, lengths, seed)


def _records(upd):
    """The record each update names, found by following its slot reference
    back to the load round that inserted the record."""
    ref = upd["ptr_ref"].reshape(-1, N)
    record = {k * N + t: k for k in range(L) for t in range(N)}
    out = np.empty(ref.shape, np.int64)
    for u in range(ref.shape[0]):
        for t in range(N):
            out[u, t] = record[int(ref[u, t])]
            record[(L + u) * N + t] = out[u, t]
    return out


def test_every_realloc_names_its_records_latest_answer_on_its_thread():
    _, load, upd = _updates(3)
    assert (load["op"] == checks.OP_MALLOC).all()
    assert (upd["op"] == checks.OP_REALLOC).all()
    assert (load["ptr_ref"] == -1).all()
    ref = upd["ptr_ref"].reshape(-1, N)
    rounds = ref.shape[0]
    # on its own thread, in an earlier round
    assert (ref % N == np.arange(N)).all()
    assert (ref // N < L + np.arange(rounds)[:, None]).all()
    # each answer is named at most once: an update never names an answer
    # that a later one of its record has replaced
    assert np.unique(ref).size == ref.size
    # and the chain of answers of a record starts at its insert
    size = np.concatenate([load["size"], upd["size"]]).reshape(-1, N)
    step = size[L:] - size[ref // N, np.arange(N)]
    assert np.abs(step).max() <= CONFIG["fieldlength"] - 1
    assert (step != 0).any()


def test_record_sizes_and_uniform_field_lengths():
    lengths, load, upd = _updates(4)
    low = (CONFIG["key_bytes"] + CONFIG["fieldcount"]
           * (CONFIG["field_length_bytes"] + 1))
    high = low + CONFIG["fieldcount"] * (CONFIG["fieldlength"] - 1)
    assert (low, high) == (54, 1044)
    for g in (load, upd):
        assert low <= g["size"].min() and g["size"].max() <= high
    assert lengths.shape == (N, L, CONFIG["fieldcount"])
    counts = np.bincount(lengths.ravel(), minlength=101)
    assert counts[0] == 0 and counts.size == 101
    expect = lengths.size / 100
    assert np.abs(counts[1:] - expect).max() < 5 * np.sqrt(expect)
    # ~34% of records fit the 512 B class at the published widths
    big = load_tape(CONFIG, (1, 8, 16), 5)[1]["size"]
    assert 0.30 < (big <= 512).mean() < 0.39


def _fnvhash64(value: int) -> int:
    """Java's `Utils.fnvhash64`, one long at a time."""
    h = 0xCBF29CE484222325
    for _ in range(8):
        h = ((h ^ (value & 0xFF)) * 1099511628211) % 2 ** 64
        value >>= 8
    return abs(h - 2 ** 64 if h >= 2 ** 63 else h)


def test_top_record_takes_its_zipfian_share():
    theta = TRAFFIC["zipfian_constant"]
    values = [0, 1, 255, 256, 65_537, 123_456_789, ITEM_COUNT]
    np.testing.assert_array_equal(fnvhash64(values),
                                  [_fnvhash64(v) for v in values])
    # the Zipfian over ITEM_COUNT + 1 items: its first two ranks exactly
    rng = np.random.default_rng(0)
    rank = zipfian(ITEM_COUNT + 1, theta, ZETAN, rng.random(400_000))
    assert 0 <= rank.min() and rank.max() <= ITEM_COUNT
    assert (rank == 0).mean() == pytest.approx(1 / ZETAN, abs=0.002)
    assert (rank == 1).mean() == pytest.approx(2 ** -theta / ZETAN,
                                               abs=0.002)
    # a record's share: the ranks hashed onto it, the first 2**20 of them
    # at their Zipfian weight and the rest spread evenly
    r = np.arange(1 << 20)
    p = (r + 1.0) ** -theta / ZETAN
    share = (np.bincount(fnvhash64(r) % L, weights=p, minlength=L)
             + (1 - p.sum()) / L)
    top = _fnvhash64(0) % L
    assert np.argmax(share) == top
    assert 0.038 < share[top] < 0.045      # not a Zipfian over L: 1/H ~ 16%
    # through the tape: each partition's most updated record
    rec = _records(_updates(5, rounds=1500)[2])
    got = [np.bincount(rec[:, t], minlength=L) / rec.shape[0]
           for t in range(N)]
    assert np.mean([g[top] for g in got]) == pytest.approx(share[top],
                                                           abs=0.004)
    assert all(np.argmax(g) == top for g in got)
    with pytest.raises(ValueError):
        scrambled_zipfian(L, 0.9, rng.random(4))


def test_a_seed_changes_only_the_update_stream():
    lengths, load, a = _updates(6)
    kept = lengths.copy()
    _, load_b, b = _updates(7)
    for g in ("op", "size", "ptr_ref", "ptr_raw"):
        np.testing.assert_array_equal(load[g], load_b[g])
    assert not np.array_equal(a["size"], b["size"])
    assert not np.array_equal(a["ptr_ref"], b["ptr_ref"])
    np.testing.assert_array_equal(a["op"], b["op"])
    again = update_tape(CONFIG, TRAFFIC, SHAPE, lengths, 6)
    for g in a:
        np.testing.assert_array_equal(a[g], again[g])
    np.testing.assert_array_equal(lengths, kept)
    other = _updates(6, load_seed=2)[1]
    assert not np.array_equal(load["size"], other["size"])


def _read(name, ctx):
    return spec.load_module(REPO, "metrics", name).read(ctx)


@pytest.fixture
def log(monkeypatch):
    records = collections.deque(maxlen=spans.MAX_RECORDS)
    monkeypatch.setattr(spans, "_log", records)
    return records


def _segment(sid, t0, seconds, rounds, reallocs, moved):
    return [
        spans.Record(sid, "serve/segment", t0, t0 + seconds, None, sid,
                     {"rounds": rounds, "h2d_bytes": 1, "reallocs": reallocs}),
        spans.Record(sid + 1, "serve/readback", t0 + 5.0, t0 + 5.1, None,
                     sid + 1, {"moved": moved}),
    ]


def test_readers_on_a_span_log(log, monkeypatch):
    ctx = types.SimpleNamespace(spans={"run": 20.0})
    assert _read("realloc_moved_share", ctx) is None
    assert _read("segment_wait_ms_per_round", ctx) is None
    log.extend(_segment(0, 1.0, 0.5, rounds=100, reallocs=800, moved=80))
    log.extend(_segment(2, 9.0, 0.25, rounds=300, reallocs=2400, moved=360))
    assert _read("realloc_moved_share", ctx) == pytest.approx(
        100.0 * 440 / 3200)
    assert _read("segment_wait_ms_per_round", ctx) == pytest.approx(
        1e3 * (20.0 - 0.75) / 400)
    assert _read("segment_wait_ms_per_round",
                 types.SimpleNamespace(spans={})) is None
    # a program whose spans count no realloc, and one with no span log
    log.clear()
    log.append(spans.Record(0, "serve/segment", 0.0, 1.0, None, 0,
                            {"rounds": 4, "h2d_bytes": 1}))
    assert _read("realloc_moved_share", ctx) is None
    assert _read("segment_wait_ms_per_round", ctx) == pytest.approx(4750.0)
    monkeypatch.setitem(sys.modules, "repro.runtime.spans", None)
    monkeypatch.delattr(repro.runtime, "spans")
    assert _read("realloc_moved_share", ctx) is None
    assert _read("segment_wait_ms_per_round", ctx) is None


def _tiny_ycsb(tmp):
    """`tiny_root` with the YCSB cell at the tiny size beside its cells."""
    root = tiny_root(tmp)
    cfg = dict(CONFIG, name="tiny_ycsb", **TINY, records_per_tasklet=32)
    (root / "bench/configs/tiny_ycsb.json").write_text(json.dumps(cfg))
    traffic = dict(TRAFFIC, update_rounds=16)
    (root / "bench/traffic/tiny_ycsb_a.json").write_text(json.dumps(traffic))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny_ycsb", "source": CONFIG["source"],
                             "file": "bench/configs/tiny_ycsb.json",
                             "reduced": sorted(TINY), "why": "tiny"})
    bench["workloads"].append({"name": "tiny_ycsb_a", "config": "tiny_ycsb",
                               "traffic": "tiny_ycsb_a", "chips": 1,
                               "why": "tiny"})
    cells = {m["name"]: m.get("workloads", ())
             for m in json.loads((REPO / "BENCHMARK.json").read_text())
             ["per_layer"]}
    for m in bench["per_layer"]:
        if "sw512_ycsb_a" in cells[m["name"]]:
            m["workloads"].append("tiny_ycsb_a")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_the_cell_runs_through_the_harness(tmp_path):
    root = _tiny_ycsb(tmp_path)
    r = harness.run_cell(root, "tiny_ycsb_a", 2**31 + 5, 0.5, True,
                         time.perf_counter(), check_chip=False)
    assert r["correct"] and r["failed"] == 0
    assert all(c["value"] == 0 for c in r["checks"].values())
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert 0 < m["realloc_moved_share"] < 100
    assert 0 < m["segment_wait_ms_per_round"] <= m["scan_ms_per_round"]
    assert m["compiles_in_window"] == 0
    assert "scan_wait_ms_per_round" not in m
    # each session serves one REALLOC per thread per update round
    assert r["attempted"] % (16 * 16) == 0 and r["attempted"] > 0


@pytest.mark.parametrize("fault", ["state_unchanged", "altered_answer",
                                   "half_batch"])
def test_the_checks_catch_a_planted_fault(tmp_path, fault):
    root = _tiny_ycsb(tmp_path)
    r = harness.run_cell(root, "tiny_ycsb_a", 2**31 + 9, 0.1, False,
                         time.perf_counter(), check_chip=False, fault=fault)
    assert not r["correct"]
    assert r["checks"]["reference_mismatches"]["value"] > 0
