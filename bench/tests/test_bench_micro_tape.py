"""The micro tape is closed per core, exports through ScanEngine.trace, and
gives every seed the same sizes; the reference copy agrees with the
repository's oracle; the fleet-wide checks catch what they should."""
import json

import numpy as np
import pytest

from bench_tiny import REPO
from bench import checks, spec
from bench.entries.micro_tape import build_tape, size_multiset

TRAFFIC = json.loads((REPO / "bench/traffic/micro_fig14.json").read_text())
CONFIG = json.loads((REPO / "bench/configs/upmem512_sw.json").read_text())
SHAPE = (2, 3, 4)


def test_sizes_are_one_multiset_in_seeded_orders():
    a = build_tape(TRAFFIC, SHAPE, 1)
    b = build_tape(TRAFFIC, SHAPE, 2)
    rounds = TRAFFIC["alloc_rounds"]
    assert not np.array_equal(a.size, b.size)
    assert np.array_equal(np.sort(a.size[:rounds], axis=None),
                          np.sort(b.size[:rounds], axis=None))
    assert np.array_equal(a.size, build_tape(TRAFFIC, SHAPE, 1).size)
    s = size_multiset(TRAFFIC["size_mix"], 131072)
    assert s.size == 131072
    assert (s == 4096).sum() == 2621 and (s == 256).sum() == 49807


def test_every_free_names_a_slot_of_its_own_thread():
    tape = build_tape(TRAFFIC, SHAPE, 5)
    R, C, T = SHAPE
    n = R * C * T
    rounds = TRAFFIC["alloc_rounds"]
    assert tape.op.shape == (2 * rounds, R, C, T)
    assert (tape.op[:rounds] == checks.OP_MALLOC).all()
    assert (tape.op[rounds:] == checks.OP_FREE).all()
    ref = tape.ptr_ref[rounds:].reshape(rounds, n)
    grid = np.arange(n)
    assert np.array_equal(ref % n, np.broadcast_to(grid, ref.shape))
    assert np.array_equal(ref // n, np.broadcast_to(
        np.arange(rounds)[:, None], ref.shape))


def test_scan_engine_exports_every_core():
    from repro.core import system as sysm
    from repro.launch.serving import ScanEngine
    cfg = sysm.SystemConfig(kind="sw", heap_bytes=1 << 20, num_threads=4)
    engine = ScanEngine(cfg, 2, 3)
    tape = build_tape(TRAFFIC, SHAPE, 9)
    for rk in range(2):
        for ck in range(3):
            tr = engine.trace(tape, rk, ck)
            assert np.array_equal(tr.op, tape.op[:, rk, ck])
            frees = tr.ptr_ref[TRAFFIC["alloc_rounds"]:]
            assert np.array_equal(frees % 4, np.broadcast_to(
                np.arange(4), frees.shape))


def _replay_answers(config, grids):
    """Answers of every core computed by the reference itself."""
    ref = spec.load_module(REPO, "reference", "pim_malloc")
    rounds, R, C, T = grids["op"].shape
    n = R * C * T
    host = {f: np.zeros(grids["op"].shape,
                        np.int32 if f in ("ptr", "path") else bool)
            for f in checks.FIELDS}
    for c in range(R * C):
        rk, ck = divmod(c, C)
        heap = ref.make(config)
        slots = np.full(rounds * T, -1)
        for r in range(rounds):
            refs = grids["ptr_ref"][r, rk, ck]
            local = (refs // n) * T + refs % n - c * T
            ptr = np.where(refs >= 0,
                           slots[np.clip(local, 0, slots.size - 1)], -1)
            want = heap.request(grids["op"][r, rk, ck].tolist(),
                                grids["size"][r, rk, ck].tolist(),
                                ptr.tolist())
            for f in checks.FIELDS:
                host[f][r, rk, ck] = want[f]
            slots[r * T:(r + 1) * T] = want["ptr"]
    return host


@pytest.fixture(scope="module")
def tape_and_answers():
    config = dict(CONFIG, num_ranks=2, cores_per_rank=3, num_threads=4,
                  heap_bytes=1 << 20)
    tape = build_tape(TRAFFIC, SHAPE, 3)
    grids = {"op": tape.op, "size": tape.size, "ptr_ref": tape.ptr_ref,
             "ptr_raw": tape.ptr_raw}
    return config, grids, _replay_answers(config, grids)


def test_checks_pass_the_reference_answers(tape_and_answers):
    config, grids, host = tape_and_answers
    ref = spec.load_module(REPO, "reference", "pim_malloc")
    assert checks.guarantees(config, grids, host) == {
        "overlapping_blocks": 0, "dropped_frees": 0, "unanswered_ops": 0,
        "failed_allocs": 0}
    bad, compared = checks.reference_mismatches(ref, config, grids, host,
                                                range(6))
    assert bad == 0 and compared == checks.served_ops(grids["op"])


def test_checks_catch_a_moved_pointer_and_a_dropped_free(tape_and_answers):
    config, grids, host = tape_and_answers
    ref = spec.load_module(REPO, "reference", "pim_malloc")
    moved = {k: v.copy() for k, v in host.items()}
    moved["ptr"][1, 1, 2, 0] = moved["ptr"][0, 1, 2, 0]   # same block twice
    assert checks.guarantees(config, grids, moved)["overlapping_blocks"] > 0
    assert checks.reference_mismatches(ref, config, grids, moved,
                                       range(6))[0] > 0
    dropped = {k: v.copy() for k, v in host.items()}
    dropped["path"][-1, 0, 0, 1] = 2
    assert checks.guarantees(config, grids, dropped)["dropped_frees"] == 1


def test_reference_copy_agrees_with_the_repository_oracle():
    """The copy kept with the benchmark answers as the repository's own
    oracle does, on a seeded stream of mixed ops."""
    from repro.core.oracle import PyPimMalloc
    ref = spec.load_module(REPO, "reference", "pim_malloc")
    config = dict(CONFIG, num_threads=4, heap_bytes=1 << 16)
    mine = ref.make(config)
    theirs = PyPimMalloc(heap_bytes=1 << 16, num_threads=4,
                         size_classes=tuple(CONFIG["size_classes"]),
                         block_bytes=4096, cap=1024)
    rng = np.random.default_rng(0)
    live = []
    for _ in range(300):
        op, size, ptr = [], [], []
        for _ in range(4):
            kind = rng.integers(0, 4)
            p = live.pop(rng.integers(len(live))) if live and kind >= 2 else -1
            op.append([1, 4, 2, 3][kind] if p >= 0 or kind < 2 else 1)
            size.append(int(rng.choice([0, 8, 40, 300, 2048, 5000]))
                        if op[-1] != 2 else 0)
            ptr.append(p)
        a = mine.request(op, size, ptr)
        b = theirs.request(op, size, ptr)
        assert a == b
        live.extend(p for p, o in zip(a["ptr"], op) if p >= 0 and o != 2)
