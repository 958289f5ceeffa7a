"""Micro cells: one closed tape run on every thread of every core.

For `alloc_rounds` rounds each thread allocates once per round, with sizes
drawn from `size_mix`; for as many rounds more each thread frees the same
blocks, in allocation order, through slot references. Every seed gets the
same multiset of sizes in another order, so seeds change no work. A
session runs its tape on a fresh fleet (`ScanEngine.run` ended by
`block_until_ready`) and reads the answers back (`serving.response_host`).
Spans: run, readback.

The ops, the slot references and the size multiset are built once; a
session's tape is one permutation of the sizes, drawn before its spans
open. The warm-up runs a tape of the same shape with every entry idle, so
it loads every program a session runs without serving a session's work.
"""
from __future__ import annotations

import types

import numpy as np

from bench import checks
from bench.window import Session


def size_multiset(size_mix: dict, n: int) -> np.ndarray:
    """`n` sizes in the mix's shares, rounded down, the remainder given to
    the commonest size; sorted, so that a seed only orders them."""
    sizes = np.asarray([int(s) for s in size_mix], np.int64)
    shares = np.asarray([float(w) for w in size_mix.values()])
    counts = np.floor(shares / shares.sum() * n).astype(np.int64)
    counts[int(np.argmax(shares))] += n - counts.sum()
    return np.repeat(sizes, counts)


def skeleton(traffic: dict, shape: tuple):
    """The tape for `shape` = (R, C, T) with its sizes sorted, as a plan
    `ScanEngine.run` takes; `with_sizes` orders them for a seed."""
    R, C, T = shape
    n = R * C * T
    rounds = traffic["alloc_rounds"]
    op = np.zeros((2 * rounds, n), np.int32)
    size = np.zeros_like(op)
    ptr_ref = np.full_like(op, -1)
    op[:rounds] = checks.OP_MALLOC
    size[:rounds] = size_multiset(traffic["size_mix"],
                                  rounds * n).reshape(rounds, n)
    op[rounds:] = checks.OP_FREE
    # the free in round rounds + i names the slot that round i's malloc on
    # the same thread filled
    ptr_ref[rounds:] = (np.arange(rounds, dtype=np.int64)[:, None] * n
                        + np.arange(n)[None, :])
    grid = (2 * rounds, R, C, T)
    return types.SimpleNamespace(
        shape=shape, placement="tape", op=op.reshape(grid),
        size=size.reshape(grid), ptr_ref=ptr_ref.reshape(grid),
        ptr_raw=np.full(grid, -1, np.int32))


def with_sizes(tape, seed: int):
    """`tape` with its malloc sizes in the order `seed` draws."""
    rounds = tape.op.shape[0] // 2
    alloc = tape.size[:rounds]
    rng = np.random.default_rng(seed)
    size = tape.size.copy()
    size[:rounds] = rng.permutation(alloc.ravel()).reshape(alloc.shape)
    return types.SimpleNamespace(**dict(vars(tape), size=size))


def build_tape(traffic: dict, shape: tuple, seed: int):
    """The tape of one session for `shape` = (R, C, T) and `seed`."""
    return with_sizes(skeleton(traffic, shape), seed)


class Entry:
    def __init__(self, system_cfg, config: dict, traffic: dict):
        from repro.launch.serving import ScanEngine
        self.config = config
        self.traffic = traffic
        self.engine = ScanEngine(system_cfg, config["num_ranks"],
                                 config["cores_per_rank"], mesh=False)
        self.skeleton = skeleton(traffic, self.engine.shape)

    def _serve(self, tape, spans) -> Session:
        import jax
        from repro.launch.serving import response_host
        with spans("run"):
            state, resps = self.engine.run(tape)
            jax.block_until_ready((state, resps))
        with spans("readback"):
            host = response_host(resps)
        del state, resps
        answers = {f: host[f] for f in checks.FIELDS}
        op = tape.op
        ops = checks.served_ops(op)
        alloc = (op != checks.OP_FREE) & (op != checks.OP_NOOP)
        failed = int((alloc & ~answers["ok"]).sum()
                     + ((op == checks.OP_FREE) & (answers["path"] == 2)).sum())
        grids = {"op": op, "size": tape.size, "ptr_ref": tape.ptr_ref,
                 "ptr_raw": tape.ptr_raw}
        return Session(ops=ops, rounds=int(op.shape[0]), attempted=ops,
                       failed=failed,
                       record={"grids": grids, "answers": answers})

    def warm(self, seed: int, spans) -> Session:
        """A session of the same shape with every entry idle."""
        idle = types.SimpleNamespace(**dict(
            vars(self.skeleton), op=np.zeros_like(self.skeleton.op),
            size=np.zeros_like(self.skeleton.size),
            ptr_ref=np.full_like(self.skeleton.ptr_ref, -1)))
        return self._serve(idle, spans)

    def session(self, seed: int, spans) -> Session:
        return self._serve(with_sizes(self.skeleton, seed), spans)

    def host_answers(self, record) -> dict:
        return record["answers"]

    def report_numbers(self, records) -> dict:
        return {}
