"""YCSB-A cells: a per-tasklet key-value store, loaded once, then sessions of
updates, each a REALLOC of one record to its new size.

Every tasklet owns a partition of `records_per_tasklet` records; a record is
one heap block of `key_bytes` + `fieldcount` field lengths of
`field_length_bytes` + its fields' bytes, each field 1-`fieldlength` B.

- Load (in set-up, once): L = `records_per_tasklet` rounds; in round k each
  tasklet inserts its record k (a MALLOC of its bytes). It runs as segment
  [0, L) of a slot file of L + U rounds through `ScanEngine.run_segment` on
  a fresh fleet; the entry keeps the loaded state, the slot file and the
  load's answers.
- Session: U = `update_rounds` rounds; in each, each tasklet picks a record
  of its partition by YCSB's scrambled Zipfian, rewrites one field at a fresh
  uniform length and REALLOCs the record to its new size, its pointer a
  slot reference to the record's latest answer. It runs as segment
  [L, L + U) on device copies of the loaded state and slot file, so every
  session starts from the same store and its seed draws only the updates.
- Warm-up: loads the store (drawn from the warm-up's seed), then runs a
  segment of the session's shape with every entry idle.

A session's record holds the load's grids and answers followed by its own,
so `checks` replays all L + U rounds from a fresh heap; the warm-up's holds
its idle rounds alone. Spans: run (the
copies, `run_segment` and `block_until_ready`), readback.
"""
from __future__ import annotations

import types

import numpy as np

from bench import checks
from bench.window import Session

GRIDS = ("op", "size", "ptr_ref", "ptr_raw")
FNV_OFFSET_64 = 0xCBF29CE484222325
FNV_PRIME_64 = 0x100000001B3
# ScrambledZipfianGenerator's constants: its Zipfian runs over ITEM_COUNT + 1
# items, whose zeta at ZIPFIAN_CONSTANT it takes as ZETAN
ITEM_COUNT = 10_000_000_000
ZETAN = 26.46902820178302
ZIPFIAN_CONSTANT = 0.99


def record_bytes(config: dict, field_sum) -> np.ndarray:
    """A record's bytes from the sum of its field lengths."""
    return (config["key_bytes"]
            + config["fieldcount"] * config["field_length_bytes"]
            + np.asarray(field_sum, np.int64))


def fnvhash64(values) -> np.ndarray:
    """YCSB's `Utils.fnvhash64`: FNV-1a over the value's eight bytes, low
    byte first, and the absolute value of the signed 64-bit result."""
    val = np.asarray(values, np.uint64)
    h = np.full(val.shape, FNV_OFFSET_64, np.uint64)
    with np.errstate(over="ignore"):
        for i in range(8):
            h = (h ^ ((val >> np.uint64(8 * i)) & np.uint64(0xFF))) \
                * np.uint64(FNV_PRIME_64)
    return np.abs(h.view(np.int64))


def zipfian(items: int, theta: float, zetan: float,
            u: np.ndarray) -> np.ndarray:
    """Popularity ranks for uniform draws `u`, by YCSB's
    `ZipfianGenerator.nextLong` (Gray et al.'s method) over `items` items
    whose zeta(items, theta) is `zetan`: rank 0 with probability 1 / zetan,
    rank 1 with 2**-theta of that."""
    alpha = 1.0 / (1.0 - theta)
    zeta2 = 1.0 + 0.5 ** theta
    eta = (1.0 - (2.0 / items) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)
    uz = u * zetan
    tail = (items * (eta * u - eta + 1.0) ** alpha).astype(np.int64)
    rank = np.where(uz < zeta2, 1, tail)
    return np.where(uz < 1.0, 0, rank)


def scrambled_zipfian(n: int, theta: float, u: np.ndarray) -> np.ndarray:
    """Records in [0, n) for uniform draws `u`, by YCSB's
    `ScrambledZipfianGenerator(0, n - 1)`: a rank of its Zipfian over
    ITEM_COUNT + 1 items with the precomputed ZETAN, hashed onto the records
    as fnvhash64(rank) % n. YCSB precomputes ZETAN only for theta 0.99."""
    if theta != ZIPFIAN_CONSTANT:
        raise ValueError(f"YCSB's scrambled Zipfian has no ZETAN for theta "
                         f"{theta} (only {ZIPFIAN_CONSTANT})")
    rank = zipfian(ITEM_COUNT + 1, theta, ZETAN, u)
    return fnvhash64(rank) % n


def _grids(op, size, ptr_ref, shape) -> dict:
    rounds = op.shape[0]
    grid = (rounds,) + tuple(shape)
    return {"op": op.reshape(grid), "size": size.reshape(grid),
            "ptr_ref": ptr_ref.reshape(grid),
            "ptr_raw": np.full(grid, -1, np.int32)}


def load_tape(config: dict, shape: tuple, seed: int):
    """The load for `shape` = (R, C, T) and `seed`: (field lengths
    [N, L, fieldcount], the load's grids [L, R, C, T])."""
    n = int(np.prod(shape))
    records = config["records_per_tasklet"]
    rng = np.random.default_rng(seed)
    lengths = rng.integers(1, config["fieldlength"] + 1,
                           size=(n, records, config["fieldcount"]),
                           dtype=np.int16)
    size = record_bytes(config, lengths.sum(-1, dtype=np.int64)).T
    op = np.full((records, n), checks.OP_MALLOC, np.int32)
    return lengths, _grids(op, size.astype(np.int32),
                           np.full((records, n), -1, np.int32), shape)


def update_tape(config: dict, traffic: dict, shape: tuple, lengths,
                seed: int) -> dict:
    """The update grids [U, R, C, T] of one session on a store loaded with
    field lengths `lengths` (left as they are), drawn from `seed`."""
    n = int(np.prod(shape))
    records = config["records_per_tasklet"]
    rounds = traffic["update_rounds"]
    fields = config["fieldcount"]
    rng = np.random.default_rng(seed)
    key = scrambled_zipfian(records, traffic["zipfian_constant"],
                            rng.random((rounds, n)))
    field = rng.integers(0, fields, size=(rounds, n))
    new_len = rng.integers(1, config["fieldlength"] + 1, size=(rounds, n))
    thread = np.arange(n, dtype=np.int32)
    rec = thread * records + key              # [U, N] flat record ids
    at = rec * fields + field                 # [U, N] flat field ids
    flat = lengths.reshape(-1).copy()
    field_sum = lengths.sum(-1, dtype=np.int32).reshape(-1)
    # the slot of each record's latest answer: its load round's, at first
    last = (np.arange(records, dtype=np.int32)[None, :] * n
            + thread[:, None]).reshape(-1)
    op = np.full((rounds, n), checks.OP_REALLOC, np.int32)
    size = np.empty((rounds, n), np.int32)
    ptr_ref = np.empty((rounds, n), np.int32)
    for u in range(rounds):
        field_sum[rec[u]] += new_len[u] - flat[at[u]]
        flat[at[u]] = new_len[u]
        size[u] = record_bytes(config, field_sum[rec[u]])
        ptr_ref[u] = last[rec[u]]
        last[rec[u]] = (records + u) * n + thread
    return _grids(op, size, ptr_ref, shape)


def idle_grids(shape: tuple, rounds: int) -> dict:
    z = np.zeros((rounds, int(np.prod(shape))), np.int32)
    return _grids(z, z, np.full_like(z, -1), shape)


class Entry:
    def __init__(self, system_cfg, config: dict, traffic: dict):
        from repro.launch.serving import ScanEngine
        self.config = config
        self.traffic = traffic
        self.engine = ScanEngine(system_cfg, config["num_ranks"],
                                 config["cores_per_rank"], mesh=False)
        self.load_rounds = config["records_per_tasklet"]
        self.update_rounds = traffic["update_rounds"]
        self.store = None

    def load(self, seed: int):
        """Loads the store drawn from `seed` on a fresh fleet and keeps it."""
        import jax
        import jax.numpy as jnp
        from repro.core import heap
        from repro.launch.serving import response_host
        engine = self.engine
        lengths, grids = load_tape(self.config, engine.shape, seed)
        state = heap.sharded_init(engine.cfg, engine.num_ranks,
                                  engine.num_cores)
        slots = jnp.full(((self.load_rounds + self.update_rounds)
                          * engine.capacity,), -1, jnp.int32)
        state, slots, resps = engine.run_segment(
            state, slots, 0, tuple(grids[g] for g in GRIDS))
        jax.block_until_ready((state, slots))
        host = response_host(resps)
        self.store = types.SimpleNamespace(
            state=state, slots=slots, lengths=lengths,
            record={"grids": grids,
                    "answers": {f: host[f] for f in checks.FIELDS}})

    def _serve(self, grids: dict, spans, after_load: bool) -> Session:
        """Serves `grids` on copies of the loaded store. Its record holds
        the load before it where `after_load`, so that a replay from a
        fresh heap reaches the state it was served on."""
        import jax
        import jax.numpy as jnp
        from repro.launch.serving import response_host
        store = self.store
        with spans("run"):
            state = jax.tree.map(jnp.copy, store.state)
            slots = jnp.copy(store.slots)
            state, slots, resps = self.engine.run_segment(
                state, slots, self.load_rounds,
                tuple(grids[g] for g in GRIDS))
            jax.block_until_ready((state, slots, resps))
        with spans("readback"):
            host = response_host(resps)
        del state, slots, resps
        op = grids["op"]
        failed = int(((op == checks.OP_REALLOC)
                      & ~(host["ok"] & (host["ptr"] >= 0))).sum())
        ops = checks.served_ops(op)
        record = {"grids": grids,
                  "answers": {f: host[f] for f in checks.FIELDS}}
        if after_load:
            record = {k: {f: np.concatenate([store.record[k][f], v[f]])
                          for f in v}
                      for k, v in record.items()}
        return Session(ops=ops, rounds=int(op.shape[0]), attempted=ops,
                       failed=failed, record=record)

    def warm(self, seed: int, spans) -> Session:
        """Loads the store, then serves a session of the same shape with
        every entry idle: idle answers from any heap, so its record leaves
        the load out (each session's record holds it)."""
        self.load(seed)
        return self._serve(idle_grids(self.engine.shape, self.update_rounds),
                           spans, after_load=False)

    def session(self, seed: int, spans) -> Session:
        return self._serve(update_tape(self.config, self.traffic,
                                       self.engine.shape, self.store.lengths,
                                       seed), spans, after_load=True)

    def host_answers(self, record) -> dict:
        return record["answers"]

    def report_numbers(self, records) -> dict:
        return {}
