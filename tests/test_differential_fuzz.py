"""Cross-backend differential fuzzing: randomized `AllocRequest` streams
replayed through every `heap.REGISTRY` kind plus the `PyPimMalloc` oracle.

The generator emits symbolic tapes (the ``pim-malloc-trace/v1`` ref
encoding, so one stream drives every backend closed-loop against its OWN
pointers) full of allocator abuse: interleaved malloc/free/realloc/calloc,
NULL and garbage pointers, cross-round double frees, realloc-after-free,
zero/negative/overflowing sizes, and capacity-exhausting bursts. Every
stream must satisfy the repo's established contract:

  * ``sw`` == ``hwsw`` on the semantic fields (ptr/ok/path/moved),
  * heap-telemetry conservation holds for every kind (strawman included),
  * ``hwsw`` == the plain-Python `PyPimMalloc.request` oracle
    pointer-for-pointer, with conservation checked after every round.

Two deliberate generator constraints, both excluding C-level data races no
backend promises to price consistently (all the kinds still agree with
each other on them — only the *conservation accounting* is off, because a
round is priced against its pre-round metadata):

  * at most one op per *pointer chain* (a malloc and the reallocs
    descending from it) per round — two same-round frees of one pointer
    race on the backend mutex;
  * frees whose target metadata may be absent pre-round (cross-round
    double frees, stale pre-realloc pointers, garbage raws) only appear in
    dedicated *misuse rounds* containing no metadata-creating ops.
    Otherwise the malloc phase can recycle the freed offset in the same
    round and the free phase — which reads live metadata — frees the
    brand-new block: free(p) racing a malloc that just returned p, a
    use-after-free by construction.

Cross-round misuse IS generated and must be dropped (path 2) or served
deterministically-identically by every backend.

The seeded deterministic subset below replays >= 200 randomized rounds per
backend; CI runs it in the tier1 ``fuzz-smoke`` lane. With hypothesis
installed, property variants widen the stream space under a bounded,
derandomized example budget (FUZZ_MAX_EXAMPLES).
"""
import functools
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from conftest import hypothesis_or_skip
from repro.core import heap, system as sysm, telemetry
from repro.core.oracle import PyArena, PyPimMalloc
from repro.workloads.replay import replay, replay_all_kinds
from repro.workloads.trace import Trace

given, settings, st = hypothesis_or_skip()

T = 4
HEAP = 1 << 19
INT32_MAX = np.iinfo(np.int32).max
SMOKE_SEEDS = (0, 1, 2)
SMOKE_ROUNDS = int(os.environ.get("FUZZ_ROUNDS", "80"))
MAX_EXAMPLES = int(os.environ.get("FUZZ_MAX_EXAMPLES", "15"))

GARBAGE_PTRS = (-7, 3, 17, 4096, HEAP - 16, HEAP + 104, 1 << 21)
# negative sizes are raw-protocol territory: a MALLOC/CALLOC with size <= 0
# is idle (path -1), a REALLOC with size <= 0 and a live ptr is free(p)
ALLOC_SIZES = (-5, 0, 1, 16, 48, 100, 256, 1024, 2047, 2048, 2049, 4096,
               12000, HEAP, HEAP * 2)
REALLOC_SIZES = (1, 16, 48, 100, 256, 1024, 2047, 2048)
BURST_SIZES = (4096, 8192, 1 << 14, 1 << 15, HEAP // 4)
CALLOC_SIZES = (-3, 16, 64, 1024, 4096, INT32_MAX)


def fuzz_trace(seed: int, rounds: int = SMOKE_ROUNDS, num_threads: int = T,
               heap_bytes: int = HEAP, clean: bool = False) -> Trace:
    """One randomized symbolic tape (deterministic in `seed`).

    The generator is *oracle-guided*: it steps a `PyPimMalloc` alongside
    generation, so it knows the concrete pointer value behind every slot and
    the exact set of live values. That knowledge enforces the two UB
    exclusions from the module docstring — misuse targets are verified
    dead-by-value at selection time, and misuse rounds carry no
    metadata-creating ops. ``clean=True`` drops the misuse rounds and
    garbage pointers entirely: every alloc freed at most once through its
    latest producer slot — well-formed under ANY correct allocator, which is
    what lets one clean tape check conservation on ``strawman`` too (its
    placements differ from the oracle's, so value-guided misuse does not
    transfer).
    """
    rng = np.random.default_rng(seed)
    op = np.zeros((rounds, num_threads), np.int32)
    size = np.zeros_like(op)
    ref = np.full_like(op, -1)
    raw = np.full_like(op, -1)

    py = PyPimMalloc(heap_bytes=heap_bytes, num_threads=num_threads)
    n_slots = rounds * num_threads
    vals = np.full((n_slots,), -1, np.int64)  # oracle value per slot
    live_vals = set()
    # chain = one malloc + the reallocs descending from it: {"slot": latest
    # producing slot, "stale": earlier slots, "live": not yet retired}
    chains = []

    def pick(pool, used):
        pool = [c for c in pool if id(c) not in used]
        return pool[int(rng.integers(len(pool)))] if pool else None

    for r in range(rounds):
        u0 = rng.random()
        misuse = (not clean) and u0 < 0.18
        burst = not misuse and u0 > 0.88
        used = set()                       # chains touched this round
        actions = [None] * num_threads     # (kind, chain) to reconcile
        for t in range(num_threads):
            slot = r * num_threads + t
            live = [c for c in chains if c["live"]]
            dead_safe = [c for c in chains if not c["live"]
                         and vals[c["slot"]] not in live_vals]
            if misuse:
                v = rng.random()
                op[r, t] = heap.OP_FREE
                if v < 0.30:               # cross-round double free
                    c = pick(dead_safe, used)
                    if c is not None:
                        used.add(id(c))
                        ref[r, t] = c["slot"]
                        continue
                if v < 0.45:               # free a stale pre-realloc slot
                    pool = [c for c in chains if any(
                        vals[s] not in live_vals for s in c["stale"])]
                    c = pick(pool, used)
                    if c is not None:
                        used.add(id(c))
                        cand = [s for s in c["stale"]
                                if vals[s] not in live_vals]
                        ref[r, t] = cand[int(rng.integers(len(cand)))]
                        continue
                if v < 0.62:               # raw garbage pointer
                    g = [g for g in GARBAGE_PTRS if g not in live_vals]
                    if g:
                        raw[r, t] = int(rng.choice(g))
                    continue
                if v < 0.72:               # NULL free: benign by contract
                    continue
                if v < 0.85:               # realloc(dead_ptr, 0)
                    c = pick(dead_safe, used)
                    if c is not None:
                        used.add(id(c))
                        op[r, t] = heap.OP_REALLOC
                        ref[r, t] = c["slot"]
                        continue
                c = pick(live, used)       # plain retire (safe anywhere)
                if c is not None:
                    used.add(id(c))
                    ref[r, t] = c["slot"]
                    actions[t] = ("free", c)
                continue
            u = rng.random()
            if burst or u < 0.40 or not live:
                op[r, t] = heap.OP_MALLOC
                size[r, t] = int(rng.choice(BURST_SIZES if burst
                                            else ALLOC_SIZES))
                if size[r, t] > 0:
                    actions[t] = ("alloc", None)
            elif u < 0.50:
                op[r, t] = heap.OP_CALLOC
                size[r, t] = int(rng.choice(CALLOC_SIZES))
                if size[r, t] > 0:
                    actions[t] = ("alloc", None)
            elif u < 0.72:                 # retire a live chain
                c = pick(live, used)
                op[r, t] = heap.OP_FREE
                if c is not None:
                    used.add(id(c))
                    ref[r, t] = c["slot"]
                    actions[t] = ("free", c)
            else:                          # REALLOC
                w = rng.random()
                op[r, t] = heap.OP_REALLOC
                size[r, t] = int(rng.choice(REALLOC_SIZES))
                c = pick(live, used)
                if w < 0.80 and c is not None:
                    used.add(id(c))
                    ref[r, t] = c["slot"]
                    if rng.random() < 0.15:
                        # realloc(p, <=0) == free(p) at the raw protocol
                        size[r, t] = int(rng.choice((0, -5)))
                        actions[t] = ("free", c)
                    else:
                        actions[t] = ("realloc", c)
                elif not clean and w < 0.90:   # raw garbage ptr realloc
                    g = [g for g in GARBAGE_PTRS if g not in live_vals]
                    if g:
                        raw[r, t] = int(rng.choice(g))
                else:                      # realloc(NULL, n) == malloc
                    actions[t] = ("alloc", None)

        # -- advance the oracle guide and reconcile chain/value state -----
        resolved = np.where(ref[r] >= 0,
                            vals[np.clip(ref[r], 0, n_slots - 1)],
                            raw[r]).astype(np.int64)
        out = py.request(op[r].tolist(), size[r].tolist(), resolved.tolist())
        for t in range(num_threads):
            slot = r * num_threads + t
            p_new = int(out["ptr"][t])
            vals[slot] = p_new
            if actions[t] is None:
                continue
            kind, c = actions[t]
            if kind == "alloc":
                if p_new >= 0:
                    live_vals.add(p_new)
                    chains.append({"slot": slot, "stale": [], "live": True})
            elif kind == "free":
                if out["path"][t] in (0, 1):
                    live_vals.discard(int(resolved[t]))
                    c["live"] = False
            elif kind == "realloc":
                if out["ok"][t]:
                    if out["moved"][t]:
                        live_vals.discard(int(resolved[t]))
                    live_vals.add(p_new)
                    c["stale"].append(c["slot"])
                    c["slot"] = slot
                # on failure the old block stays intact: chain unchanged
    return Trace(name=f"fuzz_{seed}", heap_bytes=heap_bytes,
                 num_threads=num_threads, recorded_kind="hwsw",
                 description=f"differential fuzz stream seed={seed}",
                 op=op, size=size, ptr_ref=ref, ptr_raw=raw)


def assert_stream_contract(trace: Trace, kinds=None):
    """The cross-backend contract every fuzz stream must satisfy."""
    results = replay_all_kinds(trace, kinds)
    reps = {k: rep for k, (_, rep) in results.items()}
    for kind, rep in reps.items():
        assert rep["telemetry"]["conservation_residual"] == 0, \
            f"{trace.name}/{kind}: conservation violated"
    if "sw" in reps and "hwsw" in reps:
        assert reps["sw"]["digest_sem"] == reps["hwsw"]["digest_sem"], \
            f"{trace.name}: sw != hwsw on semantic fields"
    return reps


# --------------------------------------------------------------------------
# deterministic smoke subset (the CI fuzz-smoke lane): >= 200 rounds/backend
# --------------------------------------------------------------------------
@pytest.mark.parametrize("seed", SMOKE_SEEDS)
def test_fuzz_misuse_stream_contract(seed):
    """Misuse streams (double frees, garbage pointers, realloc-after-free)
    through the pim family: sw/hwsw parity + conservation."""
    trace = fuzz_trace(seed)
    reps = assert_stream_contract(trace, kinds=("sw", "hwsw"))
    # the streams genuinely exercise the nasty paths
    assert reps["hwsw"]["dropped_frees"] > 0, "no misuse generated?"
    assert reps["hwsw"]["ops"] > SMOKE_ROUNDS  # multi-op rounds


@pytest.mark.parametrize("seed", SMOKE_SEEDS)
def test_fuzz_clean_stream_contract_all_kinds(seed):
    """Well-formed streams through every registered kind (strawman included):
    parity + conservation on every backend."""
    trace = fuzz_trace(seed + 100, clean=True)
    reps = assert_stream_contract(trace)
    assert set(reps) == set(heap.kinds())


def test_fuzz_total_rounds_meet_acceptance():
    """>= 200 randomized rounds per backend in the CI smoke configuration
    (strawman sees the clean streams; the pim family sees both)."""
    assert len(SMOKE_SEEDS) * SMOKE_ROUNDS >= 200


def test_fuzz_exhaustion_bursts_fail_cleanly():
    """Capacity-exhausting bursts must produce path-3 failures (not crashes,
    not pointer reuse) and keep conservation intact."""
    trace = fuzz_trace(seed=7, rounds=60, clean=True)
    resps, _, rep = replay(trace, "hwsw")
    assert rep["failed_allocs"] > 0, "bursts never exhausted the heap?"
    assert rep["telemetry"]["conservation_residual"] == 0
    # every successful alloc in one round returns distinct pointers
    ptr = np.asarray(resps.ptr)
    ok = np.asarray(resps.ok)
    isal = np.isin(trace.op, (heap.OP_MALLOC, heap.OP_CALLOC))
    for r in range(trace.rounds):
        got = ptr[r][isal[r] & ok[r]]
        assert len(set(got.tolist())) == got.shape[0]


def test_fuzz_replay_is_deterministic():
    """Same tape, two replays: bitwise-identical response streams."""
    trace = fuzz_trace(seed=1, rounds=24)
    r1, _, rep1 = replay(trace, "hwsw")
    r2, _, rep2 = replay(trace, "hwsw")
    assert rep1["digest_full"] == rep2["digest_full"]


# --------------------------------------------------------------------------
# differential oracle: hwsw vs plain-Python PyPimMalloc, round by round
# --------------------------------------------------------------------------
def _resolve(trace: Trace, slots: np.ndarray, r: int) -> np.ndarray:
    ref = trace.ptr_ref[r]
    return np.where(ref >= 0, slots[np.clip(ref, 0, slots.shape[0] - 1)],
                    trace.ptr_raw[r]).astype(np.int32)


def run_oracle_differential(seed: int, rounds: int = 36):
    """Step hwsw eagerly against the oracle; verify semantics + conservation
    after EVERY round (the scan-based tests only snapshot the end state)."""
    trace = fuzz_trace(seed, rounds=rounds)
    cfg = sysm.SystemConfig(kind="hwsw", heap_bytes=HEAP, num_threads=T)
    state = heap.init(cfg)
    py = PyPimMalloc(heap_bytes=HEAP, num_threads=T)
    step = jax.jit(functools.partial(heap.step, cfg))
    slots = np.full((rounds * T,), -1, np.int32)
    for r in range(rounds):
        ptr = _resolve(trace, slots, r)
        req = heap.AllocRequest(op=jnp.asarray(trace.op[r]),
                                size=jnp.asarray(trace.size[r]),
                                ptr=jnp.asarray(ptr))
        state, resp = step(state, req)
        want = py.request(trace.op[r].tolist(), trace.size[r].tolist(),
                          ptr.tolist())
        got_ptr = np.asarray(resp.ptr)
        np.testing.assert_array_equal(got_ptr, want["ptr"],
                                      err_msg=f"round {r}: ptr")
        np.testing.assert_array_equal(np.asarray(resp.ok), want["ok"],
                                      err_msg=f"round {r}: ok")
        np.testing.assert_array_equal(np.asarray(resp.path), want["path"],
                                      err_msg=f"round {r}: path")
        np.testing.assert_array_equal(np.asarray(resp.moved), want["moved"],
                                      err_msg=f"round {r}: moved")
        snap = telemetry.snapshot(cfg, state)
        assert snap["conservation_residual"] == 0, \
            f"round {r}: conservation residual {snap['conservation_residual']}"
        slots[r * T:(r + 1) * T] = got_ptr


@pytest.mark.parametrize("seed", (0, 5))
def test_fuzz_oracle_differential(seed):
    run_oracle_differential(seed)


# --------------------------------------------------------------------------
# differential oracle: arena/tlregion vs plain-Python PyArena, round by round
# --------------------------------------------------------------------------
def run_arena_oracle_differential(kind: str, seed: int, rounds: int = 30):
    """Closed-loop mixed-op stream (incl. EPOCH_RESET rounds and frees of
    reset-staled pointers) through the layered arena kinds vs the `PyArena`
    oracle: semantic fields equal and conservation holds after EVERY round.
    Stale frees are deliberately kept in the stream — both sides must agree
    on dropping them (the reset applies at round start)."""
    cfg = sysm.SystemConfig(kind=kind, heap_bytes=HEAP, num_threads=T)
    state = heap.init(cfg)
    step = heap.REGISTRY[kind]
    py = PyArena(heap_bytes=HEAP, num_threads=T,
                 tlregion=(kind == "tlregion"))
    rng = np.random.default_rng(seed)
    live = []
    for r in range(rounds):
        op = np.zeros(T, np.int32)
        size = np.zeros(T, np.int32)
        ptr = np.full(T, -1, np.int32)
        if r % 9 == 8:
            op[rng.random(T) < 0.6] = heap.OP_EPOCH_RESET
            # `live` is NOT cleared: later frees of staled arena pointers
            # must drop identically on both sides
        else:
            for t in range(T):
                u = rng.random()
                if u < 0.45 or not live:
                    op[t] = int(rng.choice((heap.OP_MALLOC, heap.OP_CALLOC)))
                    size[t] = int(rng.choice(ALLOC_SIZES[2:]))
                elif u < 0.70:
                    op[t] = heap.OP_FREE
                    if live:
                        ptr[t] = live.pop(int(rng.integers(len(live))))
                else:
                    op[t] = heap.OP_REALLOC
                    size[t] = int(rng.choice((0,) + REALLOC_SIZES + (8192,)))
                    if live and rng.random() < 0.8:
                        ptr[t] = live.pop(int(rng.integers(len(live))))
        req = heap.AllocRequest(op=jnp.asarray(op), size=jnp.asarray(size),
                                ptr=jnp.asarray(ptr))
        state, resp = step(cfg, state, req)
        want = py.request(op.tolist(), size.tolist(), ptr.tolist())
        for f in ("ptr", "ok", "path", "moved"):
            np.testing.assert_array_equal(
                np.asarray(getattr(resp, f)), want[f],
                err_msg=f"{kind} round {r}: {f}")
        live += [int(p) for p in np.asarray(resp.ptr) if p >= 0]
        snap = telemetry.snapshot(cfg, state)
        assert snap["conservation_residual"] == 0, \
            f"{kind} round {r}: residual {snap['conservation_residual']}"


@pytest.mark.parametrize("kind", ("arena", "tlregion"))
@pytest.mark.parametrize("seed", (0, 3))
def test_fuzz_arena_oracle_differential(kind, seed):
    run_arena_oracle_differential(kind, seed)


# --------------------------------------------------------------------------
# hypothesis property variants (skip cleanly when hypothesis is absent)
# --------------------------------------------------------------------------
@settings(max_examples=MAX_EXAMPLES, deadline=None, derandomize=True)
@given(st.integers(0, 2**31 - 1))
def test_property_stream_contract(seed):
    """Any seed's stream satisfies sw/hwsw parity + conservation."""
    assert_stream_contract(fuzz_trace(seed, rounds=20),
                           kinds=("sw", "hwsw"))


@settings(max_examples=max(MAX_EXAMPLES // 3, 3), deadline=None,
          derandomize=True)
@given(st.integers(0, 2**31 - 1))
def test_property_oracle_differential(seed):
    run_oracle_differential(seed, rounds=12)
