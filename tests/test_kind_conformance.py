"""New-kind conformance: every `heap.REGISTRY` entry is pinned by
construction, not by copy-pasted per-kind tests.

Each test parametrizes over `heap.kinds()`, so registering a design point
(PR 9: ``arena`` / ``tlregion``; any future kind) automatically enrolls it
in the core contracts:

  * telemetry conservation after a mixed malloc/realloc/reset/free stream,
  * C-semantics edge cases (realloc(NULL, n) / realloc(p, 0) /
    realloc(NULL, 0) / negative sizes) served through the live heap,
  * tape-replay digest stability (same tape -> same digest, including
    through a JSON round-trip).
"""
import jax.numpy as jnp
import pytest

from repro.core import heap, system as sysm
from repro.core.api import HeapClient
from repro.workloads.replay import replay
from repro.workloads.trace import RecordingAllocator, Trace

T = 4
HEAP = 1 << 19
KINDS = tuple(heap.kinds())


def test_registry_and_kinds_agree():
    # system.KINDS orders for presentation; the membership must match the
    # registry exactly so nothing escapes the parametrized contracts
    assert set(sysm.KINDS) == set(KINDS)
    assert {"strawman", "sw", "hwsw", "sanitizer", "arena",
            "tlregion"} <= set(KINDS)


# --------------------------------------------------------------------------
# telemetry conservation through a mixed stream (reset round included)
# --------------------------------------------------------------------------
@pytest.mark.parametrize("kind", KINDS)
def test_conservation_through_mixed_rounds(kind):
    """live + buddy-free + frontend-cached == heap_bytes after every round
    of a stream that crosses size classes, the bypass range, a realloc
    round, and an epoch reset."""
    cl = HeapClient(heap_bytes=HEAP, num_threads=T, kind=kind)

    def residual():
        return cl.telemetry()["conservation_residual"]

    r0 = cl.malloc_batch(jnp.array([16, 100, 2048, 8192], jnp.int32))
    assert all(bool(x) for x in r0.ok)
    assert residual() == 0
    r1 = cl.realloc_batch(r0.ptr, jnp.array([300, 100, 0, 16384], jnp.int32))
    assert residual() == 0
    cl.epoch_reset()
    assert residual() == 0
    # post-reset traffic: only pointers produced after the reset (plus the
    # big bypass block, which survives it on every kind) are referenced —
    # the same well-formedness rule trace_lint enforces on tapes
    r3 = cl.malloc_batch(jnp.full((T,), 64, jnp.int32))
    assert all(bool(x) for x in r3.ok)
    assert residual() == 0
    cl.free_batch(r3.ptr)
    assert residual() == 0
    cl.free(int(r1.ptr[3]), thread=3)
    assert residual() == 0


# --------------------------------------------------------------------------
# C-semantics edges, served through the live heap
# --------------------------------------------------------------------------
@pytest.mark.parametrize("kind", KINDS)
def test_c_semantics_edges(kind):
    """One round exercising every realloc edge the builder normalizes:
    realloc(NULL, n) allocates, realloc(p, 0) frees, realloc(NULL, 0)
    idles, and a negative size fails while the old block stays live."""
    cl = HeapClient(heap_bytes=HEAP, num_threads=T, kind=kind)
    r0 = cl.malloc_batch(jnp.array([100, 100, 100, 8192], jnp.int32))
    assert all(bool(x) for x in r0.ok)
    ptrs = jnp.array([-1, int(r0.ptr[1]), -1, int(r0.ptr[3])], jnp.int32)
    sizes = jnp.array([64, 0, 0, -5], jnp.int32)
    r1 = cl.realloc_batch(ptrs, sizes)
    assert int(r1.ptr[0]) >= 0 and bool(r1.ok[0])     # realloc(NULL, n)
    assert int(r1.ptr[1]) == -1                        # realloc(p, 0) == free
    assert int(r1.path[2]) == -1                       # realloc(NULL, 0) idle
    assert int(r1.ptr[3]) == -1 and not bool(r1.ok[3])  # negative size fails
    assert int(r1.path[3]) == 3
    # the failed realloc kept thread 3's block live: freeing it succeeds
    r2 = cl.free_batch(jnp.array([-1, -1, -1, int(r0.ptr[3])], jnp.int32))
    assert bool(r2.ok[3])
    assert cl.telemetry()["conservation_residual"] == 0


# --------------------------------------------------------------------------
# tape replay digest stability
# --------------------------------------------------------------------------
def _small_tape() -> Trace:
    rec = RecordingAllocator(heap_bytes=HEAP, num_threads=T, kind="hwsw")
    r0 = rec.request(heap.malloc_request(
        jnp.array([16, 100, 2048, 8192], jnp.int32)))
    rec.request(heap.realloc_request(
        r0.ptr, jnp.array([300, 0, 64, 16384], jnp.int32)))
    rec.request(heap.epoch_reset_request(T))
    r3 = rec.request(heap.malloc_request(jnp.full((T,), 64, jnp.int32)))
    rec.request(heap.free_request(r3.ptr))
    return rec.finish("conformance", "unit")


@pytest.mark.parametrize("kind", KINDS)
def test_tape_replay_digest_stable(kind, tmp_path):
    """Replaying the same tape (reset round included) is deterministic per
    kind, and a JSON round-trip replays to the identical digest."""
    tr = _small_tape()
    _, _, a = replay(tr, kind)
    _, _, b = replay(tr, kind)
    assert a["digest_full"] == b["digest_full"]
    assert a["digest_sem"] == b["digest_sem"]
    p = str(tmp_path / "t.json")
    tr.save(p)
    _, _, c = replay(Trace.load(p), kind)
    assert c["digest_full"] == a["digest_full"]
