"""ShardedHeap (shard_map tier) + FleetRouter conformance.

The fleet tier must be a pure transform of the same `heap.step` every other
tier serves: a 1-device-mesh ShardedHeap reproduces MultiCoreHeap pointer
sequences bitwise, donation/fallback change nothing, the router round-trips
flat request streams through the [R, C, T] grid, and the cost accounting is
an exact per-rank decomposition.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import heap
from repro.core import system as sysm
from repro.launch import fleet

T = 4
HEAP = 1 << 18
R, C = 3, 2


def _cfg(kind="sw"):
    return sysm.SystemConfig(kind=kind, heap_bytes=HEAP, num_threads=T)


def _tape(rounds=4):
    """[rounds, R, C, T] malloc sizes, distinct per (rank, core, thread)."""
    rng = np.random.RandomState(7)
    return jnp.asarray(
        rng.choice([16, 100, 256, 2048, 3000, 8192], (rounds, R, C, T))
        .astype(np.int32))


@pytest.mark.parametrize("kind", sysm.KINDS)
def test_sharded_matches_multicore_bitwise(kind):
    """Acceptance: ShardedHeap on a 1-device mesh == MultiCoreHeap, pointer
    for pointer, across malloc/free/realloc rounds on every backend kind.
    Each rank sees a DISTINCT request stream and must match a MultiCoreHeap
    replaying exactly that rank's stream."""
    cfg = _cfg(kind)
    sh = heap.ShardedHeap(cfg, num_ranks=R, num_cores=C)
    assert sh.mesh is not None and sh.mesh.devices.size >= 1
    replays = [heap.MultiCoreHeap(cfg, num_cores=C) for _ in range(R)]
    for sizes in _tape():
        ra = sh.malloc(sizes)
        rr = sh.realloc(ra.ptr, jnp.roll(sizes, 1, axis=-1))
        live = jnp.where(rr.ptr >= 0, rr.ptr, ra.ptr)
        sh.free(live)
        for rk, mch in enumerate(replays):
            rm = mch.malloc(sizes[rk])
            np.testing.assert_array_equal(np.asarray(ra.ptr[rk]),
                                          np.asarray(rm.ptr))
            np.testing.assert_allclose(np.asarray(ra.latency_cyc[rk]),
                                       np.asarray(rm.latency_cyc))
            rrm = mch.step(jax.vmap(heap.realloc_request)(
                rm.ptr, jnp.roll(sizes[rk], 1, axis=-1)))
            np.testing.assert_array_equal(np.asarray(rr.ptr[rk]),
                                          np.asarray(rrm.ptr))
            mch.free(jnp.where(rrm.ptr >= 0, rrm.ptr, rm.ptr))


def test_donation_and_fallback_do_not_change_results():
    """donate=True (in-place state buffers), donate=False, and the pure-vmap
    fallback (mesh=False) produce identical pointer streams."""
    cfg = _cfg()
    variants = [heap.ShardedHeap(cfg, R, C, donate=True),
                heap.ShardedHeap(cfg, R, C, donate=False),
                heap.ShardedHeap(cfg, R, C, mesh=False, donate=True)]
    assert variants[2].mesh is None
    for sizes in _tape():
        resps = [v.malloc(sizes) for v in variants]
        for other in resps[1:]:
            np.testing.assert_array_equal(np.asarray(resps[0].ptr),
                                          np.asarray(other.ptr))
        for v, r in zip(variants, resps):
            v.free(r.ptr)
    # states converged identically too
    for leaf_a, leaf_b in zip(jax.tree.leaves(variants[0].state),
                              jax.tree.leaves(variants[1].state)):
        np.testing.assert_array_equal(np.asarray(leaf_a), np.asarray(leaf_b))


def test_rank_independence():
    """Rank 0's requests never perturb rank 1's heap."""
    cfg = _cfg()
    sh = heap.ShardedHeap(cfg, num_ranks=2, num_cores=C)
    baseline = jax.tree.map(np.asarray, sh.state)
    sizes = jnp.zeros((2, C, T), jnp.int32).at[0].set(
        jnp.full((C, T), 256, jnp.int32))
    resp = sh.malloc(sizes)
    assert bool((resp.ptr[0] >= 0).all()) and bool((resp.ptr[1] == -1).all())
    for a, b in zip(jax.tree.leaves(baseline), jax.tree.leaves(sh.state)):
        np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))


def test_router_round_trips_flat_batches():
    """scatter -> route -> gather preserves request order exactly, including
    a partially filled final rank, and matches a direct [R, C, T] round."""
    cfg = _cfg()
    router = fleet.FleetRouter(heap.ShardedHeap(cfg, R, C))
    n = R * C * T - 5                      # ragged tail: NOOP padding
    sizes = (np.arange(n, dtype=np.int32) % 7 + 1) * 32
    out = router.route_flat(np.full(n, heap.OP_MALLOC, np.int32), sizes,
                            np.full(n, -1, np.int32))
    assert out["ptr"].shape == (n,) and (out["ptr"] >= 0).all()

    # same sizes served directly as a full grid on a fresh fleet
    direct = heap.ShardedHeap(cfg, R, C)
    grid = np.zeros((R * C * T,), np.int32)
    grid[:n] = sizes
    rd = direct.malloc(jnp.asarray(grid.reshape(R, C, T)))
    np.testing.assert_array_equal(out["ptr"],
                                  np.asarray(rd.ptr).reshape(-1)[:n])

    # frees round-trip through the same slots
    out2 = router.route_flat(np.full(n, heap.OP_FREE, np.int32),
                             np.zeros(n, np.int32), out["ptr"])
    assert out2["ok"].all()

    with pytest.raises(ValueError):
        fleet.scatter_flat(np.zeros(R * C * T + 1, np.int32),
                           np.zeros(R * C * T + 1, np.int32),
                           np.zeros(R * C * T + 1, np.int32), router.shape)


def test_accounting_sums_across_ranks():
    cfg = _cfg()
    router = fleet.FleetRouter(heap.ShardedHeap(cfg, R, C))
    for sizes in _tape(3):
        ra = router.route(heap.malloc_request(sizes))
        router.route(heap.free_request(ra.ptr))
    st = router.stats
    assert st["rounds"] == 6
    assert st["ops"] == 6 * R * C * T == sum(st["per_rank"]["ops"])
    assert st["latency_cyc"] == pytest.approx(
        sum(st["per_rank"]["latency_cyc"]))
    assert st["dram_bytes"] == sum(st["per_rank"]["dram_bytes"])
    assert st["us_per_op"] > 0

    # per-rank latencies match an independent single-rank replay
    solo = fleet.FleetRouter(heap.ShardedHeap(cfg, 1, C))
    for sizes in _tape(3):
        ra = solo.route(heap.malloc_request(sizes[:1]))
        solo.route(heap.free_request(ra.ptr))
    assert solo.stats["per_rank"]["latency_cyc"][0] == pytest.approx(
        st["per_rank"]["latency_cyc"][0])


def test_fleet_accounting_shapes():
    """system.fleet_accounting: totals on [T] rounds, per_rank on [R,C,T]."""
    cfg = _cfg()
    st = heap.init(cfg)
    req = heap.malloc_request(jnp.full((T,), 64, jnp.int32))
    st, resp = heap.step(cfg, st, req)
    acct = sysm.fleet_accounting(req, resp)
    assert acct["ops"] == T and "per_rank" not in acct

    sh = heap.ShardedHeap(cfg, R, C)
    req3 = heap.malloc_request(jnp.full((R, C, T), 64, jnp.int32))
    acct3 = sysm.fleet_accounting(req3, sh.step(req3))
    assert len(acct3["per_rank"]["ops"]) == R
    assert acct3["ops"] == sum(acct3["per_rank"]["ops"])


def test_serve_fleet_page_requests():
    """The serving driver's fleet page-growth round: one MALLOC per needy
    sequence, landed on rank b % R, gathered accounting balanced."""
    from repro.launch import serve as serve_mod
    router = serve_mod.make_fleet_pool(num_ranks=2, n_pages=1 << 16,
                                       num_threads=T)
    need = np.array([True, False, True, True])
    req = serve_mod.fleet_page_request(router, need)
    assert int((np.asarray(req.op) == heap.OP_MALLOC).sum()) == 3
    resp = router.route(req)
    ptr = np.asarray(resp.ptr)
    assert int((ptr >= 0).sum()) == 3
    assert router.stats["per_rank"]["ops"] == [2, 1]


RANK_MESH = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
from jax.sharding import AxisType
from repro.core import heap, system as sysm
from repro.parallel.meshctx import make_rank_mesh

cfg = sysm.SystemConfig(kind="sw", heap_bytes=1 << 16, num_threads=4)
for R in (6, 2, 1):
    for build in (lambda: make_rank_mesh(R),
                  lambda: heap.ShardedHeap(cfg, num_ranks=R, num_cores=2)):
        try:
            build()
        except ValueError:
            continue
        raise SystemExit(f"R={R} over 4 devices did not raise")
    assert heap.ShardedHeap(cfg, R, 2, mesh=False).mesh is None
mesh = make_rank_mesh(8)
assert mesh.devices.size == 4 and mesh.axis_types == (AxisType.Auto,)
assert heap.ShardedHeap(cfg, num_ranks=8, num_cores=2).mesh.devices.size == 4
print("rank-mesh-ok")
"""


def test_make_rank_mesh_raises_on_indivisible_request():
    """With ``mesh=None`` a fleet whose rank count the device count does not
    divide raises (make_rank_mesh and ShardedHeap alike) instead of quietly
    spreading over fewer devices; ``mesh=False`` still serves it. Four
    virtual CPU devices, in a subprocess so the XLA flag never leaks."""
    import os
    import subprocess
    import sys
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", RANK_MESH], env=env,
                         capture_output=True, text=True, timeout=300,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert out.returncode == 0, out.stderr[-3000:]
    assert "rank-mesh-ok" in out.stdout
