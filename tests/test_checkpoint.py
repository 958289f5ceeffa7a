"""Direct coverage for repro.checkpoint.ckpt (the elastic tier's substrate).

Save/restore round-trips over real heap-state pytrees for every registered
backend, the dtype-drift regression (the shardings path used to device_put
raw npz arrays with only shape checked — a drifted dtype restored silently
wrong), AsyncCheckpointer exception propagation, the COMMITTED-marker
contract, and restore-onto-a-different-mesh parity.
"""
import os
import threading

import numpy as np
import pytest

import jax

from repro.checkpoint import ckpt
from repro.core import heap as heap_api
from repro.core import system as sysm
from repro.core.heap import (OP_FREE, OP_MALLOC, OP_REALLOC, AllocRequest,
                             MultiCoreHeap)

from conftest import hypothesis_or_skip

given, settings, st = hypothesis_or_skip()

T = 4
HEAP = 1 << 16


def _cfg(kind):
    return sysm.SystemConfig(kind=kind, heap_bytes=HEAP, num_threads=T)


def _churned_state(kind, seed=0, rounds=6):
    """A heap state that has actually worked: malloc/free/realloc churn."""
    heap = MultiCoreHeap(_cfg(kind), num_cores=2)
    rng = np.random.default_rng(seed)
    ptrs = np.full((2, T), -1, np.int64)
    for _ in range(rounds):
        op = rng.choice([OP_MALLOC, OP_FREE, OP_REALLOC], (2, T))
        has = ptrs >= 0
        op = np.where((op != OP_MALLOC) & ~has, OP_MALLOC, op).astype(np.int32)
        size = rng.choice([32, 128, 2048], (2, T)).astype(np.int32)
        resp = heap.step(AllocRequest(op=jax.numpy.asarray(op),
                                      size=jax.numpy.asarray(size),
                                      ptr=jax.numpy.asarray(
                                          ptrs.astype(np.int32))))
        rp = np.asarray(resp.ptr)
        ptrs = np.where(op == OP_FREE, -1, np.where(rp >= 0, rp, ptrs))
    return heap.state


def _assert_tree_equal(a, b):
    fa = jax.tree_util.tree_leaves(a)
    fb = jax.tree_util.tree_leaves(b)
    assert len(fa) == len(fb)
    for x, y in zip(fa, fb):
        assert np.asarray(x).dtype == np.asarray(y).dtype
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# --------------------------------------------------------------------------
# round-trips over every backend's real state pytree
# --------------------------------------------------------------------------
@pytest.mark.parametrize("kind", heap_api.kinds())
def test_save_restore_roundtrip_every_backend(kind, tmp_path):
    state = _churned_state(kind)
    path = ckpt.save(state, 3, str(tmp_path))
    assert os.path.exists(os.path.join(path, "COMMITTED"))
    assert ckpt.latest_step(str(tmp_path)) == 3
    back = ckpt.restore(state, 3, str(tmp_path))
    _assert_tree_equal(state, back)


@pytest.mark.parametrize("kind", ("sw", "hwsw"))
def test_restore_into_shapedtypestruct_templates(kind, tmp_path):
    """Restore needs only shapes/dtypes, not live arrays — the elastic
    resume path restores into eval_shape templates."""
    state = _churned_state(kind, seed=1)
    ckpt.save(state, 0, str(tmp_path))
    templates = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype),
        state)
    back = ckpt.restore(templates, 0, str(tmp_path))
    _assert_tree_equal(state, back)


@given(seed=st.integers(min_value=0, max_value=1 << 30))
@settings(max_examples=12, deadline=None)
def test_property_roundtrip_random_pytrees(seed):
    """Property: irregular pytrees (nested dicts/lists, mixed dtypes,
    0-d scalars) round-trip exactly through the flatten-key naming."""
    import tempfile
    rng = np.random.default_rng(seed)
    tree = {
        "a": rng.integers(-100, 100, int(rng.integers(1, 5)),
                          dtype=np.int32),
        "b": [rng.random(3).astype(np.float32),
              {"c": rng.integers(0, 2, (2, 2)).astype(bool)}],
        "d": np.int64(rng.integers(1 << 40)),
    }
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(tree, 0, d)
        back = ckpt.restore(tree, 0, d)
        _assert_tree_equal(tree, back)


def test_seeded_roundtrip_many_steps(tmp_path):
    """latest_step tracks the newest committed step across many saves."""
    rng = np.random.default_rng(7)
    for step in range(8):
        tree = {"x": rng.integers(-5, 5, 4, dtype=np.int32)}
        ckpt.save(tree, step, str(tmp_path))
        _assert_tree_equal(tree, ckpt.restore(tree, step, str(tmp_path)))
    assert ckpt.latest_step(str(tmp_path)) == 7


# --------------------------------------------------------------------------
# the dtype-drift regression (satellite fix)
# --------------------------------------------------------------------------
def test_restore_casts_drifted_dtype_losslessly(tmp_path):
    """A writer/restorer dtype drift must cast (when lossless) instead of
    restoring bits under the wrong type — on BOTH restore paths."""
    saved = {"x": np.arange(8, dtype=np.int64)}
    ckpt.save(saved, 0, str(tmp_path))
    want = {"x": np.zeros(8, np.int32)}
    back = ckpt.restore(want, 0, str(tmp_path))
    assert np.asarray(back["x"]).dtype == np.int32
    np.testing.assert_array_equal(np.asarray(back["x"]), saved["x"])

    sharding = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    back_sh = ckpt.restore(want, 0, str(tmp_path),
                           shardings={"x": sharding})
    assert np.asarray(back_sh["x"]).dtype == np.int32
    np.testing.assert_array_equal(np.asarray(back_sh["x"]), saved["x"])


def test_restore_refuses_lossy_dtype_cast(tmp_path):
    """Values that do not survive the cast (an int64 pointer truncated to
    int32) must raise, not silently corrupt — with and without shardings."""
    ckpt.save({"x": np.array([1 << 40], np.int64)}, 0, str(tmp_path))
    want = {"x": np.zeros(1, np.int32)}
    with pytest.raises(ValueError, match="lossy"):
        ckpt.restore(want, 0, str(tmp_path))
    sharding = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    with pytest.raises(ValueError, match="lossy"):
        ckpt.restore(want, 0, str(tmp_path), shardings={"x": sharding})


def test_restore_keeps_64_bit_leaves_or_refuses_a_sharding(tmp_path):
    """With jax_enable_x64 off, a 64-bit leaf restores as a host array with
    its values intact; asked to place it on a sharding, restore raises
    rather than let device_put narrow it to 32 bits."""
    assert not jax.config.jax_enable_x64
    saved = {"x": np.array([1 << 40, -3], np.int64)}
    ckpt.save(saved, 0, str(tmp_path))
    back = ckpt.restore(saved, 0, str(tmp_path))
    assert back["x"].dtype == np.int64
    np.testing.assert_array_equal(back["x"], saved["x"])
    sharding = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    with pytest.raises(ValueError, match="narrowed"):
        ckpt.restore(saved, 0, str(tmp_path), shardings={"x": sharding})


def test_restore_rejects_shape_mismatch(tmp_path):
    ckpt.save({"x": np.zeros((4,), np.int32)}, 0, str(tmp_path))
    with pytest.raises(AssertionError):
        ckpt.restore({"x": np.zeros((5,), np.int32)}, 0, str(tmp_path))


# --------------------------------------------------------------------------
# AsyncCheckpointer
# --------------------------------------------------------------------------
def test_async_checkpointer_saves_and_waits(tmp_path):
    acp = ckpt.AsyncCheckpointer(str(tmp_path))
    tree = {"x": np.arange(10, dtype=np.int32)}
    acp.save(tree, 1)
    acp.save(tree, 2)
    paths = acp.wait()
    assert len(paths) == 2
    assert ckpt.latest_step(str(tmp_path)) == 2
    _assert_tree_equal(tree, ckpt.restore(tree, 2, str(tmp_path)))


def test_async_checkpointer_exception_propagates_through_wait(tmp_path):
    """A failed background save must surface at wait(), not vanish on the
    worker thread."""
    blocker = os.path.join(str(tmp_path), "step_00000005")
    with open(blocker, "w") as f:        # step dir path is a FILE:
        f.write("in the way")            # os.makedirs must fail
    acp = ckpt.AsyncCheckpointer(str(tmp_path))
    acp.save({"x": np.zeros(2)}, 5)
    with pytest.raises(OSError):
        acp.wait()
    assert ckpt.latest_step(str(tmp_path)) is None


def test_async_checkpointer_snapshots_before_mutation(tmp_path):
    """The tree is host-snapshotted synchronously: mutating the source
    array after save() must not corrupt the checkpoint."""
    gate = threading.Event()
    orig = ckpt.save

    def slow_save(tree, step, ckpt_dir):
        gate.wait(5)
        return orig(tree, step, ckpt_dir)

    x = np.arange(6, dtype=np.int32)
    acp = ckpt.AsyncCheckpointer(str(tmp_path))
    ckpt.save, saved_fn = slow_save, ckpt.save
    try:
        acp.save({"x": x}, 0)
    finally:
        ckpt.save = saved_fn
    x[:] = -1                            # mutate after the enqueue
    gate.set()
    acp.wait()
    back = ckpt.restore({"x": np.zeros(6, np.int32)}, 0, str(tmp_path))
    np.testing.assert_array_equal(np.asarray(back["x"]), np.arange(6))


# --------------------------------------------------------------------------
# COMMITTED-marker contract
# --------------------------------------------------------------------------
def test_partial_save_without_committed_is_ignored(tmp_path):
    ckpt.save({"x": np.zeros(2)}, 1, str(tmp_path))
    ckpt.save({"x": np.zeros(2)}, 4, str(tmp_path))
    os.remove(os.path.join(str(tmp_path), "step_00000004", "COMMITTED"))
    assert ckpt.latest_step(str(tmp_path)) == 1
    assert ckpt.latest_step(os.path.join(str(tmp_path), "nope")) is None


# --------------------------------------------------------------------------
# restore onto a different mesh: re-placed leaves, identical values
# --------------------------------------------------------------------------
def test_restore_onto_mesh_parity(tmp_path):
    """A fleet state saved from plain (vmap) arrays restores under a rank
    mesh's NamedSharding with identical values — the elastic re-placement
    path (`ElasticFleetServe.restore(mesh=None)` builds on this)."""
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.parallel.meshctx import make_rank_mesh
    cfg = _cfg("sw")
    state = heap_api.sharded_init(cfg, 1, 2)
    ckpt.save(state, 0, str(tmp_path))
    mesh = make_rank_mesh(1, "ranks")
    sh = jax.tree.map(
        lambda _: NamedSharding(mesh, PartitionSpec("ranks")), state)
    back = ckpt.restore(state, 0, str(tmp_path), shardings=sh)
    for leaf in jax.tree_util.tree_leaves(back):
        assert leaf.sharding.mesh.axis_names == ("ranks",)
    _assert_tree_equal(state, back)
