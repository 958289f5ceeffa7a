"""Checkpointing: named-leaf npz shards + JSON manifest, async save,
restore-with-resharding (elastic: restore onto a different mesh/device
count — host round-trip re-places every leaf under the target sharding).

Single-host implementation; in a multi-host deployment each process writes
its addressable shards under `dir/proc-<k>/` with the same manifest format
(documented contract — the restore path already takes per-leaf shardings).
"""
from __future__ import annotations

import json
import os
import threading
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import jax


def _flatten(tree):
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    out = {}
    for path, leaf in flat:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        out[key] = leaf
    return out


def save(tree, step: int, ckpt_dir: str) -> str:
    """Blocking save. Returns the checkpoint path."""
    path = os.path.join(ckpt_dir, f"step_{step:08d}")
    os.makedirs(path, exist_ok=True)
    named = _flatten(tree)
    arrays = {k: np.asarray(v) for k, v in named.items()}
    np.savez(os.path.join(path, "leaves.npz"), **arrays)
    manifest = {
        "step": step,
        "leaves": {k: {"shape": list(a.shape), "dtype": str(a.dtype)}
                   for k, a in arrays.items()},
    }
    with open(os.path.join(path, "manifest.json"), "w") as f:
        json.dump(manifest, f, indent=1)
    # atomic completion marker (restart-safe: partial saves are ignored)
    with open(os.path.join(path, "COMMITTED"), "w") as f:
        f.write("ok")
    return path


class AsyncCheckpointer:
    """Fire-and-forget saves on a background thread; `wait()` to drain.

    The tree is snapshotted to host memory synchronously (cheap vs. training
    step), serialization happens off-thread — the paper-independent but
    deployment-required 'don't stall the TPUs on I/O' pattern."""

    def __init__(self, ckpt_dir: str):
        self.ckpt_dir = ckpt_dir
        self._pool = ThreadPoolExecutor(max_workers=1)
        self._futures = []
        self._lock = threading.Lock()

    def save(self, tree, step: int):
        # np.array, not np.asarray: asarray is a no-copy view of host
        # arrays, and the caller may mutate them before the worker writes
        host_tree = jax.tree.map(lambda x: np.array(x), tree)
        with self._lock:
            self._futures.append(
                self._pool.submit(save, host_tree, step, self.ckpt_dir))

    def wait(self):
        with self._lock:
            futs, self._futures = self._futures, []
        return [f.result() for f in futs]


def latest_step(ckpt_dir: str):
    if not os.path.isdir(ckpt_dir):
        return None
    steps = []
    for d in os.listdir(ckpt_dir):
        if d.startswith("step_") and os.path.exists(
                os.path.join(ckpt_dir, d, "COMMITTED")):
            steps.append(int(d.split("_")[1]))
    return max(steps) if steps else None


def restore(tree_like, step: int, ckpt_dir: str, shardings=None):
    """Restore into the structure of `tree_like` (pytree of arrays or
    ShapeDtypeStructs). `shardings`: optional matching pytree of
    jax.sharding.Sharding for elastic re-placement onto a new mesh."""
    path = os.path.join(ckpt_dir, f"step_{step:08d}")
    data = np.load(os.path.join(path, "leaves.npz"))
    named = _flatten(tree_like)
    flat_sh = _flatten(shardings) if shardings is not None else None
    out = {}
    for key, leaf in named.items():
        arr = data[key]
        assert tuple(arr.shape) == tuple(leaf.shape), (key, arr.shape, leaf.shape)
        want = np.dtype(leaf.dtype)
        if arr.dtype != want:
            # dtype drift between writer and restorer: cast, but refuse a
            # lossy cast — a silently-truncated heap pointer is corruption
            cast = arr.astype(want)
            if not np.array_equal(cast.astype(arr.dtype), arr):
                raise ValueError(
                    f"lossy dtype cast restoring {key!r}: saved "
                    f"{arr.dtype} -> wanted {want}")
            arr = cast
        narrowed = jax.dtypes.canonicalize_dtype(want) != want
        if flat_sh is not None:
            if narrowed:
                # device_put would narrow it to 32 bits without a word
                raise ValueError(
                    f"cannot place {key!r} ({want}) on a sharding with "
                    f"jax_enable_x64 off: it would be narrowed to 32 bits")
            out[key] = jax.device_put(arr, flat_sh[key])
        elif narrowed:
            # a 64-bit leaf with jax_enable_x64 off: a jax array would
            # narrow it to 32 bits, so it stays a host array
            out[key] = arr
        else:
            out[key] = jax.numpy.asarray(arr)
    # rebuild tree
    paths, treedef = jax.tree_util.tree_flatten_with_path(tree_like)
    leaves = []
    for path_, _ in paths:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path_)
        leaves.append(out[key])
    return jax.tree_util.tree_unflatten(treedef, leaves)
