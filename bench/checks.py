"""The comparisons that decide `correct`, made after the window closes.

Inputs are a session's request grids (op, size, ptr_ref, ptr_raw as
[rounds, R, C, T]: what was sent) and the answers read back from the
device (ptr, ok, path, moved, same shape). Nothing here calls the program.

- `guarantees` holds every core of the fleet to the allocator's stated
  guarantees: no allocation overlaps a block that is live on its core, no
  free is dropped, and no op goes unanswered.
- `reference_mismatches` replays cores through the plain reference, each
  closed on its own answers, and counts the (round, thread) answers that
  differ in pointer, success, path or relocation.

Every number is an exact count: its limit is 0.
"""
from __future__ import annotations

import numpy as np

OP_NOOP, OP_MALLOC, OP_FREE, OP_REALLOC, OP_CALLOC = 0, 1, 2, 3, 4
FIELDS = ("ptr", "ok", "path", "moved")
LIMITS = {"overlapping_blocks": 0, "dropped_frees": 0, "unanswered_ops": 0,
          "reference_mismatches": 0, "conservation_residual": 0}


def served_ops(op: np.ndarray) -> int:
    """Protocol ops in a grid: malloc, calloc, realloc and free entries."""
    return int(np.isin(op, (OP_MALLOC, OP_FREE, OP_REALLOC, OP_CALLOC)).sum())


def rounded_bytes(size: np.ndarray, config: dict) -> np.ndarray:
    """The bytes a request of `size` holds: its size class, or a power of
    two of at least one block for the buddy bypass."""
    classes = np.asarray(config["size_classes"], np.int64)
    size = np.maximum(np.asarray(size, np.int64), 1)
    small = classes[np.minimum(np.searchsorted(classes, size),
                               len(classes) - 1)]
    big = np.maximum(1 << np.ceil(np.log2(size)).astype(np.int64),
                     config["block_bytes"])
    return np.where(size <= classes[-1], small, big)


def _flat(grids: dict) -> dict:
    return {k: np.asarray(v).reshape(v.shape[0], -1) for k, v in grids.items()}


def resolve_operands(grids: dict, ptr: np.ndarray) -> np.ndarray:
    """Pointer operands as the scan resolves them: a slot reference reads
    the pointer that slot's op left (a failed relocating realloc leaves
    its old block), else the raw operand. [rounds, N] from [rounds, N]."""
    g = _flat(grids)
    ptr = ptr.reshape(ptr.shape[0], -1)
    rounds, n = ptr.shape
    slots = np.full(rounds * n, -1, np.int64)
    out = np.empty((rounds, n), np.int64)
    for r in range(rounds):
        ref = g["ptr_ref"][r]
        operand = np.where(ref >= 0, slots[np.clip(ref, 0, slots.size - 1)],
                           g["ptr_raw"][r])
        survived = ((g["op"][r] == OP_REALLOC) & (g["size"][r] > 0)
                    & (ptr[r] < 0) & (operand >= 0))
        slots[r * n:(r + 1) * n] = np.where(survived, operand, ptr[r])
        out[r] = operand
    return out


def guarantees(config: dict, grids: dict, host: dict) -> dict:
    """Fleet-wide counts of broken guarantees, and failed allocations."""
    g = _flat(grids)
    h = _flat(host)
    T = config["num_threads"]
    operand = resolve_operands(grids, h["ptr"])
    rounds, n = operand.shape
    core = (np.arange(n, dtype=np.int64) // T) << 32
    live_start = np.zeros(0, np.int64)
    live_end = np.zeros(0, np.int64)
    overlaps = dropped = unanswered = failed_allocs = 0
    for r in range(rounds):
        op, size, opd = g["op"][r], g["size"][r], operand[r]
        ptr, ok, path, moved = (h["ptr"][r], h["ok"][r].astype(bool),
                                h["path"][r], h["moved"][r].astype(bool))
        active = np.isin(op, (OP_MALLOC, OP_FREE, OP_REALLOC, OP_CALLOC))
        alloc = np.isin(op, (OP_MALLOC, OP_CALLOC))
        resize = (op == OP_REALLOC) & (size > 0)
        frees = (op == OP_FREE) | ((op == OP_REALLOC) & (size <= 0))
        failed_allocs += int(((alloc | resize) & ~ok).sum())
        dropped += int((frees & (path == 2)).sum())
        unanswered += int((active & (path == -1)
                           & ~(frees & (opd == -1))).sum())
        # blocks placed this round may overlap nothing live at its start,
        # nor each other
        new = ((alloc & ok) | (resize & moved & ok)) & (ptr >= 0)
        starts = core[new] + ptr[new]
        order = np.argsort(starts, kind="stable")
        starts = starts[order]
        ends = starts + rounded_bytes(size[new][order], config)
        if starts.size > 1:
            reach = np.maximum.accumulate(ends)[:-1]
            overlaps += int((starts[1:] < reach).sum())
        if starts.size and live_start.size:
            before = np.searchsorted(live_start, ends, "left")
            reach = np.maximum.accumulate(live_end)
            hit = before > 0
            overlaps += int((reach[before[hit] - 1] > starts[hit]).sum())
        # then this round's frees and relocations retire their old blocks
        # (a block placed this round is never retired in it)
        gone = (frees & np.isin(path, (0, 1))) | (resize & moved & ok)
        gone_keys = core[gone] + opd[gone]
        at = np.searchsorted(live_start, gone_keys, "left")
        found = at < live_start.size
        found[found] = live_start[at[found]] == gone_keys[found]
        live_start = np.delete(live_start, at[found])
        live_end = np.delete(live_end, at[found])
        at = np.searchsorted(live_start, starts, "left")
        live_start = np.insert(live_start, at, starts)
        live_end = np.insert(live_end, at, ends)
    return {"overlapping_blocks": overlaps, "dropped_frees": dropped,
            "unanswered_ops": unanswered, "failed_allocs": failed_allocs}


def reference_mismatches(reference, config: dict, grids: dict, host: dict,
                         cores) -> tuple[int, int]:
    """(answers that differ, ops compared) over the flat core ids `cores`.

    Each core's tape is replayed through a fresh reference heap; a slot
    reference reads the reference's own earlier answer, so the replay
    depends on nothing the program returned.
    """
    op = np.asarray(grids["op"])
    rounds, R, C, T = op.shape
    n = R * C * T
    g = {k: np.asarray(v).reshape(rounds, R * C, T) for k, v in grids.items()}
    h = {k: np.asarray(host[k]).reshape(rounds, R * C, T) for k in FIELDS}
    template = reference.make(config)
    idle = {"ptr": -1, "ok": False, "path": -1, "moved": False}
    mismatches = compared = 0
    for c in cores:
        heap = template.copy()
        slots = np.full(rounds * T, -1, np.int64)
        for r in range(rounds):
            o = g["op"][r, c]
            if not o.any():
                mismatches += int(sum(
                    (h[f][r, c] != idle[f]) for f in FIELDS).astype(bool)
                    .sum())
                continue
            ref = g["ptr_ref"][r, c]
            local = (ref // n) * T + (ref % n) - c * T
            ptr = np.where(ref >= 0, slots[np.clip(local, 0, slots.size - 1)],
                           g["ptr_raw"][r, c])
            size = g["size"][r, c]
            want = heap.request(o.tolist(), size.tolist(), ptr.tolist())
            bad = np.zeros(T, bool)
            for f in FIELDS:
                bad |= h[f][r, c] != np.asarray(want[f], h[f].dtype)
            mismatches += int(bad.sum())
            compared += int((o != OP_NOOP).sum())
            out = np.asarray(want["ptr"], np.int64)
            survived = ((o == OP_REALLOC) & (size > 0) & (out < 0)
                        & (ptr >= 0))
            slots[r * T:(r + 1) * T] = np.where(survived, ptr, out)
    return mismatches, compared
