"""Faults planted under the timed path, to show that `correct` catches them.

Each wraps the fleet step that `ScanEngine` calls every round
(`ScanEngine._inner`, state and [R, C, T] request in, state and answers
out) before anything is traced. None of them is reachable from
`bench/run.py`: the control runs through `bench/control.py`, the faults
through the tests.

- `drop_frees` (the control): frees are never served, which breaks the
  configuration's no-dropped-free guarantee, the shortcut a faster
  allocator would be tempted by.
- `state_unchanged`: the step returns the state it was given.
- `half_batch`: the upper half of the ranks is left out of every round.
- `altered_answer`: the last thread of the last core has its pointer moved
  by one smallest size class where it is produced.
"""
from __future__ import annotations

OP_NOOP, OP_FREE = 0, 2
FAULTS = ("drop_frees", "state_unchanged", "half_batch", "altered_answer")


def plant(engine, fault: str, min_class: int = 16):
    import jax.numpy as jnp
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r} (have {FAULTS})")
    inner = engine._inner
    half = engine.num_ranks // 2

    def step(state, req):
        if fault == "drop_frees":
            req = req._replace(op=jnp.where(req.op == OP_FREE, OP_NOOP,
                                            req.op))
        if fault == "half_batch":
            req = req._replace(op=req.op.at[half:].set(OP_NOOP))
        new_state, resp = inner(state, req)
        if fault == "state_unchanged":
            new_state = state
        if fault == "altered_answer":
            p = resp.ptr[-1, -1, -1]
            resp = resp._replace(ptr=resp.ptr.at[-1, -1, -1].set(
                jnp.where(p >= 0, p + min_class, p)))
        return new_state, resp

    engine._inner = step
