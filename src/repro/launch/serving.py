"""Shared serving-engine machinery for the closed-loop engines.

`repro.launch.serve_fleet.FleetServe` (raw multi-tenant alloc traffic) and
`repro.launch.serve_decode.DecodeServe` (paged-KV LLM decode) plan very
different host-side workloads, but they execute and report them the same
way. This module holds that common substance — extracted, not copied:

  * :class:`SessionPlan` — the planned device tape (op / size / pointer-ref
    grids of shape [rounds, R, C, T]) plus the host-side dispatch ledger
    and admission/backpressure series.
  * :class:`ScanEngine` — the round driver: the whole planned session runs
    as ONE ``lax.scan`` of the fleet step (`heap.sharded_inner`: vmap over
    cores and ranks, optionally shard_mapped over a rank mesh) with the
    heap state **donated**. Pointer operands are symbolic slot references
    resolved in-scan against the pointers the fleet actually returned
    (exactly the `repro.workloads` tape mechanism lifted to the grid), so
    sessions are closed-loop: frees free the real pointers of this run.
    `ScanEngine.trace` exports any (rank, core)'s slice of a session as a
    standard ``pim-malloc-trace/v1`` tape.
  * report helpers — latency percentiles over round barriers
    (:func:`pct`, :func:`round_barrier_cum`), in-scan pointer resolution
    for accounting (:func:`resolve_pointers`), and the per-core heap-health
    sweep (:func:`fleet_health` — |residual| summed so signed residuals of
    two broken cores never cancel into a clean-looking fleet).
"""
from __future__ import annotations

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from repro.core import heap as heap_api
from repro.core import telemetry
from repro.core.heap import OP_REALLOC, AllocRequest, AllocResponse
from repro.runtime.spans import span
from repro.workloads.trace import Trace

PERCENTILES = (50, 95, 99)


@dataclasses.dataclass
class SessionPlan:
    """One planned serve session: the device tape + the host-side ledger."""

    shape: tuple                 # (R, C, T)
    placement: str
    op: np.ndarray               # int32[rounds, R, C, T]
    size: np.ndarray
    ptr_ref: np.ndarray          # global slot id round*(R*C*T) + grid slot, -1
    ptr_raw: np.ndarray
    # per dispatched request, in dispatch order:
    enq_round: np.ndarray        # int32[n]
    disp_round: np.ndarray       # int32[n]
    slot: np.ndarray             # int32[n] flat in-round grid slot id
    tenant: np.ndarray           # int32[n]
    external: np.ndarray         # bool[n] (False = expiry free)
    # admission/backpressure ledger:
    offered: int                 # external arrivals
    dropped: int                 # rejected at the full admission queue
    backlog_end: int             # still queued when the session ended
    queue_depth: np.ndarray      # int32[rounds] backlog after each dispatch
    external_queue_depth: np.ndarray  # int32[rounds] admission queue only
    drops_per_round: np.ndarray  # int32[rounds]
    dispatched_per_round: np.ndarray
    tenant_home: dict            # tenant -> (rank, core)

    @property
    def rounds(self) -> int:
        return int(self.op.shape[0])

    @property
    def dispatched(self) -> int:
        return int(self.slot.shape[0])


def epoch_boundaries(rounds: int, epoch_rounds: int) -> np.ndarray:
    """bool[rounds] mask of epoch-boundary rounds.

    With ``epoch_rounds = E > 0`` every E-th round (r = E-1, 2E-1, ...) is
    dedicated to ``OP_EPOCH_RESET``: the planner dispatches no traffic into
    it and every epoch-managed (small) allocation made since the previous
    boundary is invalid afterwards — the arena frontend reclaims them in
    one bulk reset instead of one FREE per block. ``epoch_rounds <= 0``
    disables epochs (all-False mask).
    """
    mask = np.zeros(rounds, bool)
    if epoch_rounds > 0:
        mask[epoch_rounds - 1::epoch_rounds] = True
    return mask


def pct(x, percentiles=PERCENTILES) -> dict:
    """{'p50_cyc': ..., ...} percentile dict (zeros for an empty sample)."""
    x = np.asarray(x)
    if x.size == 0:
        return {f"p{p}_cyc": 0.0 for p in percentiles}
    return {f"p{p}_cyc": float(np.percentile(x, p)) for p in percentiles}


def response_host(resps: AllocResponse) -> dict:
    """One device->host conversion per response field, reused throughout.
    Span: `serve/readback`, counting `moved` (relocated reallocs)."""
    with span("serve/readback") as counts:
        host = {f: np.asarray(getattr(resps, f))
                for f in AllocResponse._fields}
        if counts is not None:
            counts["moved"] = int(np.count_nonzero(host["moved"]))
    return host


def _reallocs(op) -> int:
    """REALLOC entries of a plan's op grid."""
    return int(np.count_nonzero(np.asarray(op) == OP_REALLOC))


def round_barrier_cum(lat: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(per-round barrier cycles, cumulative barrier prefix [rounds+1]).

    Threads within a round run concurrently; rounds serialize, so one
    round's barrier is its slowest thread and a queued request waits
    through the barriers between enqueue and dispatch.
    """
    rounds = lat.shape[0]
    flat = lat.reshape(rounds, -1)
    round_cyc = flat.max(axis=1) if flat.size else np.zeros(rounds)
    return round_cyc, np.concatenate([[0.0], np.cumsum(round_cyc)])


def resolve_pointers(plan, host_ptr: np.ndarray) -> np.ndarray:
    """Pointer operands as the scan actually resolved them (slot refs
    against this run's returned pointers), not the raw placeholders —
    accounting must see the served request."""
    flat_ptr = host_ptr.reshape(-1)
    return np.where(
        plan.ptr_ref >= 0,
        flat_ptr[np.clip(plan.ptr_ref, 0, flat_ptr.shape[0] - 1)],
        plan.ptr_raw).astype(np.int32)


def fleet_health(cfg, state, R: int, C: int) -> dict:
    """Per-core telemetry sweep over the final sharded state.

    ``conservation_residual`` sums |per-core residuals| (signed residuals
    of two broken cores must not cancel into a clean-looking fleet);
    ``hwm_bytes_per_rank`` is each rank's busiest core (heaps are per-core,
    so a rank's high-water footprint is bounded by its hottest heap).
    """
    residual = live_b = 0
    hwm_rank = [0] * R
    frags = []
    for rk in range(R):
        for ck in range(C):
            snap = telemetry.snapshot(
                cfg, jax.tree.map(lambda x: x[rk, ck], state))
            residual += abs(snap["conservation_residual"])
            live_b += snap["live_bytes"]
            hwm_rank[rk] = max(hwm_rank[rk], snap["hwm_bytes"])
            frags.append(snap["external_frag"])
    return {
        "live_bytes": int(live_b),
        "conservation_residual": int(residual),
        "hwm_bytes_per_rank": [int(h) for h in hwm_rank],
        "hwm_bytes_max": int(max(hwm_rank)),
        "external_frag_mean": float(np.mean(frags)) if frags else 0.0,
    }


class ScanEngine:
    """The scanned round driver every serving engine shares.

    ``mesh`` follows :class:`repro.core.heap.ShardedHeap`: ``False``
    scans the pure-vmap fleet step, ``None`` builds a 1-D rank mesh and
    shard_maps it, or pass an explicit mesh. The scanned step is
    bitwise-identical either way (pinned for the one-round path in
    tests/test_sharded_heap.py, for whole sessions in
    tests/test_fleet_serve.py and tests/test_serve_decode.py).
    """

    def __init__(self, cfg, num_ranks: int, num_cores: int, mesh=False):
        self.cfg = cfg
        self.num_ranks = num_ranks
        self.num_cores = num_cores
        inner, self.mesh = heap_api.sharded_inner(cfg, num_ranks, mesh=mesh)
        self._inner = inner
        self._scan = jax.jit(self._scan_fn, donate_argnums=(0,))
        # segmented driver (elastic tier): same round body, but the slot
        # file and the round offset are carried across calls so a session
        # can be executed in pieces with host-side decisions in between —
        # bitwise-identical to one uninterrupted scan (same per-round math)
        self._segment = jax.jit(self._segment_fn, donate_argnums=(0, 1))

    @property
    def shape(self) -> tuple:
        return (self.num_ranks, self.num_cores, self.cfg.num_threads)

    @property
    def capacity(self) -> int:
        R, C, T = self.shape
        return R * C * T

    def _round_body(self, n_slots: int, cap: int):
        @jax.named_scope("round")
        def body(carry, x):
            st, slots = carry
            r, op_r, size_r, ref_r, raw_r = x
            with jax.named_scope("slots"):
                ptr = jnp.where(ref_r >= 0,
                                slots[jnp.clip(ref_r, 0, n_slots - 1)], raw_r)
            st, resp = self._inner(st, AllocRequest(op=op_r, size=size_r,
                                                    ptr=ptr))
            # slot = the op's surviving pointer (same rule as the workloads
            # replayer): a failed relocating realloc keeps the old block,
            # so the tenant's scheduled expiry FREE must still reach it
            with jax.named_scope("slots"):
                survived = ((op_r == OP_REALLOC) & (size_r > 0)
                            & (resp.ptr < 0) & (ptr >= 0))
                slots = lax.dynamic_update_slice(
                    slots, jnp.where(survived, ptr, resp.ptr).reshape(-1),
                    (r * cap,))
            return (st, slots), resp

        return body

    def _scan_fn(self, state, op, size, ptr_ref, ptr_raw):
        rounds = op.shape[0]
        cap = self.capacity
        n_slots = rounds * cap
        slots0 = jnp.full((n_slots,), -1, jnp.int32)
        (state, _), resps = lax.scan(
            self._round_body(n_slots, cap), (state, slots0),
            (jnp.arange(rounds, dtype=jnp.int32), op, size, ptr_ref,
             ptr_raw))
        return state, resps

    def _segment_fn(self, state, slots, r0, op, size, ptr_ref, ptr_raw):
        """Scan a contiguous slice [r0, r0+len) of a session.

        ``slots`` is the full-session slot file (rounds * capacity), carried
        across segments; ``r0`` the slice's first global round index. The
        round body is exactly :meth:`_scan_fn`'s, so running a session as N
        segments is bitwise-identical to one scan — the elastic tier's
        snapshot/resume and fault-surgery points rely on this.
        """
        seg = op.shape[0]
        cap = self.capacity
        (state, slots), resps = lax.scan(
            self._round_body(slots.shape[0], cap), (state, slots),
            (r0 + jnp.arange(seg, dtype=jnp.int32), op, size, ptr_ref,
             ptr_raw))
        return state, slots, resps

    def run_segment(self, state, slots, r0: int, plan):
        """Execute rounds [r0, r1) of a planned session (r1 = r0 + segment
        length implied by the sliced grids passed via ``plan`` tuple
        ``(op, size, ptr_ref, ptr_raw)``); returns (state, slots, resps).
        Spans: `serve/segment` (counts `rounds`, `h2d_bytes`, `reallocs`)
        around `serve/h2d` and `serve/dispatch`, as in :meth:`run`."""
        with span("serve/segment", rounds=int(plan[0].shape[0]),
                  h2d_bytes=sum(int(g.nbytes) for g in plan)) as counts:
            if counts is not None:
                counts["reallocs"] = _reallocs(plan[0])
            with span("serve/h2d"):
                grids = [jnp.asarray(g) for g in plan]
            with span("serve/dispatch"):
                return self._segment(state, slots, jnp.int32(r0), *grids)

    def run(self, plan):
        """Execute a planned session on a fresh fleet; returns the final
        sharded state and the stacked [rounds, R, C, T] responses.

        Spans (`repro.runtime.spans`): `serve/session` (counts `rounds`,
        `h2d_bytes`, the plan grids' bytes, and `reallocs`, their REALLOC
        entries) around `serve/init` (state init), `serve/h2d` (the grids'
        copies) and `serve/dispatch` (the scan's dispatch; its device work
        ends after the call returns)."""
        grids = (plan.op, plan.size, plan.ptr_ref, plan.ptr_raw)
        with span("serve/session", rounds=int(plan.op.shape[0]),
                  h2d_bytes=sum(int(g.nbytes) for g in grids)) as counts:
            if counts is not None:
                counts["reallocs"] = _reallocs(plan.op)
            with span("serve/init"):
                state = heap_api.sharded_init(self.cfg, self.num_ranks,
                                              self.num_cores)
            with span("serve/h2d"):
                grids = [jnp.asarray(g) for g in grids]
            with span("serve/dispatch"):
                return self._scan(state, *grids)

    # ------------------------------------------------------------------
    # tape export: one core's slice of a session is a standard trace
    # ------------------------------------------------------------------
    def trace(self, plan, rank: int, core: int, name: str = None,
              description: str = None, meta: dict = None) -> Trace:
        """Export (rank, core)'s slice as a ``pim-malloc-trace/v1`` tape.

        Tenant stickiness guarantees every pointer ref in a core's slice
        points at a slot of the same core, so the slice is a closed,
        self-contained workload: replaying it through
        `repro.workloads.replay` reproduces this core's serve responses
        bitwise (pinned in tests/test_fleet_serve.py and
        tests/test_serve_decode.py).
        """
        R, C, T = plan.shape
        cap = R * C * T
        base = (rank * C + core) * T
        refs = plan.ptr_ref[:, rank, core, :]
        m = refs >= 0
        in_round = refs % cap
        if m.any() and not ((in_round[m] >= base)
                            & (in_round[m] < base + T)).all():
            raise ValueError("cross-core pointer ref: slice is not closed")
        new_ref = np.where(m, (refs // cap) * T + (in_round - base), -1)
        return Trace(
            name=name or f"serve_{plan.placement}_r{rank}c{core}",
            heap_bytes=self.cfg.heap_bytes, num_threads=T,
            recorded_kind=self.cfg.kind,
            description=description or
            f"serve session slice rank={rank} core={core} "
            f"placement={plan.placement}",
            op=plan.op[:, rank, core, :].astype(np.int32),
            size=plan.size[:, rank, core, :].astype(np.int32),
            ptr_ref=new_ref.astype(np.int32),
            ptr_raw=plan.ptr_raw[:, rank, core, :].astype(np.int32),
            meta=meta or {"placement": plan.placement, "rank": rank,
                          "core": core})
