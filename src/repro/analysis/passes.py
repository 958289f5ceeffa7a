"""The `pimcheck` checker passes: rule sets over traced allocator jaxprs.

Each pass is a function ``(traced, ctx) -> [Finding]`` over a
`TracedStep` (the closed jaxpr of one backend step plus the state/request
calling convention). The rules are *calibrated against the real
backends*: every registered kind must trace green (or carry an explicit
entry in `SUPPRESSIONS` with a written justification), while the seeded
broken mini-backends in `repro.analysis.fixtures` must be flagged — both
directions are pinned by tests/test_analysis.py.

Passes
------
  donation     donated-state discipline: every state buffer threads
               in -> out with an unchanged (shape, dtype) multiset, and
               no large state leaf is silently re-materialized from a
               constant (a dropped donation turns an in-place update
               into a fresh allocation every round).
  int-width    pointer/size arithmetic stays 32-bit: no 64-bit values
               on the allocator path, no pointer/size routed through
               float and back (lossy above 2^24), and any product of two
               request-derived int32 values must be overflow-guarded by
               a division check (the `total_calloc_bytes` idiom).
  index-bounds every gather/scatter lowered with PROMISE_IN_BOUNDS must
               have index provenance passing through a bounding op
               (clip/min/max/mod/mask/bool-count...); `dynamic_slice`
               is hardware-clamped and always fine.
  write-race   intra-round thread-axis races: a top-level (outside the
               serialized scan mutex region) non-commutative scatter
               whose per-thread indices are request-derived and carry no
               structural disjointness witness (iota over the thread
               axis, or an argsort permutation) lets two threads write
               the same metadata address in one round — the UB class the
               trace linter excludes by construction.
"""
from __future__ import annotations

import dataclasses
import fnmatch

from . import jaxpr_utils as ju
from .jaxpr_utils import (Literal, Var, aval_sig, derives_from,
                          forward_taint, iter_eqns, producers)

PASS_NAMES = ("donation", "int-width", "index-bounds", "write-race")


@dataclasses.dataclass(frozen=True)
class Finding:
    pass_name: str
    target: str      # backend kind or fixture name
    tier: str        # single | vmap | sharded
    severity: str    # error | warn
    message: str

    def fmt(self) -> str:
        return (f"[{self.pass_name}] {self.target}/{self.tier} "
                f"{self.severity}: {self.message}")


# --------------------------------------------------------------------------
# suppressions: (pass, target glob, message substring, justification).
# A suppressed finding is reported but does not fail pimcheck. Every entry
# must say WHY the hazard is acceptable; docs/analysis.md documents the
# policy (prefer fixing the code or sharpening the pass — the calibration
# sweep for this file turned its one candidate entry, the masked
# `where(valid, idx, fallback)` scatter idiom, into a pass rule instead).
# --------------------------------------------------------------------------
SUPPRESSIONS = ()


def suppression_for(f: Finding):
    for pass_name, target_glob, substr, reason in SUPPRESSIONS:
        if (f.pass_name == pass_name
                and fnmatch.fnmatch(f.target, target_glob)
                and substr in f.message):
            return reason
    return None


@dataclasses.dataclass
class TracedStep:
    """One traced backend step + its calling convention, fed to passes."""

    target: str          # kind / fixture name
    tier: str            # single | vmap | sharded
    closed_jaxpr: object
    n_state_in: int      # leading invars that are donated state leaves
    n_state_out: int     # leading outvars that are next-round state leaves

    @property
    def jaxpr(self):
        return self.closed_jaxpr.jaxpr

    @property
    def state_invars(self):
        return self.jaxpr.invars[:self.n_state_in]

    @property
    def req_invars(self):
        return self.jaxpr.invars[self.n_state_in:]

    @property
    def state_outvars(self):
        return self.jaxpr.outvars[:self.n_state_out]


# --------------------------------------------------------------------------
# taint / guard vocabulary (calibrated on the real backends' jaxprs)
# --------------------------------------------------------------------------
# a result of these is bounded regardless of operand wildness
_BOUND_PRIMS = frozenset({
    "clamp", "min", "max", "rem", "and", "iota", "population_count",
    "shift_right_logical", "shift_right_arithmetic",
    "reduce_min", "reduce_max", "argmin", "argmax", "sort",
})
# jnp helpers that lower to jit-wrapped sub-jaxprs; identified by name
_BOUND_JIT_NAMES = frozenset({
    "clip", "_clip", "remainder", "mod", "argsort", "searchsorted",
})
_DISJOINT_PRIMS = frozenset({"iota"})
_DISJOINT_JIT_NAMES = frozenset({"argsort"})  # permutations never collide


def _is_bounding(eqn, tainted) -> bool:
    name = eqn.primitive.name
    if name in _BOUND_PRIMS:
        return True
    if name == "jit" and eqn.params.get("name") in _BOUND_JIT_NAMES:
        return True
    if name == "convert_element_type":
        src = getattr(eqn.invars[0].aval, "dtype", None)
        if str(src) == "bool":   # {0, 1} however wild the inputs
            return True
    # the codebase's guard idiom: `where(valid, expr, fallback)` with an
    # untainted fallback bounds the result (a masked write / parked
    # index). JAX's negative-index normalization select —
    # select_n(idx < 0, idx, idx + N) — has BOTH branches tainted and is
    # deliberately NOT a guard.
    if (name == "select_n"
            or (name == "jit" and eqn.params.get("name") == "_where")):
        data_ops = eqn.invars[1:]   # operand 0 is the predicate
        if any(isinstance(v, Literal) or v not in tainted
               for v in data_ops):
            return True
    # comparisons produce bools
    return name in ("lt", "le", "gt", "ge", "eq", "ne")


def _request_taint(tr: TracedStep):
    """Vars data-derived from the request operands with no bounding op in
    between (top jaxpr level; higher-order eqns propagate in -> out)."""
    return forward_taint(tr.jaxpr, tr.req_invars, kill_fn=_is_bounding)


def _disjoint_witness(jaxpr, var, prods) -> bool:
    return derives_from(
        jaxpr, var,
        lambda e: (e.primitive.name in _DISJOINT_PRIMS
                   or (e.primitive.name == "jit"
                       and e.params.get("name") in _DISJOINT_JIT_NAMES)),
        prods)


# --------------------------------------------------------------------------
# pass: donation
# --------------------------------------------------------------------------
_BIG_LEAF = 64  # elements; below this a copy is noise, not a donation bug


def check_donation(tr: TracedStep, _ctx=None):
    finds = []

    def f(sev, msg):
        finds.append(Finding("donation", tr.target, tr.tier, sev, msg))

    in_sigs = sorted(aval_sig(v) for v in tr.state_invars)
    out_sigs = sorted(aval_sig(v) for v in tr.state_outvars)
    if in_sigs != out_sigs:
        gone = [s for s in in_sigs if s not in out_sigs]
        new = [s for s in out_sigs if s not in in_sigs]
        f("error", "state buffer multiset changed across the round: "
          f"dropped {gone}, introduced {new} — donated buffers cannot be "
          "reused in place")

    prods = producers(tr.jaxpr)
    used = set()
    for eqn in tr.jaxpr.eqns:
        used.update(v for v in eqn.invars if isinstance(v, Var))
    out_set = set(v for v in tr.jaxpr.outvars if isinstance(v, Var))

    for i, v in enumerate(tr.state_outvars):
        if isinstance(v, Literal):
            f("error", f"state output leaf #{i} is a literal constant — "
              "the round discards this buffer entirely")
            continue
        if v in set(tr.jaxpr.invars):
            continue  # threaded through untouched: ideal donation
        eqn = prods.get(v)
        if eqn is None:
            continue
        size = 1
        for d in aval_sig(v)[0]:
            size *= d
        if size < _BIG_LEAF:
            continue
        if eqn.primitive.name == "broadcast_in_dim" and all(
                isinstance(iv, Literal) or prods.get(iv) is None
                for iv in eqn.invars):
            f("error", f"state output leaf #{i} {aval_sig(v)} is "
              "re-materialized from a constant broadcast — the donated "
              "input buffer is silently dropped and a fresh allocation "
              "is made every round")

    for i, v in enumerate(tr.state_invars):
        size = 1
        for d in aval_sig(v)[0]:
            size *= d
        if size >= _BIG_LEAF and v not in used and v not in out_set:
            f("warn", f"state input leaf #{i} {aval_sig(v)} is never read "
              "and never returned — dead donated buffer")
    return finds


# --------------------------------------------------------------------------
# pass: int-width
# --------------------------------------------------------------------------
def check_int_width(tr: TracedStep, _ctx=None):
    finds = []

    def f(sev, msg):
        finds.append(Finding("int-width", tr.target, tr.tier, sev, msg))

    for eqn, path in iter_eqns(tr.jaxpr):
        for v in list(eqn.invars) + list(eqn.outvars):
            dt = str(getattr(getattr(v, "aval", None), "dtype", ""))
            if dt in ("int64", "uint64", "float64"):
                f("error", f"64-bit value ({dt}) at `{eqn.primitive.name}` "
                  f"in {'/'.join(path) or 'top level'} — allocator "
                  "arithmetic must stay 32-bit")
                break

    # int -> float -> int roundtrip: pointers/sizes above 2^24 lose bits
    floaty = set()
    for eqn in tr.jaxpr.eqns:
        name = eqn.primitive.name
        if name == "convert_element_type":
            src = str(eqn.invars[0].aval.dtype)
            dst = str(eqn.params["new_dtype"])
            if src.startswith("int") and dst.startswith("float"):
                floaty.update(v for v in eqn.outvars if isinstance(v, Var))
                continue
            if (dst.startswith(("int", "uint"))
                    and src.startswith("float")
                    and any(isinstance(v, Var) and v in floaty
                            for v in eqn.invars)):
                f("error", "integer value routed through float and back "
                  "(int -> float -> int convert chain) — pointer/size "
                  "bits above 2^24 are lost")
                continue
        if any(isinstance(v, Var) and v in floaty for v in eqn.invars):
            floaty.update(v for v in eqn.outvars if isinstance(v, Var))

    # unguarded products of two request-derived int32s (calloc overflow
    # class): the result must feed a division check, as in
    # `pim_malloc.total_calloc_bytes` (wide = a*b; ok = wide // b == a)
    tainted = _request_taint(tr)
    div_guarded = set()
    for eqn in tr.jaxpr.eqns:
        name = eqn.primitive.name
        if name == "div" or (name == "jit"
                             and eqn.params.get("name") == "floor_divide"):
            div_guarded.update(v for v in eqn.invars if isinstance(v, Var))
    for eqn in tr.jaxpr.eqns:
        if eqn.primitive.name != "mul":
            continue
        ins = [v for v in eqn.invars if isinstance(v, Var)]
        if len(ins) < 2 or not all(v in tainted for v in ins):
            continue
        if not str(eqn.outvars[0].aval.dtype).startswith("int"):
            continue
        if any(v in div_guarded for v in eqn.outvars):
            continue
        f("error", "int32 product of two request-derived values with no "
          "overflow guard — a division check on the product "
          "(total_calloc_bytes idiom) or a pre-clamp is required")
    return finds


# --------------------------------------------------------------------------
# pass: index-bounds
# --------------------------------------------------------------------------
def check_index_bounds(tr: TracedStep, _ctx=None):
    finds = []
    tainted = _request_taint(tr)
    unsafe = "PROMISE_IN_BOUNDS"
    for eqn in tr.jaxpr.eqns:  # top level: where request-driven indexing is
        name = eqn.primitive.name
        if not name.startswith(("gather", "scatter")):
            continue
        mode = str(eqn.params.get("mode"))
        if unsafe not in mode:
            continue  # FILL_OR_DROP / CLIP are safe by construction
        idx = eqn.invars[1]
        if not isinstance(idx, Var) or idx not in tainted:
            continue  # constant or bounded provenance
        finds.append(Finding(
            "index-bounds", tr.target, tr.tier, "error",
            f"`{name}` with mode PROMISE_IN_BOUNDS indexes "
            f"{aval_sig(eqn.invars[0])} with a request-derived index that "
            "has no bounding op (clip/min/max/mod/mask) in its provenance "
            "— out-of-bounds requests reach unchecked memory"))
    return finds


# --------------------------------------------------------------------------
# pass: write-race
# --------------------------------------------------------------------------
def check_write_race(tr: TracedStep, _ctx=None):
    finds = []
    tainted = _request_taint(tr)
    prods = producers(tr.jaxpr)
    # only the top level: eqns inside scan/while run in the serialized
    # mutex region (one thread per iteration) and cannot race
    for eqn, path in iter_eqns(tr.jaxpr, skip_prims=ju.SERIAL_PRIMS):
        if path:  # nested in jit etc.: vars are scoped, skip
            continue
        if eqn.primitive.name != "scatter":   # scatter-add is commutative
            continue
        upd = eqn.invars[2]
        shape = aval_sig(upd)[0]
        if not shape or shape[0] < 2:
            continue  # a single update cannot self-race
        idx = eqn.invars[1]
        if not isinstance(idx, Var) or idx not in tainted:
            continue  # indices not request-controlled
        if _disjoint_witness(tr.jaxpr, idx, prods):
            continue  # iota / argsort permutation: provably distinct slots
        finds.append(Finding(
            "write-race", tr.target, tr.tier, "error",
            f"non-commutative `scatter` of {shape[0]} per-thread updates "
            f"into {aval_sig(eqn.invars[0])} with request-derived indices "
            "and no disjointness witness (iota/argsort) — two threads can "
            "write the same address in one round, and the winner is "
            "scatter-order-defined"))
    return finds


ALL_PASSES = {
    "donation": check_donation,
    "int-width": check_int_width,
    "index-bounds": check_index_bounds,
    "write-race": check_write_race,
}


def run_passes(tr: TracedStep, passes=None):
    """Run the selected passes; returns (active, suppressed) finding
    lists, where suppressed entries are (finding, justification)."""
    active, suppressed = [], []
    for name in (passes or PASS_NAMES):
        for f in ALL_PASSES[name](tr):
            reason = suppression_for(f)
            if reason is None:
                active.append(f)
            else:
                suppressed.append((f, reason))
    return active, suppressed
