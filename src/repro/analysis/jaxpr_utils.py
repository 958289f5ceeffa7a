"""Jaxpr-walking utilities shared by the `pimcheck` passes.

Everything here operates on the closed jaxprs produced by
`jax.make_jaxpr` over a backend step: recursive equation iteration
through every higher-order primitive (scan / while / cond / jit /
custom-derivative calls / pallas_call), producer maps, and small
provenance / taint dataflow helpers. The passes in
`repro.analysis.passes` are thin rule sets over these.
"""
from __future__ import annotations

from jax import core as jcore
try:  # jax >= 0.4.30 moved the jaxpr types
    from jax.extend import core as jexcore
    Jaxpr = jexcore.Jaxpr
    ClosedJaxpr = jexcore.ClosedJaxpr
    Var = jexcore.Var
    Literal = jexcore.Literal
except Exception:  # pragma: no cover - older jax layouts
    Jaxpr = jcore.Jaxpr
    ClosedJaxpr = jcore.ClosedJaxpr
    Var = jcore.Var
    Literal = jcore.Literal

# higher-order primitives whose sub-jaxprs are *serialized* per element —
# the scan carry makes iterations a mutex region, so intra-round
# cross-thread race analysis must not descend into them
SERIAL_PRIMS = ("scan", "while")


def _as_jaxpr(obj):
    if isinstance(obj, ClosedJaxpr):
        return obj.jaxpr
    if isinstance(obj, Jaxpr):
        return obj
    return None


def sub_jaxprs(eqn):
    """All sub-jaxprs of one equation, regardless of the primitive.

    Scans `eqn.params` generically: any value that is a (Closed)Jaxpr, or
    a tuple/list containing them (cond branches, custom-vjp pairs), is a
    sub-program. This stays correct as primitives evolve, instead of
    keying on a hard-coded param-name table.
    """
    subs = []
    for val in eqn.params.values():
        j = _as_jaxpr(val)
        if j is not None:
            subs.append(j)
        elif isinstance(val, (tuple, list)):
            for item in val:
                j = _as_jaxpr(item)
                if j is not None:
                    subs.append(j)
    return subs


def iter_eqns(jaxpr, path=(), descend=True, skip_prims=()):
    """Yield ``(eqn, path)`` for every equation, recursively.

    ``path`` is the tuple of enclosing primitive names (e.g.
    ``("scan", "cond")``); ``skip_prims`` prunes descent into the named
    higher-order primitives (their eqns are not yielded either).
    """
    j = _as_jaxpr(jaxpr)
    for eqn in j.eqns:
        name = eqn.primitive.name
        yield eqn, path
        if descend and name not in skip_prims:
            for sub in sub_jaxprs(eqn):
                yield from iter_eqns(sub, path + (name,),
                                     descend=descend, skip_prims=skip_prims)


def producers(jaxpr):
    """Map every output `Var` to the equation that produces it (one level,
    no descent — sub-jaxpr vars live in their own scope)."""
    out = {}
    for eqn in _as_jaxpr(jaxpr).eqns:
        for v in eqn.outvars:
            if isinstance(v, Var):
                out[v] = eqn
    return out


def forward_taint(jaxpr, seed_vars, kill_prims=(), kill_fn=None):
    """Forward may-taint dataflow at one jaxpr level.

    Starts from ``seed_vars`` and marks every value data-dependent on
    them. An equation whose primitive is in ``kill_prims`` (or for which
    ``kill_fn(eqn, tainted)`` is true) *bounds* its result — taint does
    not propagate through it (e.g. a gather from a constant size-class
    table yields a bounded value however wild the index was; the
    ``kill_fn`` receives the current tainted set so guards like
    ``where(valid, idx, 0)`` — a select with an untainted fallback
    branch — can be recognized).

    Higher-order equations propagate conservatively: any tainted input
    taints every output. Returns the set of tainted Vars.
    """
    tainted = set(v for v in seed_vars if isinstance(v, Var))
    for eqn in _as_jaxpr(jaxpr).eqns:
        if eqn.primitive.name in kill_prims:
            continue
        if kill_fn is not None and kill_fn(eqn, tainted):
            continue
        if any(isinstance(v, Var) and v in tainted for v in eqn.invars):
            tainted.update(v for v in eqn.outvars if isinstance(v, Var))
    return tainted


def derives_from(jaxpr, var, pred, prods=None, _seen=None):
    """True iff any equation in ``var``'s producer chain satisfies
    ``pred(eqn)`` (backward DFS at one jaxpr level; literals/invars end
    the walk)."""
    if prods is None:
        prods = producers(jaxpr)
    if _seen is None:
        _seen = set()
    if not isinstance(var, Var) or var in _seen:
        return False
    _seen.add(var)
    eqn = prods.get(var)
    if eqn is None:
        return False
    if pred(eqn):
        return True
    return any(derives_from(jaxpr, v, pred, prods, _seen)
               for v in eqn.invars)


def aval_sig(v):
    """(shape, dtype) signature of a var/aval, for donation matching."""
    aval = v.aval if hasattr(v, "aval") else v
    return (tuple(getattr(aval, "shape", ())),
            str(getattr(aval, "dtype", "abstract")))
