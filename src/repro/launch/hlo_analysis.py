"""Loop-aware HLO accounting for the roofline analysis.

XLA's `compiled.cost_analysis()` visits every while body ONCE (known
HloCostAnalysis behavior), so a scan-over-layers model under-reports FLOPs
by ~n_layers x n_microbatches. This module re-derives loop-scaled totals
from `compiled.as_text()`:

  1. parse computations + per-instruction result shapes;
  2. read `known_trip_count` from every while's backend_config (present in
     optimized HLO) and propagate multipliers through the call graph
     (while bodies x trip count; fusions/calls x 1);
  3. FLOPs: 2 * prod(result_shape) * prod(contracted lhs dims) per dot /
     convolution, scaled by the enclosing computation's multiplier;
  4. collective bytes: result-buffer sizes of all-gather / all-reduce /
     reduce-scatter / all-to-all / collective-permute / ragged-all-to-all
     (including async -start forms; -done skipped), scaled likewise;
  5. memory bytes: 2x the result-buffer bytes of every *producing* op
     (dot, fusion, copy, gather/scatter, dynamic slice/update, reduce,
     concatenate, custom-call, collectives) — each produced buffer is
     written once and read ~once by its consumer. broadcast/iota/transpose
     are EXCLUDED: they materialize on the CPU backend used for the
     dry-run but fuse into consumers on TPU.

Used by launch/dryrun.py; validated against cost_analysis() on unrolled
single-layer probes in tests/test_dryrun_small.py.
"""
from __future__ import annotations

import re
from collections import defaultdict

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1, "s4": 1,
    "u4": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute", "ragged-all-to-all")
_MEM_OPS = ("dot", "fusion", "copy", "gather", "scatter", "dynamic-slice",
            "dynamic-update-slice", "reduce", "concatenate", "custom-call",
            "convolution", "reverse", "pad", "slice",
            "select-and-scatter") + _COLLECTIVES


def _shape_bytes(shape_str: str) -> int:
    """Total bytes of all array shapes in a (possibly tuple) shape string."""
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _shape_dims(shape_str: str):
    m = _SHAPE_RE.search(shape_str)
    if not m or m.group(1) not in _DTYPE_BYTES:
        return None
    return [int(d) for d in m.group(2).split(",")] if m.group(2) else []


def _shape_elems(shape_str: str) -> int:
    dims = _shape_dims(shape_str)
    if not dims:
        return 1
    n = 1
    for d in dims:
        n *= d
    return n


def cost_analysis_dict(compiled) -> dict:
    """`compiled.cost_analysis()` as a plain dict."""
    return dict(compiled.cost_analysis())


class Instruction:
    __slots__ = ("name", "shape_str", "op", "line")

    def __init__(self, name, shape_str, op, line):
        self.name, self.shape_str, self.op, self.line = name, shape_str, op, line


def _parse_instr(line: str):
    """'%name = SHAPE op(...)' -> (name, shape_str, op) or None.

    Handles tuple shapes with nested parens and /*index=N*/ comments via
    bracket counting (regexes break on those)."""
    ls = line.strip()
    if ls.startswith("ROOT "):
        ls = ls[5:]
    if not ls.startswith("%"):
        return None
    eq = ls.find(" = ")
    if eq < 0:
        return None
    name = ls[:eq]
    rest = ls[eq + 3:]
    if rest.startswith("("):
        depth = 0
        end = -1
        for i, ch in enumerate(rest):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    end = i
                    break
        if end < 0:
            return None
        shape, rest2 = rest[:end + 1], rest[end + 1:].lstrip()
    else:
        sp = rest.find(" ")
        if sp < 0:
            return None
        shape, rest2 = rest[:sp], rest[sp + 1:].lstrip()
    m = re.match(r"([\w\-]+)\(", rest2)
    if not m:
        return None
    return name, shape, m.group(1)


def parse_hlo(text: str):
    """-> {comp_name: [Instruction]}, {comp_name: trip_multiplier}."""
    comps = {}
    cur = None
    for line in text.splitlines():
        ls = line.strip()
        # computation headers: "[ENTRY] %name (args...) -> result {"
        if ls.endswith("{") and "->" in ls and not line.startswith("    "):
            tok = ls.split()[0]
            if tok == "ENTRY":
                tok = ls.split()[1]
            cur = tok.lstrip("%")
            comps[cur] = []
            continue
        if ls == "}":
            cur = None
            continue
        if cur is None:
            continue
        im = _parse_instr(line)
        if im:
            comps[cur].append(Instruction(im[0], im[1], im[2], line))

    # while call sites: body computation -> trip count
    calls = defaultdict(list)  # callee -> [(caller, factor)]
    for cname, instrs in comps.items():
        for ins in instrs:
            if ins.op == "while":
                body = re.search(r"body=(%?[\w.\-]+)", ins.line)
                trip = re.search(r'known_trip_count.{0,6}?"n":"(\d+)"', ins.line)
                n = int(trip.group(1)) if trip else 1
                if body:
                    calls[body.group(1).lstrip("%")].append((cname, n))
            else:
                for attr in ("calls", "to_apply", "condition",
                             "true_computation", "false_computation",
                             "branch_computations"):
                    for m in re.finditer(attr + r"=\{?(%?[\w.\-]+)", ins.line):
                        calls[m.group(1).lstrip("%")].append((cname, 1))

    # propagate multipliers (call graph is a DAG in HLO)
    mult = {}

    def resolve(comp):
        if comp in mult:
            return mult[comp]
        sites = calls.get(comp)
        if not sites:
            mult[comp] = 1  # entry or unreferenced
            return 1
        mult[comp] = 0  # cycle guard
        total = sum(resolve(caller) * n for caller, n in sites)
        mult[comp] = max(total, 1)
        return mult[comp]

    for comp in comps:
        resolve(comp)
    return comps, mult


def _dot_flops(ins: Instruction, symtab) -> float:
    out_dims = _shape_dims(ins.shape_str)
    if out_dims is None:
        return 0.0
    # lhs operand: either typed ('dot(f32[32,64]{1,0} %x, ...)' — read dims
    # straight off the annotation) or bare ('dot(%x, ...)' — symtab lookup).
    # Split at the op's own paren: the result layout may contain parens too
    # (TPU tiling, 'f32[64,128]{1,0:T(8,128)}').
    parts = ins.line.split(ins.op + "(", 1)
    if len(parts) < 2:
        return 0.0
    args = parts[1]
    m = re.match(r"\s*(?:(\w+\[[\d,]*\])\S*\s+)?(%[\w.\-]+)", args)
    lhs_dims = None
    if m:
        lhs_dims = (_shape_dims(m.group(1)) if m.group(1)
                    else symtab.get(m.group(2)))
    cm = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", ins.line)
    contracted = 1
    if lhs_dims and cm and cm.group(1):
        for d in cm.group(1).split(","):
            di = int(d)
            if di < len(lhs_dims):
                contracted *= lhs_dims[di]
    out = 1
    for d in out_dims:
        out *= d
    return 2.0 * out * contracted


def analyze(text: str) -> dict:
    """Loop-scaled totals from optimized HLO text."""
    comps, mult = parse_hlo(text)
    flops = 0.0
    coll_bytes = 0.0
    coll_ops = defaultdict(float)
    mem_bytes = 0.0
    for cname, instrs in comps.items():
        k = mult.get(cname, 1)
        symtab = {ins.name: _shape_dims(ins.shape_str) for ins in instrs}
        for ins in instrs:
            op = ins.op
            if op in ("dot", "convolution"):
                flops += k * _dot_flops(ins, symtab)
            base = op[:-6] if op.endswith("-start") else op
            if base in _COLLECTIVES and not op.endswith("-done"):
                b = _shape_bytes(ins.shape_str)
                coll_bytes += k * b
                coll_ops[base] += k * b
            if base in _MEM_OPS and not op.endswith("-done"):
                # produced buffer: one write + ~one consumer read.
                # In-place patterns are aliased by XLA, not re-materialized:
                #  * dynamic-update-slice: traffic = the UPDATE slice, not the
                #    full result (scan stacking / grad accumulation);
                #  * large fusions whose result dims equal an operand's dims
                #    (whole-carry converts/copies) alias on TPU -> skip.
                b = _shape_bytes(ins.shape_str)
                operands = re.findall(r"(%[\w.\-]+)",
                                      ins.line.split("(", 1)[1])
                if base == "dynamic-update-slice" and len(operands) >= 2:
                    upd = symtab.get(operands[1])
                    if upd is not None:
                        ub = 1
                        for d in upd:
                            ub *= d
                        width = max(_shape_bytes(ins.shape_str)
                                    // max(_shape_elems(ins.shape_str), 1), 1)
                        b = ub * width  # traffic = the update slice only
                elif (base == "fusion" and b > 1e8
                      and not ins.shape_str.startswith("(")):
                    rdims = _shape_dims(ins.shape_str)
                    if rdims is not None and any(
                            symtab.get(o) == rdims for o in operands):
                        b = 0
                mem_bytes += 2 * k * b
    return {
        "flops_scaled": flops,
        "collective_bytes_scaled": coll_bytes,
        "collective_bytes_by_op": dict(coll_ops),
        "memory_bytes_scaled": mem_bytes,
        "n_computations": len(comps),
    }


def collective_schedule(text: str, limit: int = 40):
    """Human-readable (op, result shape, multiplier) list for EXPERIMENTS.md."""
    comps, mult = parse_hlo(text)
    out = []
    for cname, instrs in comps.items():
        for ins in instrs:
            base = ins.op[:-6] if ins.op.endswith("-start") else ins.op
            if base in _COLLECTIVES and not ins.op.endswith("-done"):
                out.append({
                    "op": base, "shape": ins.shape_str.strip(),
                    "times": mult.get(cname, 1),
                    "bytes": _shape_bytes(ins.shape_str),
                })
    out.sort(key=lambda d: -d["bytes"] * d["times"])
    return out[:limit]
