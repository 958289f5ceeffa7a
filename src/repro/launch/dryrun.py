import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: lower + compile every (arch x shape) on the production
meshes, capture memory/cost analysis + the loop-scaled collective schedule.

    PYTHONPATH=src python -m repro.launch.dryrun --arch granite_3_8b \
        --shape train_4k [--multi-pod] [--out results/dryrun]
    PYTHONPATH=src python -m repro.launch.dryrun --all

The XLA_FLAGS line above MUST run before any other jax-importing module:
this container has one CPU device; the dry-run fakes 512 host devices so
`jax.make_mesh((2,16,16))` can build the production mesh. Smoke tests and
benchmarks do NOT import this module and keep seeing 1 device.
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro import configs
from repro.launch import hlo_analysis
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import (make_decode_step, make_prefill_step,
                                make_train_step, opt_state_sds)
from repro.models import registry
from repro.models.config import SHAPES
from repro.optim.adamw import AdamWConfig
from repro.parallel import sharding

RESULTS_DIR = "results/dryrun"

# TPU v5e-class hardware constants (per chip)
PEAK_FLOPS = 197e12          # bf16
HBM_BW = 819e9               # bytes/s
ICI_BW = 50e9                # bytes/s per link


def input_specs(arch: str, shape_name: str):
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    cfg = configs.get(arch)
    shape = SHAPES[shape_name]
    if shape.kind == "train":
        return {"batch": registry.train_specs(cfg, shape)}
    if shape.kind == "prefill":
        batch, cache = registry.prefill_specs(cfg, shape)
        return {"batch": batch, "cache": cache}
    batch, cache = registry.decode_specs(cfg, shape)
    return {"batch": batch, "cache": cache}


def _sharded_bytes(sds_tree, spec_tree, mesh) -> int:
    """Per-device bytes of a sharded pytree (analytic)."""
    import numpy as np
    total = 0
    for s, p in zip(jax.tree.leaves(sds_tree),
                    jax.tree.leaves(spec_tree,
                                    is_leaf=lambda x: isinstance(
                                        x, jax.sharding.PartitionSpec))):
        n = int(np.prod(s.shape)) if s.shape else 1
        div = 1
        for axes in p:
            if axes is None:
                continue
            for a in (axes if isinstance(axes, tuple) else (axes,)):
                div *= mesh.shape[a]
        total += n * jnp.dtype(s.dtype).itemsize // max(div, 1)
    return total


def dryrun_cell(arch: str, shape_name: str, multi_pod: bool = False,
                n_micro: int | None = None, overrides: dict | None = None,
                verbose: bool = True) -> dict:
    cfg = configs.get(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "kind": shape.kind, "status": "ok",
    }

    if shape_name == "long_500k" and not cfg.sub_quadratic:
        result["status"] = "skipped"
        result["reason"] = ("pure full-attention arch: O(L^2) at 512K is out "
                            "of assigned scope (DESIGN.md)")
        return result

    p_sds = registry.param_sds(cfg)
    # serving (prefill/decode) has no optimizer state: params place TP-only
    # (replicated over data); FSDP gathers per step would be pure overhead
    fsdp = cfg.fsdp and shape.kind == "train"
    p_spec = sharding.param_specs(mesh, p_sds, fsdp=fsdp)
    dp = 1
    for a in sharding.dp_axes(mesh):
        dp *= mesh.shape[a]

    t0 = time.time()
    def nm_(spec):
        return sharding.named(mesh, spec)
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            opt_cfg = AdamWConfig(moment_dtype=cfg.opt_moment_dtype)
            nm = (n_micro or cfg.train_microbatches
                  or max(1, min(8, shape.global_batch // dp)))
            step = make_train_step(cfg, opt_cfg, n_micro=nm,
                                    grad_pspec=p_spec)
            o_sds = opt_state_sds(cfg, opt_cfg)
            from repro.optim.adamw import AdamWState
            o_spec = AdamWState(count=jax.sharding.PartitionSpec(),
                                m=p_spec, v=p_spec)  # moments shard like params
            b_sds = input_specs(arch, shape_name)["batch"]
            b_spec = sharding.batch_specs(mesh, b_sds)
            result["n_micro"] = nm
            jitted = jax.jit(
                step, in_shardings=(nm_(p_spec), nm_(o_spec), nm_(b_spec)),
                out_shardings=(nm_(p_spec), nm_(o_spec), None))
            lowered = jitted.lower(p_sds, o_sds, b_sds)
            state_parts = {"params": (p_sds, p_spec), "opt_m": (o_sds.m, p_spec),
                           "opt_v": (o_sds.v, p_spec)}
        elif shape.kind == "prefill":
            step = make_prefill_step(cfg)
            sp = input_specs(arch, shape_name)
            b_spec = sharding.batch_specs(mesh, sp["batch"])
            c_spec = sharding.cache_specs(mesh, sp["cache"])
            jitted = jax.jit(
                step, in_shardings=(nm_(p_spec), nm_(b_spec), nm_(c_spec)),
                out_shardings=(nm_(c_spec), None))
            lowered = jitted.lower(p_sds, sp["batch"], sp["cache"])
            state_parts = {"params": (p_sds, p_spec),
                           "cache": (sp["cache"], c_spec)}
        else:  # decode
            step = make_decode_step(cfg)
            sp = input_specs(arch, shape_name)
            b_spec = sharding.batch_specs(mesh, sp["batch"])
            c_spec = sharding.cache_specs(mesh, sp["cache"])
            jitted = jax.jit(
                step, in_shardings=(nm_(p_spec), nm_(c_spec), nm_(b_spec)),
                out_shardings=(nm_(c_spec), None))
            lowered = jitted.lower(p_sds, sp["cache"], sp["batch"])
            state_parts = {"params": (p_sds, p_spec),
                           "cache": (sp["cache"], c_spec)}

        result["lower_s"] = round(time.time() - t0, 1)
        t1 = time.time()
        compiled = lowered.compile()
        result["compile_s"] = round(time.time() - t1, 1)

    # ----- memory analysis --------------------------------------------------
    mem = {}
    try:
        ma = compiled.memory_analysis()
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "generated_code_size_in_bytes"):
            v = getattr(ma, attr, None)
            if v is not None:
                mem[attr] = int(v)
    except Exception as e:  # CPU backend may not implement it
        mem["error"] = str(e)
    result["memory_analysis"] = mem
    result["state_bytes_per_device"] = {
        k: _sharded_bytes(sds, spec, mesh) for k, (sds, spec) in
        state_parts.items()
    }

    # ----- cost analysis (raw; while bodies counted once) -------------------
    try:
        ca = hlo_analysis.cost_analysis_dict(compiled)
        result["cost_analysis_raw"] = {
            k: float(v) for k, v in ca.items()
            if k in ("flops", "bytes accessed", "transcendentals")
        }
    except Exception as e:
        result["cost_analysis_raw"] = {"error": str(e)}

    # ----- loop-scaled HLO accounting ---------------------------------------
    txt = compiled.as_text()
    result["hlo"] = hlo_analysis.analyze(txt)
    result["collective_schedule"] = hlo_analysis.collective_schedule(txt, 25)
    if os.environ.get("DRYRUN_SAVE_HLO"):
        os.makedirs(RESULTS_DIR, exist_ok=True)
        hp = os.path.join(RESULTS_DIR, f"{arch}__{shape_name}__"
                          f"{result['mesh'].replace('x', '_')}.hlo.txt")
        with open(hp, "w") as f:
            f.write(txt)
        result["hlo_path"] = hp

    # ----- roofline terms (the SPMD HLO is already the per-device program) --
    n_dev = mesh.devices.size
    terms = {
        "compute_s": result["hlo"]["flops_scaled"] / PEAK_FLOPS,
        "memory_s": result["hlo"]["memory_bytes_scaled"] / HBM_BW,
        "collective_s": result["hlo"]["collective_bytes_scaled"] / ICI_BW,
    }
    terms["bottleneck"] = max(terms, key=lambda k: terms[k]
                              if k.endswith("_s") else -1)
    result["roofline"] = terms
    result["devices"] = int(n_dev)

    if verbose:
        print(json.dumps({k: result[k] for k in
                          ("arch", "shape", "mesh", "status", "compile_s")},
                         indent=None))
    return result


def save_result(res: dict, out_dir: str = RESULTS_DIR):
    os.makedirs(out_dir, exist_ok=True)
    name = f"{res['arch']}__{res['shape']}__{res['mesh'].replace('x', '_')}.json"
    with open(os.path.join(out_dir, name), "w") as f:
        json.dump(res, f, indent=1)
    return os.path.join(out_dir, name)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default=RESULTS_DIR)
    ap.add_argument("--overrides", default=None,
                    help="JSON dict of ArchConfig overrides (SSPerf iters)")
    args = ap.parse_args()
    overrides = json.loads(args.overrides) if args.overrides else None

    cells = []
    if args.all:
        for arch in configs.ARCHS:
            for shape in SHAPES:
                meshes = (False, True) if args.both_meshes else (args.multi_pod,)
                for mp in meshes:
                    cells.append((arch, shape, mp))
    else:
        meshes = (False, True) if args.both_meshes else (args.multi_pod,)
        for mp in meshes:
            cells.append((args.arch, args.shape, mp))

    failures = 0
    for arch, shape, mp in cells:
        key = f"{arch}/{shape}/{'2x16x16' if mp else '16x16'}"
        try:
            res = dryrun_cell(arch, shape, multi_pod=mp, overrides=overrides)
        except Exception as e:
            failures += 1
            res = {"arch": arch, "shape": shape,
                   "mesh": "2x16x16" if mp else "16x16",
                   "status": "error", "error": str(e)[-2000:],
                   "traceback": traceback.format_exc()[-4000:]}
            print(f"FAIL {key}: {e}")
        path = save_result(res, args.out)
        print(f"{key}: {res['status']} -> {path}", flush=True)
    if failures:
        raise SystemExit(f"{failures} dry-run cells failed")


if __name__ == "__main__":
    main()
