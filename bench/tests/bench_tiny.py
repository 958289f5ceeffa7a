"""A checkout of the benchmark at a tiny size, for the tests on the CPU.

`tiny_root(tmp)` copies the harness into `tmp/bench` and writes a
BENCHMARK.json whose cells run the two entries on 2 ranks x 2 cores x 4
threads with 1 MiB heaps. The files it adds are data only: the harness
finds them by name.
"""
import json
import pathlib
import shutil
import sys

REPO = pathlib.Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

TINY = {"num_ranks": 2, "cores_per_rank": 2, "num_threads": 4,
        "heap_bytes": 1 << 20}
SERVE = {"rounds": 6, "num_tenants": 16, "zipf_a": 1.1, "arrival_rate": 12.0,
         "queue_cap": 64}
MICRO = {"alloc_rounds": 3}


def tiny_root(tmp, kinds=("hwsw",)) -> pathlib.Path:
    tmp = pathlib.Path(tmp)
    shutil.copytree(REPO / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    configs, workloads = [], []
    for kind in kinds:
        src = json.loads(
            (REPO / "bench" / "configs" / f"upmem512_{kind}.json").read_text())
        name = f"tiny_{kind}"
        cfg = dict(src, name=name, **TINY)
        path = tmp / "bench" / "configs" / f"{name}.json"
        path.write_text(json.dumps(cfg))
        configs.append({"name": name, "source": src["source"],
                        "file": f"bench/configs/{name}.json",
                        "reduced": sorted(TINY), "why": "tiny"})
        for traffic in ("serve_zipf", "micro_fig14"):
            workloads.append({"name": f"{name}_{traffic}", "config": name,
                              "traffic": f"tiny_{traffic}", "chips": 1,
                              "why": "tiny"})
    t = json.loads((REPO / "bench/traffic/serve_zipf.json").read_text())
    t["traffic"].update(SERVE)
    (tmp / "bench/traffic/tiny_serve_zipf.json").write_text(json.dumps(t))
    t = json.loads((REPO / "bench/traffic/micro_fig14.json").read_text())
    t.update(MICRO)
    (tmp / "bench/traffic/tiny_micro_fig14.json").write_text(json.dumps(t))
    cells = [w["name"] for w in workloads]
    for m in bench["per_layer"]:
        kinds_of = {w["traffic"] for w in bench["workloads"]
                    if w["name"] in m.get("workloads", ())}
        m["workloads"] = [c for c in cells
                          if any(c.endswith(k) for k in kinds_of)]
    bench.update(configs=configs, workloads=workloads)
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp
