"""Dynamic graph updates — the paper's case study (Section 6.2 / Fig 16).

    PYTHONPATH=src python examples/graph_update.py

Static CSR vs linked-list adjacency on three allocators. The dynamic
structure is functionally real (pointers into an allocator-managed heap);
throughput comes from the DPU cost model.
"""
from repro.graphupd.workload import GraphConfig, compare_all
from repro.runtime.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    cfg = GraphConfig()
    print(f"partition: {cfg.n_nodes} nodes, {cfg.n_edges_pre} pre-edges, "
          f"{cfg.n_edges_new} new edges (1:2, paper methodology)\n")
    res = compare_all(cfg)
    st = res["static_csr"]["us_per_edge"]
    print(f"{'structure':22s} {'us/edge':>9s} {'edges/s':>12s} {'vs static':>10s}")
    for name, v in res.items():
        speed = st / v["us_per_edge"]
        print(f"{name:22s} {v['us_per_edge']:9.3f} {v['edges_per_s']:12.0f} "
              f"{speed:9.1f}x")
    sw, hw = res["sw"], res["hwsw"]
    fr = sw["frontend_ops"] / (sw["frontend_ops"] + sw["backend_ops"])
    print(f"\nfrontend service rate (PIM-malloc-SW): {fr:.1%} (paper: >90%)")
    if sw["dram_bytes"]:
        red = 1 - hw["dram_bytes"] / sw["dram_bytes"]
        print(f"metadata DRAM traffic reduction HW/SW vs SW: {red:.0%} "
              "(paper: 33%)")


if __name__ == "__main__":
    main()
