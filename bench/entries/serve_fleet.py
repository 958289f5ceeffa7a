"""Serve cells: `FleetServe` sessions as its three calls.

Each session plans a fresh traffic draw (`engine.plan()`, the host
planner), serves it on a fresh fleet (`engine.run(plan)` ended by
`block_until_ready`: state init, host-to-device copy and the scanned
rounds) and reports it (`engine.report(...)`, with the per-core health
sweep). Spans: plan, run, report.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench import checks
from bench.window import Session


class Entry:
    def __init__(self, system_cfg, config: dict, traffic: dict):
        from repro.launch.serve_fleet import FleetServe, TrafficConfig
        self.config = config
        params = dict(traffic["traffic"])
        params["size_choices"] = tuple(params["size_choices"])
        self.traffic = TrafficConfig(**params)
        self.engine = FleetServe(system_cfg, config["num_ranks"],
                                 config["cores_per_rank"],
                                 traffic=self.traffic,
                                 placement=traffic["placement"], mesh=False)

    def session(self, seed: int, spans) -> Session:
        import jax
        engine = self.engine
        engine.traffic = dataclasses.replace(self.traffic, seed=seed)
        with spans("plan"):
            plan = engine.plan()
        with spans("run"):
            state, resps = engine.run(plan)
            jax.block_until_ready((state, resps))
        with spans("report"):
            rep = engine.report(plan, resps, state)
        del state
        queued = plan.backlog_end + plan.dropped
        record = {"grids": {"op": plan.op, "size": plan.size,
                            "ptr_ref": plan.ptr_ref, "ptr_raw": plan.ptr_raw},
                  "resps": resps,
                  "conservation_residual": rep["conservation_residual"]}
        return Session(
            ops=checks.served_ops(plan.op), rounds=plan.rounds,
            attempted=plan.dispatched + queued,
            failed=queued + rep["failed_allocs"] + rep["dropped_frees"],
            record=record)

    def host_answers(self, record) -> dict:
        return {f: np.asarray(getattr(record["resps"], f))
                for f in checks.FIELDS}

    def report_numbers(self, records) -> dict:
        """The report's own health reading, summed over sessions."""
        return {"conservation_residual": sum(
            abs(r["conservation_residual"]) for r in records)}
