"""Runs a cell on several seeds in one process, with a fault planted or
without, and prints each run's checks: the readings the limits are set from.

    python3 bench/control.py --workload sw512_micro_fig14 --seconds 10 \
        --seeds 11 12 13 [--fault drop_frees | --fault none]

`--fault drop_frees` (the default) is the control of `bench/faults.py`;
`--fault none` gives the sound readings. Not part of a benchmark run.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))
os.environ.setdefault("TPU_LOG_DIR", os.path.join(ROOT, "results", "tpu_logs"))
os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)

from bench import faults, harness  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", default="drop_frees",
                    choices=faults.FAULTS + ("none",))
    args = ap.parse_args(argv)
    fault = None if args.fault == "none" else args.fault
    for seed in args.seeds:
        t0 = time.perf_counter()
        r = harness.run_cell(ROOT, args.workload, seed, args.seconds, False,
                             t0, fault=fault)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "fault": args.fault, "correct": r["correct"],
                          "checks": r["checks"], "metrics": r["metrics"],
                          "attempted": r["attempted"], "failed": r["failed"],
                          "seconds": time.perf_counter() - t0}), flush=True)


if __name__ == "__main__":
    main()
