"""Runs one benchmark cell on the chips of this machine and prints one line.

    python3 bench/run.py --workload sw512_micro_fig14 --seed 7 --seconds 10 --trace 0

The cells are the `workloads` of BENCHMARK.json at the checkout's root.
Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero before any work and prints no result.
"""
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the harness is the `bench` package at the checkout's root, the program
# under test is in src/
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))
# the TPU runtime logs to a fixed /tmp path unless told otherwise: keep its
# logs inside the checkout
os.environ.setdefault("TPU_LOG_DIR", os.path.join(ROOT, "results", "tpu_logs"))
os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(root=ROOT, t_start=T_START))
