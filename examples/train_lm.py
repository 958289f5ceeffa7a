"""End-to-end fault-tolerant LM training (reduced granite-3-8b, ~100M-class
family at smoke scale) for a few hundred steps with an injected failure +
checkpoint recovery.

    PYTHONPATH=src python examples/train_lm.py [--steps 200]
"""
import argparse
import shutil
import sys

from repro.launch import train
from repro.runtime.compile_cache import enable_compile_cache

if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    args, _ = ap.parse_known_args()
    shutil.rmtree("/tmp/repro_train_lm", ignore_errors=True)
    sys.argv = [sys.argv[0], "--arch", "granite_3_8b", "--reduced",
                "--steps", str(args.steps), "--batch", "8", "--seq", "128",
                "--n-micro", "2", "--ckpt-dir", "/tmp/repro_train_lm",
                "--ckpt-every", "25", "--fail-at", str(args.steps // 2)]
    train.main()
