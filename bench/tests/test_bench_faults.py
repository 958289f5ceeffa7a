"""A whole run, the chip check skipped, with the timed path sound and with
each fault planted under it: sound runs are correct, every fault and the
control are not. (The cells run on one chip, so there is no exchange
between chips to leave out.)"""
import time

import pytest

from bench_tiny import tiny_root
from bench import faults, harness

CELLS = ("tiny_hwsw_serve_zipf", "tiny_sw_micro_fig14",
         "tiny_hwsw_micro_fig14")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("faults"), kinds=("hwsw", "sw"))


def _run(root, cell, fault=None, seed=2**31 + 7):
    return harness.run_cell(root, cell, seed, 0.3, False,
                            time.perf_counter(), check_chip=False,
                            fault=fault)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(root, cell):
    r = _run(root, cell)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["metrics"]["ops_per_s"]["value"] > 0
    assert set(r["metrics"]) == {"ops_per_s", "setup_s"}
    assert list(r)[-1] == "checks"
    assert all(c["value"] == 0 for c in r["checks"].values())


@pytest.mark.parametrize("fault", faults.FAULTS)
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_caught(root, cell, fault):
    r = _run(root, cell, fault)
    assert not r["correct"], (fault, r["checks"])
