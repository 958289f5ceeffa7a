"""chip_smoke.py's phases at a tiny size on the CPU.

The script itself refuses to run without a TPU; these tests drive its phase
functions directly (JAX_PLATFORMS=cpu): the fleet session and its checks,
the op-for-op oracle comparison (and that it catches a wrong pointer), and
the rank-sharded phase on four virtual CPU devices in a subprocess.
"""
import dataclasses
import os
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.configs.paper_upmem import CONFIG as PAPER
from repro.core import system as sysm
from repro.core.heap import OP_MALLOC
from repro.launch.serve_fleet import FleetServe, TrafficConfig
from repro.launch.serving import response_host

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import chip_smoke as cs  # noqa: E402

TRAFFIC = TrafficConfig(seed=0, rounds=24, num_tenants=32, arrival_rate=24,
                        queue_cap=96)
R, C = 4, 3


def _cfg(kind="hwsw"):
    return sysm.SystemConfig(kind=kind, heap_bytes=1 << 20, num_threads=4)


def test_device_check_refuses_cpu():
    with pytest.raises(SystemExit) as e:
        cs.require_tpu()
    assert "no TPU" in str(e.value.code)


@pytest.mark.parametrize("alone", (False, True))
def test_script_exits_nonzero_without_result(alone, tmp_path):
    """On a CPU, and copied into a directory without the repo, the script
    exits non-zero and prints no result line."""
    script = os.path.join(ROOT, "chip_smoke.py")
    if alone:
        script = shutil.copy(script, tmp_path / "chip_smoke.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = subprocess.run([sys.executable, str(script)], env=env,
                         capture_output=True, text=True, timeout=300,
                         cwd=str(tmp_path))
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_paper_config_is_table_3():
    """chip_smoke builds its configs from SystemConfig's defaults, which
    must be the paper's Table 3."""
    cfg = sysm.SystemConfig(kind="hwsw", heap_bytes=PAPER.heap_bytes,
                            num_threads=PAPER.num_threads)
    assert cfg.heap_bytes == 32 << 20 and cfg.num_threads == 16
    assert cfg.pm.size_classes == PAPER.size_classes
    assert cfg.pm.block_bytes == PAPER.block_bytes == 4096
    assert cfg.straw.min_block == PAPER.min_block
    assert cfg.bc.n_entries * 4 == PAPER.buddy_cache_bytes
    assert cfg.dpu.freq_hz == PAPER.freq_hz
    assert cs.RANKS * cs.CORES == PAPER.n_cores == 512


def test_main_phase_agrees_with_oracle(capsys):
    rep = cs.main_phase(_cfg(), R, C, TRAFFIC, jax.devices()[0])
    out = capsys.readouterr().out
    assert rep["dropped_frees"] == 0 and rep["conservation_residual"] == 0
    assert "PyPimMalloc oracle agrees op for op" in out
    assert len(cs.oracle_cores(R, C)) == 4


@pytest.mark.parametrize("kind", cs.OTHER_KINDS)
def test_kind_phase(kind):
    short = dataclasses.replace(TRAFFIC, rounds=8)
    rep = cs.kind_phase(_cfg(kind), R, C, short, jax.devices()[0])
    assert rep["dispatched"] > 0


def test_oracle_check_catches_a_wrong_pointer():
    engine = FleetServe(_cfg(), R, C, traffic=TRAFFIC)
    plan = engine.plan()
    state, resps, _ = cs.serve(engine, plan)
    host = {f: np.array(v) for f, v in response_host(resps).items()}
    assert cs.check_oracle(engine, plan, host, 0, 0) > 0
    r, t = np.argwhere(plan.op[:, 0, 0] == OP_MALLOC)[0]
    host["ptr"][r, 0, 0, t] += 16
    with pytest.raises(cs.SmokeFailure, match="field ptr"):
        cs.check_oracle(engine, plan, host, 0, 0)


SHARDED = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, os.getcwd())
import chip_smoke as cs
from repro.core import system as sysm
from repro.launch.serve_fleet import TrafficConfig
tr = TrafficConfig(seed=0, rounds=16, num_tenants=32, arrival_rate=24,
                   queue_cap=96)
cs.sharded_phase(sysm.SystemConfig(kind="hwsw", heap_bytes=1 << 20,
                                   num_threads=4), 8, 3, tr)
"""


def test_sharded_phase_on_four_virtual_devices():
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", SHARDED], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "equal to mesh=False" in out.stdout
    assert out.stdout.count("holds 818952 of 3275808 state bytes") == 4
