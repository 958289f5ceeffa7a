"""Without a TPU, and in a directory that holds only the benchmark, a run
exits non-zero before any work and prints no result."""
import shutil
import subprocess
import sys

import pytest

from bench_tiny import REPO
from bench import chip

ARGS = ["--workload", "sw512_micro_fig14", "--seed", "3", "--seconds", "1",
        "--trace", "0"]


def test_require_tpu_refuses_the_cpu():
    with pytest.raises(SystemExit) as e:
        chip.require_tpu(1)
    assert "no TPU" in str(e.value.code)


@pytest.mark.parametrize("alone", (False, True))
def test_run_exits_nonzero_without_a_result(alone, tmp_path):
    root = REPO
    if alone:
        root = tmp_path / "checkout"
        shutil.copytree(REPO / "bench", root / "bench",
                        ignore=shutil.ignore_patterns("__pycache__"))
        shutil.copy(REPO / "BENCHMARK.json", root)
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
           "HOME": str(tmp_path), "TMPDIR": str(tmp_path)}
    out = subprocess.run([sys.executable, str(root / "bench/run.py"), *ARGS],
                         env=env, capture_output=True, text=True,
                         timeout=300, cwd=str(root))
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    if not alone:
        assert "no TPU" in out.stderr


def test_compile_log_tells_cache_loads_from_compiles(tmp_path):
    """A program compiled once and then loaded from the persistent cache
    counts as a compile the first time and as a load the second."""
    import jax
    import jax.numpy as jnp
    chip.use_compile_cache(tmp_path)
    x = jnp.arange(7.0)
    with chip.compile_log() as first:
        jax.jit(lambda v: v * 3.0 + 1.0)(x).block_until_ready()
    jax.clear_caches()
    with chip.compile_log() as second:
        jax.jit(lambda v: v * 3.0 + 1.0)(x).block_until_ready()
    assert first == {"count": 1, "loads": 0}
    assert second == {"count": 0, "loads": 1}
