"""The CI workflows are config-as-code: pin their syntax and the invariants
this repo's lanes rely on (the bench-smoke gate, nightly dispatchability,
pip caching) so a stray YAML edit fails tier-1 instead of the first push."""
import os

import pytest

yaml = pytest.importorskip("yaml")

ROOT = os.path.join(os.path.dirname(__file__), os.pardir)
WF = os.path.join(ROOT, ".github", "workflows")


def _load(name):
    with open(os.path.join(WF, name)) as f:
        return yaml.safe_load(f)


def _steps(job):
    return job.get("steps", [])


def _run_text(job):
    return "\n".join(s.get("run", "") for s in _steps(job))


def test_ci_workflow_is_valid_yaml_with_expected_jobs():
    doc = _load("ci.yml")
    assert set(doc["jobs"]) >= {"lint", "analysis", "tier1", "bench-smoke"}


def test_tier1_matrix_has_decode_smoke_lane():
    """Acceptance: the decode serving tier rides tier-1 — the suite plus
    the example CLI as a closed-loop smoke."""
    job = _load("ci.yml")["jobs"]["tier1"]
    lanes = {e["suite"]: e["run"]
             for e in job["strategy"]["matrix"]["include"]}
    assert "decode-smoke" in lanes
    assert "tests/test_serve_decode.py" in lanes["decode-smoke"]
    assert "examples/serve_decode.py --smoke" in lanes["decode-smoke"]


def test_tier1_matrix_has_chaos_smoke_lane():
    """Acceptance: the elastic-fleet chaos harness (fault injection,
    heap-pressure migration, snapshot/restore) and the checkpoint
    substrate suite ride tier-1 with a bounded seed sweep."""
    job = _load("ci.yml")["jobs"]["tier1"]
    lanes = {e["suite"]: e["run"]
             for e in job["strategy"]["matrix"]["include"]}
    assert "chaos-smoke" in lanes
    assert "tests/test_elastic_fleet.py" in lanes["chaos-smoke"]
    assert "tests/test_checkpoint.py" in lanes["chaos-smoke"]
    assert "CHAOS_SEEDS=" in lanes["chaos-smoke"]


def test_tier1_fuzz_smoke_lane_runs_kind_conformance():
    """Acceptance: the registry-generic conformance suite (which enrolls
    arena/tlregion in conservation, C-edges and digest-stability) rides
    the fuzz-smoke lane on every PR."""
    job = _load("ci.yml")["jobs"]["tier1"]
    lanes = {e["suite"]: e["run"]
             for e in job["strategy"]["matrix"]["include"]}
    assert "tests/test_kind_conformance.py" in lanes["fuzz-smoke"]


def test_analysis_lane_has_region_frontend_pimcheck_cell():
    """Acceptance: arena+tlregion are pimcheck-traced at every deployment
    tier as an explicit CI cell (and with zero suppressions — the
    SUPPRESSIONS list ships empty, pinned by tests/test_analysis.py)."""
    text = _run_text(_load("ci.yml")["jobs"]["analysis"])
    assert "--kinds arena,tlregion" in text
    assert "--tiers single,vmap,sharded" in text


def test_bench_smoke_job_runs_the_modeled_gate():
    """Acceptance: bench-smoke writes the schema'd smoke JSON, fails on
    /ERROR rows, gates it with --lane modeled against the committed
    BENCH_BASELINE.json, and uploads the JSON as the run's artifact."""
    job = _load("ci.yml")["jobs"]["bench-smoke"]
    text = _run_text(job)
    assert "benchmarks.run --smoke" in text and "bench_smoke.json" in text
    assert '"/ERROR"' in text
    gate = [line for line in text.splitlines() if "perf_gate.py" in line]
    assert gate and "bench_smoke.json" in gate[0]
    assert "--baseline BENCH_BASELINE.json --lane modeled" in text
    upload = [s for s in _steps(job)
              if "upload-artifact" in str(s.get("uses", ""))]
    assert upload and "bench_smoke.json" in upload[0]["with"]["path"]


def test_nightly_workflow_scheduled_and_dispatchable():
    """The nightly lane must be cron-scheduled AND workflow_dispatch-able
    (the acceptance path for syntax validation), run the non-smoke sweep,
    and raise the fuzzer budget above the PR smoke lane's 15."""
    doc = _load("nightly.yml")
    trig = doc.get("on") or doc.get(True)  # yaml 1.1 parses bare `on:` as True
    assert "schedule" in trig and "workflow_dispatch" in trig
    jobs = doc["jobs"]
    bench = _run_text(jobs["bench-full"])
    assert "benchmarks.run" in bench and "--smoke" not in bench
    fuzz = _run_text(jobs["fuzz-deep"])
    assert "FUZZ_MAX_EXAMPLES=" in fuzz
    budget = int(fuzz.split("FUZZ_MAX_EXAMPLES=")[1].split()[0])
    assert budget > 15


def test_nightly_chaos_sweep_deepens_the_smoke_lane():
    """The nightly chaos sweep must rerun the elastic harness with a
    strictly wider seed sweep than the per-PR chaos-smoke lane."""
    tier1 = _load("ci.yml")["jobs"]["tier1"]
    lanes = {e["suite"]: e["run"]
             for e in tier1["strategy"]["matrix"]["include"]}
    smoke = int(lanes["chaos-smoke"].split("CHAOS_SEEDS=")[1].split()[0])
    sweep_text = _run_text(_load("nightly.yml")["jobs"]["chaos-sweep"])
    assert "tests/test_elastic_fleet.py" in sweep_text
    deep = int(sweep_text.split("CHAOS_SEEDS=")[1].split()[0])
    assert deep > smoke


def test_all_setup_python_steps_cache_pip():
    """Every job in every workflow must enable actions/setup-python pip
    caching — cold dependency installs dominate lane latency."""
    for wf in ("ci.yml", "nightly.yml"):
        for jname, job in _load(wf)["jobs"].items():
            for s in _steps(job):
                if "setup-python" in str(s.get("uses", "")):
                    cfg = s.get("with", {})
                    assert cfg.get("cache") == "pip", f"{wf}:{jname}"
                    assert cfg.get("cache-dependency-path"), f"{wf}:{jname}"


def test_pytest_timeout_session_default_configured():
    """pyproject pins a session-wide pytest-timeout default and the plugin
    is in requirements.txt, so CI hangs fail fast."""
    with open(os.path.join(ROOT, "pyproject.toml")) as f:
        py = f.read()
    assert "timeout = " in py.split("[tool.pytest.ini_options]")[1]
    with open(os.path.join(ROOT, "requirements.txt")) as f:
        assert "pytest-timeout" in f.read()
