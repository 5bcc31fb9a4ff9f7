"""The bench's no-chip fallback arm must always produce a valid, honest
number: it is what bench.py prints when no chip comes up (phase B). Runs
the worker directly (fast — no supervisor ladder, no chip attempts)."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bench_engine_worker_emits_valid_result():
    """Phase B's FIRST fallback arm: the native-engine 2-process loopback
    E2E (methodology-matched to the baseline's own E2E probe). It must
    attach the engine (a Python-tier rate must not masquerade as the engine
    number) and emit the standard schema."""
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    env["ST_ENGINE_BENCH_S"] = "3"  # shrink the measure window for CI speed
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--worker", "engine"],
        capture_output=True,
        text=True,
        timeout=180,
        cwd=REPO,
        env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "ST_BACKEND_UP cpu" in proc.stderr
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["metric"] == "sync_bandwidth_equiv_fp32_per_link"
    assert out["detail"]["codec"] == "engine-e2e"
    assert out["detail"]["backend"] == "cpu"
    # the engine E2E clears the baseline ~4x; require a generous fraction
    # even under parallel-suite load
    assert out["value"] > 0.4, out


def test_bench_host_worker_emits_valid_result():
    env = dict(os.environ, PYTHONPATH=REPO)
    env["ST_TIMING_BUDGET_S"] = "3"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--worker", "host"],
        capture_output=True,
        text=True,
        timeout=120,
        cwd=REPO,
        env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "ST_BACKEND_UP cpu" in proc.stderr
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["metric"] == "sync_bandwidth_equiv_fp32_per_link"
    assert out["detail"]["codec"] == "host"
    assert out["detail"]["backend"] == "cpu"
    # the host tier beats the reference codec ~5x per core; even a heavily
    # loaded run must clear a generous fraction of the baseline
    assert out["value"] > 0.2, out
