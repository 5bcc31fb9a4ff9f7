"""Smoke test for benchmarks/train_bench.py: one parseable JSON line with
every arm, and a nonzero exit when an arm did not produce a number."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_train_bench_emits_all_arms():
    env = dict(os.environ)
    env["ST_TRAIN_BENCH_BUDGET_S"] = "120"
    env.pop("JAX_PLATFORMS", None)
    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(REPO, "benchmarks", "train_bench.py"),
            "--platform", "cpu", "--peers", "2", "--tiny",
            "--batch", "2", "--seq", "32",
        ],
        capture_output=True,
        text=True,
        timeout=180,
        cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = proc.stdout.strip().splitlines()[-1]
    out = json.loads(line)
    assert out["metric"] == "train_step_bench"
    assert set(out["arms"]) == {
        "sync_off", "compressed", "compressed_overlap", "exact"
    }
    for name, arm in out["arms"].items():
        assert "error" not in arm, (name, arm)
        assert arm["tokens_per_s"] > 0
    assert out["arms"]["compressed"].get("sync_overhead_pct") is not None


def test_train_bench_exits_nonzero_when_an_arm_fails():
    """No budget left is the arm failure a test can arrange: every arm is
    recorded as an error, the JSON line still appears, and the exit code
    says the run is not a result."""
    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(REPO, "benchmarks", "train_bench.py"),
            "--platform", "cpu", "--peers", "2", "--tiny",
            "--batch", "2", "--seq", "32",
        ],
        capture_output=True,
        text=True,
        timeout=180,
        cwd=REPO,
        env=dict(os.environ, ST_TRAIN_BENCH_BUDGET_S="0"),
    )
    assert proc.returncode != 0
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert all("error" in arm for arm in out["arms"].values()), out
