"""chipbench/run.py end to end on the CPU: both job kinds at their rehearsal
sizes (four virtual devices, kernels interpreted), traced and untraced, and
the ways it must refuse to run. A rehearsal's numbers are no device's; what
is checked is the line's shape and that every check of ``correct`` holds."""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)


def _run(*args, cwd=ROOT, script=None, **env):
    return subprocess.run(
        [sys.executable, script or os.path.join(ROOT, "chipbench", "run.py"), *args],
        capture_output=True, text=True, timeout=900, cwd=cwd,
        env=dict(os.environ, **env),
    )


def _names(group, cell):
    return {m["name"] for m in MANIFEST[group]
            if "workloads" not in m or cell in m["workloads"]}


def _result_lines(stdout):
    return [l for l in stdout.splitlines() if l.startswith("{")]


@pytest.mark.parametrize("cell,trace", [
    ("resnet18_b1024", 0), ("resnet18_b1024", 1),
    ("olmoe_stream_1c", 0), ("olmoe_stream_4c", 0), ("olmoe_stream_4c", 1),
])
def test_rehearsal_prints_the_contracts_line(cell, trace, tmp_path):
    proc = _run(
        "--workload", cell, "--seed", str(2**31 + 4242), "--seconds", "1",
        "--trace", str(trace), "--rehearse-cpu",
        JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"), TMPDIR=str(tmp_path),
        BENCH_RUN="ignored",
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["rehearsal"] is True and line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    device = line["device"]
    assert (device["platform"], device["kind"], device["count"]) == ("cpu", "cpu", 4)
    assert "memory_peak_bytes" in device
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and isinstance(m["value"], float)
    if trace:
        assert set(line["metrics"]) <= _names("per_layer", cell) and line["metrics"]
        assert device["busy_s"] > 0 and device["window_s"] >= device["busy_s"]
        for key in ("device_ops", "idle_gaps"):
            assert len(line["breakdown"][key]) <= 10
    else:
        assert set(line["metrics"]) == _names("end_to_end", cell)
        assert all(m["value"] > 0 for m in line["metrics"].values())
    # the profiler's files are gone and nothing was left in TMPDIR
    assert not [p for p in os.listdir(tmp_path) if p.startswith("chipbench_trace_")]


def test_without_a_chip_and_without_the_flag_it_fails_and_prints_no_result(tmp_path):
    proc = _run(
        "--workload", "olmoe_stream_1c", "--seed", "1", "--seconds", "1",
        "--trace", "0", JAX_PLATFORMS="cpu",
        JAX_COMPILATION_CACHE_DIR=str(tmp_path),
    )
    assert proc.returncode not in (0, 2, 3), proc.stderr[-2000:]  # 2, 3: the chip tool's
    assert "'cpu'" in proc.stderr and "'tpu'" in proc.stderr
    assert not _result_lines(proc.stdout)


def test_an_unknown_cell_fails_and_prints_no_result(tmp_path):
    proc = _run("--workload", "no_such_cell", "--seed", "1", "--seconds", "1",
                "--trace", "0", "--rehearse-cpu",
                JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert proc.returncode != 0 and not _result_lines(proc.stdout)
    assert "no_such_cell" in proc.stderr


def test_without_the_program_it_fails_and_prints_no_result(tmp_path):
    """A directory that holds only BENCHMARK.json and the files under
    ``paths``: the benchmark brings the yardstick, never the system."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    ignore = shutil.ignore_patterns("__pycache__")
    for p in MANIFEST["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p, ignore=ignore)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, str(tmp_path / "chipbench" / "run.py"), "--workload",
         "olmoe_stream_1c", "--seed", "1", "--seconds", "1", "--trace", "0",
         "--rehearse-cpu"],
        capture_output=True, text=True, timeout=300, cwd=tmp_path,
        env=dict(env, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache")),
    )
    assert proc.returncode != 0 and not _result_lines(proc.stdout)
    assert "shared_tensor_tpu" in proc.stderr
