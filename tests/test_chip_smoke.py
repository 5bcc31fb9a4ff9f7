"""chip_smoke.py cannot pass without a chip, and nothing on its path picks
another device or another cache directory quietly."""

import json
import os
import subprocess
import sys

import jax
import pytest

from shared_tensor_tpu.ops import codec_pallas
from shared_tensor_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_smoke(*args, **env):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args],
        capture_output=True, text=True, timeout=600, cwd=REPO,
        env=dict(os.environ, **env),
    )


def test_without_a_chip_it_fails_and_prints_no_result(tmp_path):
    proc = _run_smoke(
        JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path)
    )
    # 2 and 3 are the chip tool's own codes
    assert proc.returncode not in (0, 2, 3), proc.stderr[-2000:]
    assert "'cpu'" in proc.stderr and "'tpu'" in proc.stderr
    assert not [l for l in proc.stdout.splitlines() if l.startswith("{")]


def test_rehearsal_runs_every_phase_at_tiny_size(tmp_path):
    proc = _run_smoke(
        "--rehearse-cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path)
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["ok"] is True and result["rehearsal"] is True
    assert result["device"] == {"platform": "cpu", "kind": "cpu", "count": 4}
    assert "meshes run: [(4, 1), (2, 2)]" in proc.stdout
    assert "device tier:" in proc.stdout and "resnet on" in proc.stdout
    assert f"compile cache: {tmp_path}" in proc.stdout


def test_interpret_follows_the_backend_and_rejects_a_third(monkeypatch):
    for backend, want in (("tpu", False), ("cpu", True)):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert codec_pallas._interpret() is want
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        codec_pallas._interpret()
    monkeypatch.delenv("ST_CODEC", raising=False)
    assert codec_pallas.use_pallas() is False  # the XLA tier runs anywhere


def test_compile_cache_is_placed_from_outside_or_in_the_checkout(
    monkeypatch, tmp_path
):
    updates = []
    monkeypatch.setattr(
        jax.config, "update", lambda *kv: updates.append(kv)
    )
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert updates == []  # JAX reads the variable itself

    monkeypatch.delenv(compile_cache.ENV_VAR)
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert updates == [("jax_compilation_cache_dir", want)]
