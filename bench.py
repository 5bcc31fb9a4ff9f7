"""Headline benchmark: approximate-delta sync bandwidth of the fused codec
path on one chip, in equivalent applied-fp32-delta GB/s per link.

Methodology (matches BASELINE.md's yardstick): the reference's 2-node
loopback E2E sync at n = 1 Mi elements moves 1.01 GB/s of equivalent fp32
deltas per link, and is codec-CPU-bound, not network-bound (SURVEY.md §6 —
the wire carries only 0.03 GB/s; one core saturates on the quantize/apply
loops, which is exactly the work reference README.md:47 wanted moved to an
accelerator kernel). This bench therefore times that bottleneck work on the
TPU: per frame, one full sender half (pow2-RMS scale + sign-quantize +
bit-pack + error feedback) plus one receiver half (unpack + apply) on an
n = 1 Mi buffer — the identical per-link per-frame math at
identical approximation error (the codec is bit-for-bit the reference
arithmetic; tests/test_codec*.py pin that). Frames are chained device-side
via lax.fori_loop into multi-second runs so per-dispatch cost is a small
bias that only understates the result; gaussian residuals keep a nonzero
scale throughout, so every frame does the full (non-idle) codec work.

One process per chip: this process NEVER imports jax itself (a parent that
has touched JAX holds the chip, and a child that needs it then fails or
hangs). Every measurement runs in a watchdogged subprocess with a hard
timeout, under a total wall-clock budget (ST_BENCH_BUDGET_S, default 420 s).
Arm ladder: chip + the scalar XLA codec (ops/codec.py; retried with backoff if
the backend does not come up; the scalar Pallas kernels this arm once timed
are gone, PR 31) -> CPU + native engine E2E (the host production data plane,
2-process loopback through the FULL stack, degraded-labeled) -> CPU + host
codec component loop (numpy/AVX-512-C, jax-free) -> CPU + XLA (last resort).
Exactly ONE JSON line is always printed, recording which arms ran and how
each ended (detail.attempts / detail.chip_state). ROADMAP S1 replaces the
ladder with a benchmark that fails without a chip.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time

N = 1 << 20  # 1 Mi elements — BASELINE.md's headline E2E config
BASELINE_GBPS = 1.01
BUDGET_S = float(os.environ.get("ST_BENCH_BUDGET_S", "420"))
CPU_RESERVE_S = 130.0  # budget held back for the CPU fallback arms
_T0 = time.monotonic()
_PRINTED = False
_ACTIVE_WORKER: "subprocess.Popen | None" = None


def _remaining() -> float:
    return BUDGET_S - (time.monotonic() - _T0)


def _kill_worker_tree(proc: "subprocess.Popen") -> None:
    """Kill a worker AND its whole process group (engine-arm grandchildren)."""
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except (OSError, ProcessLookupError):
        try:
            proc.kill()
        except OSError:
            pass


def _emit(result: dict) -> None:
    global _PRINTED
    if not _PRINTED:
        _PRINTED = True
        print(json.dumps(result), flush=True)


def _error_result(attempts, reason: str) -> dict:
    return {
        "metric": "sync_bandwidth_equiv_fp32_per_link",
        "value": 0.0,
        "unit": "GB/s",
        "vs_baseline": 0.0,
        "tier": "none",
        "detail": {"error": reason, "attempts": attempts},
    }


def _print_result(t_frame: float, backend: str, codec_name: str) -> None:
    """One schema for every worker arm (host and jax) — the supervisor and
    the round artifacts parse this."""
    fps = 1.0 / t_frame
    equiv_gbps = fps * N * 4 / 1e9
    print(
        json.dumps(
            {
                "metric": "sync_bandwidth_equiv_fp32_per_link",
                "value": round(equiv_gbps, 3),
                "unit": "GB/s",
                "vs_baseline": round(equiv_gbps / BASELINE_GBPS, 2),
                "detail": {
                    "n_elements": N,
                    "frames_per_s": round(fps, 1),
                    "backend": backend,
                    "codec": codec_name,
                    "wire_gbps": round(fps * (N / 8 + 4) / 1e9, 4),
                },
            }
        ),
        flush=True,
    )


# ---------------------------------------------------------------- worker ----


def _worker(codec_name: str) -> None:
    """Runs in a subprocess: init backend, announce it, measure, print JSON."""
    if codec_name == "engine":
        _worker_engine()
        return
    if codec_name == "host":
        # The host tier must NOT initialize a jax backend: the XLA CPU
        # client's thread pool contends with the C codec loops on a small
        # host (measured on this 1-vCPU box: 6.2 ms/frame with a live
        # backend vs 2.26 ms without — 2.7x).
        _worker_host()
        return

    import jax

    from shared_tensor_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    # Parent watches for this marker: it distinguishes "backend init hung or
    # failed" (retry chip with backoff / skip to CPU) from "backend fine but
    # the codec/measurement failed" (fall back to the XLA codec on-chip).
    # The third token says whether the backend is the tpu (the supervisor
    # itself must stay jax-free and cannot ask).
    backend = jax.default_backend()
    print(
        f"ST_BACKEND_UP {backend} {'tpu' if backend == 'tpu' else 'other'}",
        file=sys.stderr,
        flush=True,
    )

    from shared_tensor_tpu.ops import codec

    from shared_tensor_tpu.config import ScalePolicy
    from shared_tensor_tpu.utils.timing import codec_frame_time

    budget = float(os.environ.get("ST_TIMING_BUDGET_S", "120"))
    t_frame = codec_frame_time(
        codec, N, ScalePolicy.POW2_RMS, target_seconds=3.0, budget_s=budget
    )
    _print_result(t_frame, jax.default_backend(), codec_name)


def _worker_host() -> None:
    """The host production tier (ops/codec_np.py: numpy semantics over the
    AVX-512 C loops in native/stcodec.c) — synchronous host work, timed
    directly, NO jax backend (see _worker). This is what a CPU peer actually
    runs, and it beats the reference's 202 M elem/s loops ~5x per core
    (HOST_CODEC_r03.jsonl), so the no-chip fallback still clears the
    baseline."""
    import numpy as np

    from shared_tensor_tpu.config import ScalePolicy
    from shared_tensor_tpu.ops import codec_np
    from shared_tensor_tpu.ops.table import make_spec

    if codec_np._native() is None:
        raise RuntimeError("native libstcodec.so unavailable (no toolchain?)")
    print("ST_BACKEND_UP cpu other", file=sys.stderr, flush=True)
    spec = make_spec(np.zeros(N, np.float32))
    rng = np.random.default_rng(0)
    resid = rng.uniform(-1.0, 1.0, N).astype(np.float32)
    values = rng.uniform(-1.0, 1.0, N).astype(np.float32)

    def frame():  # one full link frame: sender half + receiver half
        scales, words, _ = codec_np.quantize_table_np(
            resid, spec, ScalePolicy.POW2_RMS
        )
        codec_np.apply_table_many_np((values,), scales, words, spec)

    for _ in range(3):
        frame()
    budget = float(os.environ.get("ST_TIMING_BUDGET_S", "120"))
    t0 = time.perf_counter()
    reps = 0
    while True:
        frame()
        reps += 1
        dt = time.perf_counter() - t0
        if dt >= min(3.0, budget) and reps >= 5:
            break
    _print_result(dt / reps, "cpu", "host")


def _worker_engine() -> None:
    """The host production data plane measured END TO END: the native engine
    (native/stengine.cpp) driving a 2-process loopback sync at n = 1 Mi
    through the full stack (quantize -> encode -> TCP -> decode -> flood
    apply -> ACK). This is the same methodology as the baseline's own 242
    f/s / 1.01 GB/s measurement (BASELINE.md E2E table, reference
    src/sharedtensor.c:113-189), so it is the most comparable no-chip
    number — and it clears the baseline ~4x (ENGINE_r04.json), vs ~2.9x for
    the bare codec component loop. Reported rate: the child's delivered
    frames_in/s on its one uplink (per-link, one direction — conservative,
    the link also carries the reverse stream)."""
    import multiprocessing as mp

    from shared_tensor_tpu.comm.engine import load_engine

    if load_engine() is None:
        # Cheap upfront probe (the host arm's codec_np._native() pattern):
        # without it a toolchain-less box burns ~13 s of spawn + measure
        # before discovering the run must be discarded.
        raise RuntimeError("native libstengine.so unavailable (no toolchain?)")

    sys.path.insert(
        0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmarks")
    )
    import engine_bench

    print("ST_BACKEND_UP cpu other", file=sys.stderr, flush=True)
    mp.set_start_method("spawn", force=True)
    row = engine_bench.run_size(N)
    if not (row.get("engine") and row.get("master_engine")):
        # Engine must attach on BOTH peers: a Python-tier rate on either end
        # (build race, partial toolchain failure in one spawn) must not
        # masquerade as the engine number; fall through to the host arm.
        raise RuntimeError(f"native engine did not attach on both peers: {row}")
    fps = row["frames_in_per_s"]
    if fps <= 0:
        raise RuntimeError(f"engine e2e measured no frames: {row}")
    _print_result(1.0 / fps, "cpu", "engine-e2e")


# ------------------------------------------------------------ supervisor ----


def _run_arm(platform: str | None, codec_name: str, timeout_s: float):
    """One watchdogged measurement subprocess.

    Returns (parsed_json_or_None, backend: (name, is_tpu) | None,
    outcome: str, stderr_tail: str). ``backend`` comes from the worker's
    ``ST_BACKEND_UP <name> <tpu|other>`` marker (None = backend never
    initialized). ``platform=None`` keeps the ambient JAX_PLATFORMS (the
    chip, where there is one); "cpu" forces the CPU fallback.
    """
    global _ACTIVE_WORKER
    env = dict(os.environ)
    if platform is not None:
        env["JAX_PLATFORMS"] = platform
    # Leave headroom inside the subprocess for backend init + the one compile.
    env["ST_TIMING_BUDGET_S"] = str(max(20.0, timeout_s - 90.0))
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--worker", codec_name],
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        text=True,
        cwd=os.path.dirname(os.path.abspath(__file__)),
        # Own process group: the engine arm forks multiprocessing children
        # (master/child peers); killing only the direct worker would leave
        # them streaming against the single vCPU while the NEXT arm measures
        # (the 2.7x-contention failure mode this file documents).
        start_new_session=True,
    )
    _ACTIVE_WORKER = proc  # so the SIGTERM handler can reap it (no orphans)
    try:
        stdout, stderr = proc.communicate(timeout=timeout_s)
        timed_out = False
    except subprocess.TimeoutExpired:
        _kill_worker_tree(proc)
        stdout, stderr = proc.communicate()
        stdout, stderr = stdout or "", stderr or ""
        timed_out = True
    finally:
        _ACTIVE_WORKER = None

    backend = None
    for line in stderr.splitlines():
        if line.startswith("ST_BACKEND_UP"):
            parts = line.split()
            backend = (
                parts[1] if len(parts) > 1 else "unknown",
                len(parts) > 2 and parts[2] == "tpu",
            )
            break
    backend_up = backend is not None
    parsed = None
    for line in reversed(stdout.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                parsed = json.loads(line)
                break
            except json.JSONDecodeError:
                continue
    if parsed is not None:
        outcome = "ok"
    elif timed_out:
        outcome = "timeout-backend-init" if not backend_up else "timeout-measuring"
    elif not backend_up:
        outcome = "backend-init-failed"
    else:
        outcome = "measurement-failed"
    return parsed, backend, outcome, stderr[-2000:]


def main() -> None:
    attempts: list[dict] = []
    best: dict | None = None
    chip_state = "not-tried"

    def note(platform, codec, outcome, err_tail=""):
        entry = {
            "platform": platform or "ambient",
            "codec": codec,
            "outcome": outcome,
        }
        if outcome != "ok" and err_tail:
            # Keep the root cause (Mosaic rejection, init error) in the
            # artifact — an outcome string alone is undebuggable.
            entry["stderr_tail"] = err_tail[-500:]
        attempts.append(entry)

    # On SIGTERM/SIGINT (driver timeout), still emit whatever we know — and
    # kill the in-flight worker first: an orphaned jax subprocess would keep
    # holding the chip against the NEXT run.
    def _sig(signum, frame):
        if _ACTIVE_WORKER is not None:
            _kill_worker_tree(_ACTIVE_WORKER)
        _emit(_error_result(attempts, f"signal {signum} before any arm finished"))
        os._exit(1)

    signal.signal(signal.SIGTERM, _sig)
    signal.signal(signal.SIGINT, _sig)

    # Phase A: the chip (ambient platform). Retry with backoff if the
    # backend does not come up (another process may hold the chip); never
    # burn the CPU reserve.
    def _tpu_like(backend) -> bool:
        return backend is not None and backend[1]

    tries = 0
    while best is None and tries < 3:
        budget_left = _remaining() - CPU_RESERVE_S
        if budget_left < 75:
            break
        parsed, backend, outcome, err = _run_arm(None, "xla", min(budget_left, 270.0))
        note(None, "xla", outcome, err)
        if _tpu_like(backend):
            chip_state = "up"
        elif chip_state == "not-tried":
            chip_state = "unavailable"
        if parsed is not None and _tpu_like(backend):
            best = parsed
            break
        if backend is not None:
            # The backend came up and the measurement failed (a retry would
            # fail alike), or the ambient backend resolved to CPU (no TPU
            # plugin registered at all): Phase B's ladder puts the
            # native-engine E2E first and XLA-CPU LAST, so an XLA-CPU rate
            # taken here must not short-circuit the ~6x-better engine arm.
            break
        tries += 1
        backoff = min(20.0 * tries, max(0.0, _remaining() - CPU_RESERVE_S - 75))
        if backoff > 0:
            time.sleep(backoff)

    # Phase B: CPU fallback — a degraded but real number beats no number.
    # Arm ladder: the native-engine E2E loopback first (the host production
    # data plane, methodology-matched to the baseline's own E2E probe, ~4x),
    # then the host codec component loop (numpy + AVX-512 C, ~2.9x), then
    # pure-XLA as the last resort. Each arm's timeout leaves a 20 s floor
    # for every arm still behind it (and the 15 s minimum stays below that
    # floor), so one hung fallback (e.g. engine port trouble) cannot starve
    # the simpler, more reliable ones — even under a reduced
    # ST_BENCH_BUDGET_S.
    cpu_arms = ("engine", "host", "xla")
    for i, cpu_codec in enumerate(cpu_arms):
        if best is not None or _remaining() <= 15:
            break
        arms_behind = len(cpu_arms) - 1 - i
        timeout_s = min(max(15.0, _remaining() - 10 - 20.0 * arms_behind), 100.0)
        parsed, _, outcome, err = _run_arm("cpu", cpu_codec, timeout_s)
        note("cpu", cpu_codec, outcome, err)
        if parsed is not None:
            best = parsed
            best["detail"]["degraded"] = "cpu-fallback (real chip unavailable)"

    if best is None:
        best = _error_result(attempts, "no arm completed within budget")
    best.setdefault("detail", {})
    best["detail"]["attempts"] = attempts
    best["detail"]["chip_state"] = chip_state
    # Top-level tier label (round-3 verdict Weak #1): round-over-round
    # comparisons must not silently cross tiers — a skim reader of
    # BENCH_r{N}.json sees at the top level whether this is the on-chip
    # number or a degraded host capture.
    best.setdefault(
        "tier", "host-fallback" if best["detail"].get("degraded") else "device"
    )
    _emit(best)


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--worker":
        _worker(sys.argv[2])
    else:
        try:
            main()
        except Exception as e:  # the one-JSON-line contract holds no matter what
            import traceback

            traceback.print_exc(file=sys.stderr)
            _emit(_error_result([], f"supervisor crashed: {type(e).__name__}: {e}"))
            sys.exit(1)
