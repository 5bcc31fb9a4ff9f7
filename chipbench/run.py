"""The chip benchmark's one command.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: finds the cell's entry in ``BENCHMARK.json``, its file under
``chipbench/workloads/``, its configuration's file and its job kind under
``chipbench/jobs/``; loads, checks and warms that cell's shapes (set-up),
measures for ``--seconds``, and prints one JSON object as the last line of
its standard output. ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics, each read by its own file under
``chipbench/layer_metrics/``. On anything but a TPU with the chips the cell
asks for it exits nonzero and prints no result. ``--rehearse-cpu`` is the one
way to run without a chip: the rehearsal sizes of the cell's files, four
virtual CPU devices, Pallas interpreted; its line says ``"rehearsal": true``
and its numbers are no device's.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument(
        "--rehearse-cpu", action="store_true",
        help="rehearsal sizes on four virtual CPU devices, Pallas "
        "interpreted; never chosen by the program itself",
    )
    ap.add_argument(
        "--keep-trace", default=None, metavar="DIR",
        help="keep the profiler's files, their description and the plain "
        "form under DIR/<workload>/ (traced runs)",
    )
    args = ap.parse_args(argv)
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        count = "--xla_force_host_platform_device_count"
        flags = re.sub(rf"{count}=\d+", "", os.environ.get("XLA_FLAGS", ""))
        os.environ["XLA_FLAGS"] = f"{flags} {count}=4".strip()
        os.environ["ST_CODEC"] = "pallas"  # the kernels, interpreted
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    sys.path.insert(0, str(ROOT))
    from chipbench import harness

    manifest = harness.load_json(ROOT / "BENCHMARK.json")
    entry = harness.find_cell(manifest, args.workload)
    cell = harness.load_json(ROOT / "chipbench" / "workloads" / f"{args.workload}.json")
    for key in ("config", "traffic", "chips"):
        if cell[key] != entry[key]:
            raise SystemExit(
                f"chipbench: {args.workload}: {key} is {cell[key]!r} in the "
                f"cell's file and {entry[key]!r} in BENCHMARK.json"
            )
    config = harness.load_json(harness.config_file(manifest, cell["config"]))
    ctx = harness.Context(
        workload=args.workload, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), rehearsal=args.rehearse_cpu,
        keep_trace=args.keep_trace, t_start=_T_START, manifest=manifest,
        cell=cell, config=config,
    )

    import jax

    from shared_tensor_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    # every program of a cell is found in the cache after its first run, the
    # small ones too (JAX's default keeps only compilations of a second up)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        device = harness.check_devices(ctx)
    except harness.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 1
    ctx.compiles = harness.CompileCounter()
    print(f"[chipbench] {args.workload} seed={args.seed} seconds={args.seconds} "
          f"trace={args.trace} on {device} cache={cache_dir}", flush=True)

    job = harness.load_by_path("jobs", cell["job"])
    out = job.run(ctx)

    metrics = {}
    summary = None
    if ctx.trace:
        obs = out["observations"]
        for m in harness.metrics_for(manifest, "per_layer", args.workload):
            value = harness.load_by_path("layer_metrics", m["name"]).read(obs)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        summary = obs.get("trace")
        if summary:
            device["busy_s"] = summary["busy_s"]
            device["window_s"] = summary["window_s"]
    else:
        values = dict(out["end_to_end"], setup_s=ctx.setup_s)
        for m in harness.metrics_for(manifest, "end_to_end", args.workload):
            metrics[m["name"]] = {"value": float(values[m["name"]]), "unit": m["unit"]}
    device["memory_peak_bytes"] = harness.memory_peak_bytes(cell["chips"])
    line = {
        "correct": bool(out["correct"]),
        "attempted": int(out["attempted"]),
        "failed": int(out["failed"]),
        "metrics": metrics,
        "device": device,
        "checks": out["checks"],
        "setup_s": ctx.setup_s,
        "seed": args.seed,
        "memory_stats": jax.devices()[0].memory_stats(),
    }
    if summary:
        line["breakdown"] = {
            "device_ops": summary["device_ops"], "idle_gaps": summary["idle_gaps"],
        }
    if ctx.rehearsal:
        line["rehearsal"] = True
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
