"""What every job kind of the benchmark shares: the run's context, the device
check, the compile counter, the host-clock statistics, the traced window and
the lookup of files by the names in BENCHMARK.json.

A job kind (``chipbench/jobs/<job>.py``) exposes ``run(ctx) -> dict`` and
returns ``correct``, ``checks``, ``attempted``, ``failed``, ``end_to_end``
(name -> value) and, in a traced run, ``observations`` for the per-layer
readers. run.py prints the one line.
"""

from __future__ import annotations

import dataclasses
import glob
import importlib.util
import json
import os
import shutil
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(Exception):
    """The run is not on the device the cell asks for."""


@dataclasses.dataclass
class Context:
    workload: str
    seed: int
    seconds: float
    trace: bool
    rehearsal: bool
    keep_trace: str | None
    t_start: float
    manifest: dict
    cell: dict
    config: dict
    peaks: dict | None = None
    setup_s: float | None = None
    compiles: "CompileCounter | None" = None

    def setup_done(self) -> None:
        """Loaded, checked and warm: everything before this is set-up."""
        self.setup_s = time.perf_counter() - self.t_start

    def sized(self, key: str, default=None):
        """A cell parameter, overridden by the cell's ``rehearsal`` group in
        a rehearsal."""
        if self.rehearsal and key in self.cell.get("rehearsal", {}):
            return self.cell["rehearsal"][key]
        return self.cell.get(key, default)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(manifest: dict, name: str) -> dict:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"chipbench: no workload {name!r} in BENCHMARK.json")


def config_file(manifest: dict, name: str) -> Path:
    for c in manifest["configs"]:
        if c["name"] == name:
            return ROOT / c["file"]
    raise SystemExit(f"chipbench: no configuration {name!r} in BENCHMARK.json")


def metrics_for(manifest: dict, group: str, cell: str) -> list[dict]:
    """The metrics of ``end_to_end`` or ``per_layer`` this cell reports."""
    return [
        m for m in manifest[group]
        if "workloads" not in m or cell in m["workloads"]
    ]


def load_by_path(kind: str, name: str):
    """``chipbench/<kind>/<name>.py`` as a module. Names may hold dots
    (``dispatch_ms.train``), so the file is found by path, not imported by
    name."""
    path = HERE / kind / f"{name}.py"
    if not path.is_file():
        raise SystemExit(f"chipbench: {path.relative_to(ROOT)} does not exist")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_').replace('-', '_')}", path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def kernel_patterns() -> list[str]:
    """Further names under which a trace shows the Mosaic kernels, one
    regular expression a line, from every ``chipbench/kernel_names/*.txt``:
    a PR that names the kernels adds a file."""
    pats = []
    for path in sorted(glob.glob(str(HERE / "kernel_names" / "*.txt"))):
        with open(path) as f:
            pats += [l.strip() for l in f if l.strip() and not l.startswith("#")]
    return pats


# --- the device --------------------------------------------------------------


def check_devices(ctx: Context) -> dict:
    """The device as JAX reports it; raises NoChip unless it is what the
    cell asks for. An unknown device kind is an error too: a share of a peak
    needs the peak."""
    import jax

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    want = "cpu" if ctx.rehearsal else "tpu"
    if device["platform"] != want:
        raise NoChip(
            f"jax.devices()[0].platform is {device['platform']!r}, the cell "
            f"needs {want!r}; there is no fallback (--rehearse-cpu is the "
            "tiny CPU rehearsal)"
        )
    if device["count"] < ctx.cell["chips"]:
        raise NoChip(
            f"the cell asks for {ctx.cell['chips']} chips, JAX finds "
            f"{device['count']}"
        )
    peaks = load_json(HERE / "peaks.json")
    if ctx.rehearsal:
        ctx.peaks = None
    elif device["kind"] not in peaks:
        raise NoChip(
            f"no published peak for device_kind {device['kind']!r} in "
            "chipbench/peaks.json; add it with its source"
        )
    else:
        ctx.peaks = peaks[device["kind"]]
    return device


def memory_peak_bytes(n_devices: int) -> int:
    """``peak_bytes_in_use`` on the fullest device used; 0 where the backend
    keeps no statistics (the CPU)."""
    import jax

    peak = 0
    for d in jax.devices()[:n_devices]:
        stats = d.memory_stats()
        if stats:
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class CompileCounter:
    """Counts backend compilations through JAX's own monitoring events, so
    "nothing compiled inside the window" is checked, not assumed."""

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, *_args, **_kw):
        if name == COMPILE_EVENT:
            self.count += 1


# --- host-clock statistics -----------------------------------------------------


def percentile(values: list[float], q: float) -> float:
    """Nearest-rank percentile (q in 0..100) of a non-empty list."""
    ordered = sorted(values)
    rank = max(1, -(-len(ordered) * q // 100))
    return ordered[int(rank) - 1]


def grouped_step_ms(completions: list[float], group_s: float) -> list[float]:
    """Per-step time in ms over consecutive groups of steps that each span at
    least ``group_s`` seconds. ``completions`` are host-clock times at which
    consecutive steps were seen complete; the first is the groups' origin.
    The host's clock is good to some half a millisecond, so a time is only
    read across 250 ms or more."""
    out = []
    start_i = 0
    for i in range(1, len(completions)):
        span = completions[i] - completions[start_i]
        if span >= group_s:
            out.append(1e3 * span / (i - start_i))
            start_i = i
    return out


# --- the traced window -----------------------------------------------------------


class TraceWindow:
    """``with TraceWindow(ctx) as tw:`` runs the profiler around the body and
    opens the ``chipbench:window`` annotation; afterwards ``tw.trace`` is the
    plain form trace_reduce.load_xplane gives. The profiler's files go to a
    temporary directory (under ``TMPDIR``) and are removed, unless
    ``--keep-trace DIR`` asks for them."""

    def __init__(self, ctx: Context):
        self.ctx = ctx
        self.trace = None
        self.path = None

    def __enter__(self):
        import jax

        if self.ctx.keep_trace:
            self.dir = os.path.join(self.ctx.keep_trace, self.ctx.workload)
            shutil.rmtree(self.dir, ignore_errors=True)
            os.makedirs(self.dir, exist_ok=True)
        else:
            self.dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        jax.profiler.start_trace(self.dir)
        self._ann = jax.profiler.TraceAnnotation("chipbench:window")
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        import jax

        from . import trace_reduce

        self._ann.__exit__(*exc)
        jax.profiler.stop_trace()
        try:
            if exc[0] is None:
                found = glob.glob(
                    os.path.join(self.dir, "plugins", "profile", "*", "*.xplane.pb")
                )
                if not found:
                    raise RuntimeError(f"the profiler wrote no trace under {self.dir}")
                self.path = sorted(found)[-1]
                self.trace = trace_reduce.load_xplane(
                    self.path, rehearsal=self.ctx.rehearsal
                )
                if self.ctx.keep_trace:
                    with open(os.path.join(self.dir, "describe.json"), "w") as f:
                        json.dump(trace_reduce.describe(self.path), f, indent=1)
                    with open(os.path.join(self.dir, "plain.json"), "w") as f:
                        json.dump(self.trace, f)
        finally:
            if not self.ctx.keep_trace:
                shutil.rmtree(self.dir, ignore_errors=True)
        return False


def annotate(name: str):
    """A host span on the profiler's clock: what the loop is doing, for the
    attribution of idle gaps."""
    import jax

    return jax.profiler.TraceAnnotation("chipbench:" + name)


class no_span:
    """The untraced loop's stand-in for :func:`annotate`."""

    def __init__(self, _name: str):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False
