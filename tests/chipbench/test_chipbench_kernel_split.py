"""The per-kernel readers PR 27 added (``quantize_ms_per_step.sync`` and
``apply_ms_per_step.sync``): on the chip traces recorded with the kernels
named they give the recorded figures, on the older recordings (kernels
labelled after the jitted function) and in a CPU rehearsal they find nothing
and say so with ``None``."""

import glob
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import harness, trace_reduce as tr  # noqa: E402

TESTDATA = os.path.join(ROOT, "chipbench", "testdata")
NAMED = sorted(glob.glob(os.path.join(TESTDATA, "trace_*_named.json")))
UNNAMED = sorted(set(glob.glob(os.path.join(TESTDATA, "trace_*.json"))) - set(NAMED))
READERS = ("quantize_ms_per_step.sync", "apply_ms_per_step.sync")
KERNELS = {"quantize_ms_per_step.sync": "st_quantize_rows",
           "apply_ms_per_step.sync": "st_apply_rows_batch"}
#: ms per step in the recordings (my chip runs, PR 27; two steps cut from the
#: middle of each traced window).
RECORDED = {
    "olmoe_stream_1c_named": {"quantize_ms_per_step.sync": 12.583089,
                              "apply_ms_per_step.sync": 12.1166485},
    "olmoe_stream_4c_named": {"quantize_ms_per_step.sync": 12.542553124999998,
                              "apply_ms_per_step.sync": 17.616865125},
}


def _read(name, obs):
    return harness.load_by_path("layer_metrics", name).read(obs)


def _summary(path):
    with open(path) as f:
        rec = json.load(f)
    return rec, tr.reduce(rec["trace"], rec["steps"], harness.kernel_patterns())


def test_the_named_recordings_are_there():
    assert {os.path.basename(p) for p in NAMED} >= {
        f"trace_{cell}.json" for cell in RECORDED
    }


@pytest.mark.parametrize("name", READERS)
@pytest.mark.parametrize("cell", sorted(RECORDED))
def test_reader_gives_the_recorded_figure(cell, name):
    rec, summary = _summary(os.path.join(TESTDATA, f"trace_{cell}.json"))
    got = _read(name, {"trace": summary})
    assert got == pytest.approx(RECORDED[cell][name], rel=1e-6)
    # the same from the recording's events, without the reduction: whole
    # events that touch the window, per device and step
    lo, hi = tr.window_of(rec["trace"])
    total = sum(
        d for events in rec["trace"]["devices"].values() for label, s, d, _ in events
        if label.startswith(KERNELS[name] + ".") and s + d > lo and s < hi
    )
    assert got == pytest.approx(total / 1e6 / len(rec["trace"]["devices"]) / rec["steps"])


@pytest.mark.parametrize("cell", sorted(RECORDED))
def test_the_two_kernels_add_up_to_the_kernel_time(cell):
    """Both readers count whole events, the accepted ``kernel_ms_per_step.sync``
    the part inside the window: over the 24 steps of a traced run they agree
    to 2 % (PERF.md), over a two-step cut to an event's length."""
    _, summary = _summary(os.path.join(TESTDATA, f"trace_{cell}.json"))
    obs = {"trace": summary}
    both = sum(_read(name, obs) for name in READERS)
    assert both == pytest.approx(_read("kernel_ms_per_step.sync", obs), rel=0.02)


@pytest.mark.parametrize("path", UNNAMED, ids=os.path.basename)
def test_nothing_to_read_where_the_kernels_carry_no_name(path):
    _, summary = _summary(path)
    assert summary["kernel_s"] > 0  # the kernels ran; only their labels differ
    for name in READERS:
        assert _read(name, {"trace": summary}) is None


@pytest.mark.parametrize("obs", [
    {}, {"trace": None}, {"trace": {"steps": 0, "device_ops": []}},
    {"trace": {"steps": 4, "device_ops": [["fusion.3 = f32[201] fusion", 1.0]]}},
    {"trace": {"steps": 4}},
], ids=["empty", "no_trace", "no_steps", "not_among_the_longest", "no_list"])
def test_readers_return_none_and_do_not_raise(obs):
    for name in READERS:
        assert _read(name, obs) is None


def test_reader_matches_the_kernel_label_only():
    ops = [
        ["st_quantize_rows.1 = (u32[8,4], f32[8,128]) custom-call", 0.25],
        ["st_quantize_rows.7 = (u32[8,4], f32[8,128]) custom-call", 0.75],
        ["st_apply_rows_batch = f32[8,128] custom-call", 0.5],
        ["st_quantize_rows_fusion = f32[8] fusion", 9.0],
    ]
    obs = {"trace": {"steps": 4, "device_ops": ops}}
    assert _read("quantize_ms_per_step.sync", obs) == pytest.approx(250.0)
    assert _read("apply_ms_per_step.sync", obs) == pytest.approx(125.0)


def test_rehearsal_line_with_the_new_entries(tmp_path):
    """A traced CPU rehearsal of the one-chip table cell: the kernels are
    interpreted there and leave no custom call, so the line holds the
    accepted metrics and leaves the two new ones out."""
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", "olmoe_stream_1c", "--seed", str(2**31 + 2727),
         "--seconds", "1", "--trace", "1", "--rehearse-cpu"],
        capture_output=True, text=True, timeout=900, cwd=ROOT,
        env=dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
                 TMPDIR=str(tmp_path)),
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["rehearsal"] is True
    assert "kernel_ms_per_step.sync" in line["metrics"]
    assert not set(READERS) & set(line["metrics"])
