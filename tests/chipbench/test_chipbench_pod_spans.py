"""chipbench/pod_spans.py and its four readers: the untraced arm picked out
of the program's own step log, each reader's arithmetic on a made-up log,
``None`` where there is nothing to read, and the rehearsal's line."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import harness, pod_spans  # noqa: E402

MS = 1_000_000
READERS = {
    "step_call_ms.train": 2.6,  # (1.0 + 1.2 + 0.8 + 9.0 + 1.0) / 5
    "step_call_max_ms.train": 9.0,
    "step_gap_max_over_median.train": 3.5,  # gaps 0.1, 100, 100, 350 ms
    "gc_pause_max_ms.train": 7.0,  # of 7 and 2 inside; 50 before and 80 after
}
OBS = {"job": "train", "trace": {"steps": 2}, "host": {"dispatch_calls": 5, "dispatch_s": 0.014}}


@pytest.fixture
def log():
    """The process's recorder, emptied, and a function that writes one span
    into it: ``span(name, start_ms, ms, **attrs)``."""
    from shared_tensor_tpu import obs

    hub = obs.hub()
    was = obs.obs_enabled()
    obs.set_enabled(True)
    hub.recorder.clear()

    def span(name, start_ms, ms, step=0, **attrs):
        t1 = int((start_ms + ms) * MS)
        hub.emit(name, arg=int(ms * MS), extra=step, t_ns=t1,
                 detail=" ".join(f"{k}={v}" for k, v in attrs.items()))

    yield span
    hub.recorder.clear()
    obs.set_enabled(was)


def _a_traced_run(span):
    """What ``jobs/train.py --trace 1`` leaves: set-up steps, the arm, the
    ``sync=False`` arm, the traced window; collections before, inside and
    after the arm. Returns the arm's (start, duration) pairs in ms."""
    t = 1000.0
    span("st:trainer_init", t, 300.0)
    span("st:gc", t + 10, 50.0, parent="st:trainer_init", generation=2)
    t += 400
    for i in range(3):  # the checks' steps, then the warm-up
        span("st:train.step", t, 2.0, step=i, program="sync")
        t += 110
    arm = []
    for i, (ms, gap) in enumerate([(1.0, 0.1), (1.2, 100.0), (0.8, 100.0), (9.0, 350.0), (1.0, 200.0)]):
        span("st:train.step", t, ms, step=3 + i, program="sync")
        arm.append((t, ms))
        t += ms + gap
    span("st:gc", arm[3][0] + 1, 7.0, step=6, parent="st:train.step", generation=1)
    span("st:gc", arm[1][0] + 20, 2.0, step=4, generation=0)
    for i in range(4):  # PodTrainer(sync=False): the same entry point
        span("st:train.step", t, 0.5, step=i, program="local")
        t += 90
    span("st:gc", t - 200, 80.0, generation=2)
    for i in range(2):  # the traced window
        span("st:train.step", t, 3.0, step=8 + i, program="sync")
        t += 110
    return arm


def test_arm_steps_picks_the_untraced_arm(log):
    arm = _a_traced_run(log)
    got = pod_spans.arm_steps(OBS)
    assert [(r.t0_ns / MS, (r.t1_ns - r.t0_ns) / MS) for r in got] == arm
    assert [r.step for r in got] == [3, 4, 5, 6, 7]
    assert all(r.attrs == {"program": "sync"} for r in got)
    assert [g / MS for g in pod_spans.gaps_ns(got)] == pytest.approx([0.1, 100.0, 100.0, 350.0])
    assert sorted(p / MS for p in pod_spans.pauses_ns(got)) == [2.0, 7.0]
    # a log the recorder rolled past the arm's start: what is left of it
    assert len(pod_spans.arm_steps(dict(OBS, host={"dispatch_calls": 50}))) == 8
    # a table cell's observations name no calls
    assert pod_spans.arm_steps({"job": "table_sync", "host": {"dispatch_s": 1.0}}) is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_gives_the_hand_computed_value(name, log):
    _a_traced_run(log)
    assert harness.load_by_path("layer_metrics", name).read(OBS) == pytest.approx(READERS[name])


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_gives_none_on_an_empty_log(name, log, monkeypatch):
    read = harness.load_by_path("layer_metrics", name).read
    assert read(OBS) is None  # ST_OBS=0, or a cell that drives no PodTrainer
    log("st:train.step", 1000.0, 1.0, program="local")
    assert read(OBS) is None  # no step of the program that exchanges
    # a program from before PR 38: its PodTier keeps no spans
    from shared_tensor_tpu.utils import profiling

    monkeypatch.setattr(profiling, "pod_tier", lambda: object())
    assert read(OBS) is None


def test_one_collection_free_arm_reads_zero(log):
    for i in range(3):
        log("st:train.step", 1000.0 + 100 * i, 1.0, step=i, program="sync")
    read = harness.load_by_path("layer_metrics", "gc_pause_max_ms.train").read
    assert read(dict(OBS, trace={"steps": 0}, host={"dispatch_calls": 3})) == 0.0


def test_rehearsal_prints_the_four_metrics_and_the_span_table(tmp_path):
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"), "--workload",
         "resnet18_b1024", "--seed", str(2**31 + 3838), "--seconds", "1", "--trace", "1",
         "--rehearse-cpu"],
        capture_output=True, text=True, timeout=900, cwd=ROOT,
        env=dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
                 TMPDIR=str(tmp_path)),
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    assert line["correct"] is True and line["rehearsal"] is True
    for name in READERS:
        assert isinstance(line["metrics"][name]["value"], float), name
    assert 0 < line["metrics"]["step_call_ms.train"]["value"] <= line["metrics"]["step_call_max_ms.train"]["value"]
    assert line["metrics"]["step_gap_max_over_median.train"]["value"] >= 1.0
    assert line["metrics"]["gc_pause_max_ms.train"]["value"] >= 0.0
    tables = [l for l in lines[:-1] if l.startswith("[chipbench] st spans ")]
    assert len(tables) == 1  # once a process, above the result line
    table = json.loads(tables[0][len("[chipbench] st spans "):])
    steps = line["checks"]["arms"]["steps"]
    assert table["st:train.step program=sync"][0] >= steps
    assert table["st:trainer_init"][0] == 2  # the default program's and sync=False's
    for name in ("st:init_state.seed", "st:build_train_step", "st:build.compile program=_step"):
        assert name in table, name
