"""chipbench/trace_reduce.py: the interval arithmetic, the classification of
trace names, and the reduction of the recorded chip trace kept beside it."""

import glob
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import harness, trace_reduce as tr  # noqa: E402

RECORDED = sorted(glob.glob(os.path.join(ROOT, "chipbench", "testdata", "trace_*.json")))


def _sweep(trace, patterns=()):
    """The same quantities by another method: cut the window at every event
    boundary and ask of each piece which kinds cover it. Quadratic, and
    independent of union/subtract/clip."""
    lo, hi = tr.window_of(trace)
    out = []
    for dev, events in sorted(trace["devices"].items()):
        evs = [(n, s, s + d, tr.classify(n, c, patterns)) for n, s, d, c in events
               if s + d > lo and s < hi]
        if not evs:
            continue
        spans = tr._collective_intervals(
            [e for e in events + trace.get("async", {}).get(dev, [])
             if e[1] + e[2] > lo and e[1] < hi])
        cuts = sorted({lo, hi} | {min(max(t, lo), hi) for se in spans for t in se} | {min(max(t, lo), hi) for _, s, e, _ in evs for t in (s, e)})
        busy = kernel = xla = 0.0
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            kinds = {k for _, s, e, k in evs if s <= mid < e}
            kinds |= {"collective" for s, e in spans if s <= mid < e}
            if kinds:
                busy += b - a
            if "kernel" in kinds:
                kernel += b - a
            if "xla" in kinds and not kinds & {"kernel", "collective"}:
                xla += b - a
        out.append((busy / 1e9, kernel / 1e9, xla / 1e9))
    n = len(out)
    return tuple(sum(col) / n for col in zip(*out))


def test_union_subtract_clip():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)]) == [(0, 3), (5, 8)]
    assert tr.length(tr.union([(0, 2), (1, 3)])) == 3
    assert tr.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5), (7, 10)]
    assert tr.subtract([(0, 4), (6, 10)], [(3, 7)]) == [(0, 3), (7, 10)]
    assert tr.subtract([(0, 4)], []) == [(0, 4)]
    assert tr.subtract([(0, 4)], [(0, 4)]) == []


HLO_FUSION = (
    "%fusion.3 = f32[201]{0:T(256)S(1)} fusion(s32[3277888]{0:T(1024)} %constant.11, "
    "f32[3277888]{0:T(1024)S(1)} %fusion.4), kind=kCustom, calls=%fused_computation.18"
)
HLO_KERNEL = (
    "%sync_step.2 = (u32[3277888,4]{1,0:T(8,128)}, f32[3277888,128]{1,0:T(8,128)}) "
    "custom-call(f32[3277888,1]{1,0:T(8,128)} %reshape.54), "
    'custom_call_target="tpu_custom_call", operand_layout_constraints={f32[3277888,1]{1,0}}'
)
HLO_OTHER_CALL = (
    "%custom-call.7 = f32[1,11715584]{1,0:T(1,128)S(1)} custom-call(f32[1,2929664]{1,0} "
    '%slice-done.12), custom_call_target="ConcatBitcast"'
)
HLO_GATHER = (
    "%all-gather-start.1 = (u32[1,13111552]{1,0}, u32[4,13111552]{1,0}) "
    "all-gather-start(u32[1,13111552]{1,0} %bitcast.3), dimensions={0}"
)


@pytest.mark.parametrize("name,label,category,kind", [
    (HLO_FUSION, "fusion.3 = f32[201] fusion", "fusion", "xla"),
    (HLO_KERNEL, "sync_step.2 = (u32[3277888,4], f32[3277888,128]) custom-call",
     "custom-call:tpu_custom_call", "kernel"),
    (HLO_OTHER_CALL, "custom-call.7 = f32[1,11715584] custom-call",
     "custom-call:ConcatBitcast", "xla"),
    (HLO_GATHER, "all-gather-start.1 = (u32[1,13111552], u32[4,13111552]) all-gather-start",
     "all-gather-start", "collective"),
    ("all-gather.3", "all-gather.3", "all-gather", "collective"),
    ("dot_general.1", "dot_general.1", "dot_general", "xla"),
])
def test_parse_and_classify(name, label, category, kind):
    """Names as the v5e's trace gives them today (whole HLO instructions,
    copied from my chip runs of PR 25) and as the CPU's gives them."""
    assert tr.parse_op(name) == (label, category)
    assert tr.classify(label, category) == kind


def test_extra_kernel_patterns_come_from_files():
    assert tr.classify("my_new_kernel.2", "fusion", [r"^my_new_kernel"]) == "kernel"
    for pat in harness.kernel_patterns():
        assert isinstance(pat, str) and pat


def _synthetic():
    # one device, window [100, 1100): fusion 100-300, kernel 300-500 nested in
    # a while 250-600, async all-gather 600-900 with a fusion 700-800 under
    # it, idle 900-1100 while the host waits
    dev = [
        ["fusion.1", 100.0, 200.0, "fusion"],
        ["while.2", 250.0, 350.0, "while"],
        ["custom-call.3", 300.0, 200.0, "custom-call:tpu_custom_call"],
        ["all-gather-start.4", 600.0, 10.0, "all-gather-start"],
        ["fusion.5", 700.0, 100.0, "fusion"],
        ["all-gather-done.4", 880.0, 20.0, "all-gather-done"],
        ["fusion.6", 2000.0, 50.0, "fusion"],  # outside the window
    ]
    host = [
        ["chipbench:window", 100.0, 1000.0],
        ["chipbench:dispatch", 100.0, 100.0],
        ["chipbench:wait_last", 850.0, 250.0],
    ]
    return {"devices": {"0": dev}, "host": host}


def test_reduce_on_a_trace_worked_out_by_hand():
    s = tr.reduce(_synthetic(), steps=2)
    ns = 1e-9
    assert s["window_s"] == pytest.approx(1000 * ns)
    assert s["busy_s"] == pytest.approx(800 * ns)  # 100-900
    assert s["kernel_s"] == pytest.approx(200 * ns)
    assert s["collective_s"] == pytest.approx(300 * ns)  # start of start to end of done
    assert s["collective_exposed_s"] == pytest.approx(200 * ns)  # less the fusion under it
    # fusion.1 and while.2 cover 100-600, less the kernel's 200; fusion.5
    # lies under the collective
    assert s["xla_s"] == pytest.approx(300 * ns)
    assert s["idle_gaps"] == [["wait_last", pytest.approx(200 * ns)]]
    assert s["device_ops"][0][0] == "while.2"
    assert "fusion.6" not in [n for n, _ in s["device_ops"]]
    busy, kernel, xla = _sweep(_synthetic())
    assert (busy, kernel, xla) == pytest.approx((s["busy_s"], s["kernel_s"], s["xla_s"]))


def test_reduce_returns_nothing_when_no_operation_ran_in_the_window():
    t = _synthetic()
    t["devices"] = {"0": [["fusion.6", 2000.0, 50.0, "fusion"]]}
    assert tr.reduce(t, steps=1) is None
    with pytest.raises(ValueError):
        tr.reduce({"devices": {}, "host": []}, steps=1)


@pytest.mark.parametrize("path", RECORDED, ids=[os.path.basename(p) for p in RECORDED])
def test_recorded_chip_trace(path):
    """A trace recorded on the v5e (PERF.md says of which run), cut to a few
    steps: the reduction finds the kernels and, on four chips, the
    all-gather, under the names the chip gives them today, and agrees with
    the sweep."""
    assert os.path.getsize(path) < 1 << 20
    with open(path) as f:
        rec = json.load(f)
    trace, steps, want = rec["trace"], rec["steps"], rec["expect"]
    s = tr.reduce(trace, steps, harness.kernel_patterns())
    assert s["devices"] == want["devices"]
    assert 0 < s["busy_s"] <= s["window_s"]
    assert s["kernel_s"] > 0 and s["xla_s"] > 0
    assert s["kernel_s"] + s["collective_s"] + s["xla_s"] <= s["busy_s"] * 1.0001
    if want["devices"] > 1:
        assert s["collective_s"] > 0
    busy, kernel, xla = _sweep(trace, harness.kernel_patterns())
    assert (busy, kernel, xla) == pytest.approx((s["busy_s"], s["kernel_s"], s["xla_s"]))
    for key in ("busy_s", "kernel_s", "collective_s", "xla_s", "window_s"):
        assert s[key] == pytest.approx(want[key], rel=1e-9), key


def test_there_is_a_recorded_trace():
    assert RECORDED, "chipbench/testdata/ holds no recorded trace"


def test_load_xplane_reads_a_profile_made_here(tmp_path):
    """The loader on a real profiler file (the CPU's: no device plane, so the
    rehearsal's stand-in device and the annotations are what it finds)."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with harness.annotate("window"):
        with harness.annotate("dispatch"):
            y = f(x)
        y.block_until_ready()
    jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    plain = tr.load_xplane(path, rehearsal=True)
    assert {"chipbench:window", "chipbench:dispatch"} <= {n for n, _, _ in plain["host"]}
    assert tr.load_xplane(path)["devices"] == {}
    lo, hi = tr.window_of(plain)
    assert hi > lo
    assert "/host:CPU" in tr.describe(path)
