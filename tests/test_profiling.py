"""Observability tests (SURVEY.md §5.1/§5.5 build notes)."""

import time

import jax.numpy as jnp
import numpy as np
import pytest

from shared_tensor_tpu.config import ScalePolicy
from shared_tensor_tpu.ops import codec
from shared_tensor_tpu.utils.profiling import RateMeter, effective_bits, trace


def test_effective_bits_homogeneous_is_one():
    """Uniform residual: RMS halves per frame -> 1.0 bits/elem/frame, the
    BASELINE.md reference curve."""
    n = 4096
    rng = np.random.default_rng(0)
    r = jnp.asarray(rng.uniform(-1, 1, codec.pad_flat(jnp.zeros(n)).shape[0]).astype("f4"))
    r = r.at[n:].set(0.0)
    traj = []
    for _ in range(10):
        traj.append(float(jnp.sqrt(jnp.sum(r * r) / n)))
        _, r = codec.quantize(r, n, ScalePolicy.POW2_RMS)
    bits = effective_bits(traj)
    assert 0.8 < bits < 1.2, (bits, traj)


def test_effective_bits_edge_cases():
    assert effective_bits([]) == 0.0
    assert effective_bits([1.0]) == 0.0
    assert effective_bits([0.0, 0.0]) == 0.0
    # exact convergence caps at fp32 precision instead of inf
    assert effective_bits([1.0, 0.0]) <= 24.0


def test_rate_meter():
    m = RateMeter(window_sec=60.0)
    m.update(frames=0, bytes=0)
    time.sleep(0.05)
    m.update(frames=50, bytes=5000)
    r = m.rates()
    assert r["frames"] > 100  # ~1000/s
    assert r["bytes"] / r["frames"] == pytest.approx(100.0)


def test_rate_meter_window_spans_more_than_last_interval():
    """Eviction keeps one sample at/just before the window edge: with many
    rapid updates inside the window, rates() must span the whole window, not
    just the final update interval (ADVICE.md round-1 finding)."""
    m = RateMeter(window_sec=60.0)
    for i in range(50):
        m.update(frames=i)
    assert len(m._samples) == 50  # nothing evicted within the window
    m2 = RateMeter(window_sec=0.01)
    m2.update(frames=0)
    time.sleep(0.02)
    for i in range(1, 5):
        m2.update(frames=i)
    # The stale sample is RETAINED as the one at/before the window edge
    # (eviction only pops while the second-oldest is past the cutoff), so
    # all 5 survive here; the old inverted condition would leave exactly 2.
    assert len(m2._samples) == 5
    assert m2._samples[0][0] <= time.monotonic() - m2.window


def test_rate_meter_tolerates_counter_reset():
    """r08 satellite: a counter that goes BACKWARDS (fresh link id after a
    re-graft, re-created peer) must re-anchor the window, not emit a huge
    negative rate for the whole window span."""
    m = RateMeter(window_sec=60.0)
    m.update(frames=1000, bytes=100000)
    time.sleep(0.01)
    m.update(frames=2000, bytes=200000)
    time.sleep(0.01)
    # the re-graft: counters restart near zero on the new link
    m.update(frames=5, bytes=500)
    time.sleep(0.01)
    m.update(frames=10, bytes=1000)
    r = m.rates()
    assert r["frames"] >= 0, r
    assert r["bytes"] >= 0, r
    # and the post-reset stream is measured (~5 frames / ~10 ms)
    assert r["frames"] > 50, r
    # a reset in ONE counter re-anchors the whole sample set (mixed-epoch
    # windows are meaningless), so the untouched counter stays sane too
    m2 = RateMeter(window_sec=60.0)
    m2.update(a=100, b=100)
    time.sleep(0.01)
    m2.update(a=0, b=200)
    time.sleep(0.01)
    m2.update(a=50, b=300)
    r2 = m2.rates()
    assert r2["a"] >= 0 and r2["b"] >= 0, r2


def test_rate_meter_idle_gap_does_not_dilute():
    """After an idle gap longer than the window, rates() must reflect the
    recent window (counters interpolated at the window edge), not average
    the burst over the whole gap."""
    m = RateMeter(window_sec=0.05)
    m.update(frames=0)
    time.sleep(0.5)  # idle gap 10x the window
    m.update(frames=100)
    time.sleep(0.01)
    m.update(frames=200)
    r = m.rates()
    # Diluted-over-the-gap would be ~ (200-0)/0.51 ~ 390/s; the window
    # estimate is >= (200 - interp@edge)/window ~ 2000/s.
    assert r["frames"] > 1500, r


def test_trace_writes_profile(tmp_path):
    with trace(str(tmp_path)):
        jnp.sum(jnp.ones((128, 128))).block_until_ready()
    # the profiler must have produced a trace artifact
    produced = list(tmp_path.rglob("*"))
    assert produced, "no profile output written"


# --- PR 27: the by-scope reduction's arithmetic, on made-up lines ---------------


def test_scope_of_reads_scopes_through_transformations():
    from shared_tensor_tpu.utils.profiling import innermost, scope_of

    assert scope_of("jit(_step)/vmap(st.grads)/st.flatten/jit(_pad)/pad") == "st.grads/st.flatten"
    assert scope_of("jit(_step)/vmap(st.grads)/transpose(jvp())/mul") == "st.grads"
    # of names XLA merged, the first that has a scope
    assert scope_of("jit(f)/mul;jit(f)/st.update/neg;jit(f)/st.grads/add") == "st.update"
    # an argument a caller named ``st`` is no scope
    assert scope_of("st.values") == "" and scope_of("jit(f)/st.values") == ""
    assert scope_of("jit(sync_step)/jit(main)/mul") == ""
    assert scope_of("state.values") == "" and scope_of("first.step/add") == ""
    assert innermost("st.codec_send/st.leaf_scales") == "st.leaf_scales"
    assert innermost("st.grads") == "st.grads"


def test_self_time_counts_an_enclosing_event_once():
    """A ``while`` event spans its body's operations on the same line: it
    keeps what they do not cover, and the line's self times add up to the
    union of its events."""
    from shared_tensor_tpu.utils.profiling import self_times

    events = [
        (0.0, 10.0),     # fusion
        (10.0, 110.0),   # while.2 ...
        (12.0, 40.0),    # ... its body: slice
        (40.0, 95.0),    # ... reduce, which itself encloses
        (50.0, 60.0),    # ... a nested call
        (110.0, 130.0),  # all-gather
    ]
    own = self_times(events)
    assert own == [10.0, 100.0 - 28.0 - 55.0, 28.0, 45.0, 10.0, 20.0]
    assert sum(own) == 130.0
    # the order of the events does not matter
    back = self_times(events[::-1])
    assert back[::-1] == own
    assert self_times([]) == []


def test_idle_gap_goes_to_the_span_that_covers_most_of_it():
    from shared_tensor_tpu.utils.profiling import attribute_gap

    spans = [
        ("chipbench:window", 0.0, 1000.0),
        ("chipbench:dispatch", 100.0, 200.0),
        ("st:train.step", 110.0, 190.0),
        ("chipbench:wait_step", 200.0, 400.0),
    ]
    # wholly inside three spans: the innermost (shortest) one
    assert attribute_gap(120.0, 180.0, spans) == "st:train.step"
    # the dispatch covers all of it, the step only a part
    assert attribute_gap(100.0, 150.0, spans) == "chipbench:dispatch"
    # mostly the wait, though the window covers all: the window covers most
    assert attribute_gap(190.0, 300.0, spans) == "chipbench:window"
    assert attribute_gap(210.0, 300.0, spans) == "chipbench:wait_step"
    assert attribute_gap(2000.0, 2100.0, spans) == "unattributed"
    assert attribute_gap(0.0, 50.0, []) == "unattributed"


def test_scope_map_inherits_through_called_computations():
    from shared_tensor_tpu.utils.profiling import scope_map

    text = """HloModule jit_f, is_scheduled=true

%fused_a (p: f32[8]) -> f32[8] {
  %p = f32[8] parameter(0)
  %neg.1 = f32[8] negate(%p), metadata={op_name="jit(f)/st.codec_send/st.quantize/neg"}
  ROOT %add.1 = f32[8] add(%neg.1, %p), metadata={op_name="jit(f)/st.codec_apply/st.apply/add"}
}

%body (q: f32[8]) -> f32[8] {
  %q = f32[8] parameter(0)
  %slice.7 = f32[8] slice(%q), slice={[0:8]}
  ROOT %copy.3 = f32[8] copy(%slice.7)
}

%cond (r: f32[8]) -> pred[] {
  %r = f32[8] parameter(0)
  ROOT %lt = pred[] constant(false)
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8] parameter(0), metadata={op_name="a"}
  %fusion.3 = f32[8] fusion(%a), kind=kLoop, calls=%fused_a
  %copy-start.1 = (f32[8], f32[8], u32[]) copy-start(%fusion.3)
  %copy-done.1 = f32[8] copy-done(%copy-start.1)
  %while.2 = f32[8] while(%copy-done.1), condition=%cond, body=%body, metadata={op_name="jit(f)/st.codec_send/st.allgather/while"}
  %mul.9 = f32[8] multiply(%while.2, %a), metadata={op_name="jit(f)/mul"}
  ROOT %bitcast.5 = f32[8] bitcast(%mul.9)
}
"""
    m = scope_map(text)
    # a fusion with no name of its own takes its root's scope
    assert m[("jit_f", "fusion.3")] == "st.codec_apply/st.apply"
    assert m[("jit_f", "neg.1")] == "st.codec_send/st.quantize"
    # a while body's unnamed operations take the while's scope
    assert m[("jit_f", "slice.7")] == "st.codec_send/st.allgather"
    assert m[("jit_f", "copy.3")] == "st.codec_send/st.allgather"
    assert m[("jit_f", "lt")] == "st.codec_send/st.allgather"
    # what the compiler put in with no name goes to what uses its result
    assert m[("jit_f", "copy-start.1")] == "st.codec_send/st.allgather"
    assert m[("jit_f", "copy-done.1")] == "st.codec_send/st.allgather"
    # a name with no scope in it stays unscoped, and gives none to the
    # nameless around it
    for name in ("mul.9", "a", "bitcast.5"):
        assert ("jit_f", name) not in m
    # a dump prints names without the percent sign
    assert scope_map(text.replace("%", "")) == m


# --- PR 27: the pod tier's counters ------------------------------------------------


def _pod_trainer(**kw):
    from shared_tensor_tpu.parallel import make_mesh
    from shared_tensor_tpu.train import PodTrainer

    tpl = {"w": jnp.ones((16, 128), jnp.float32)}
    loss = lambda p, b: jnp.mean((b * p["w"][None]) ** 2)
    tr = PodTrainer(make_mesh(2, 1), tpl, loss, **kw)
    return tr, lambda rows: tr.shard_batch(jnp.ones((2, rows, 16, 128)))


def _pod_counts():
    from shared_tensor_tpu.obs.schema import label_key
    from shared_tensor_tpu.utils.profiling import pod_registry

    snap = pod_registry().snapshot()
    steps = {
        p: snap[label_key("st_pod_steps_total", "program", p)] for p in ("sync", "local")
    }
    return snap, steps


def test_pod_step_counter_follows_trainer_steps():
    tr, batch = _pod_trainer(sync_every=3)
    _, before = _pod_counts()
    for _ in range(7):
        tr.step(batch(4))
    _, after = _pod_counts()
    assert tr.steps == 7
    assert after["sync"] - before["sync"] == 2  # steps 3 and 6
    assert after["local"] - before["local"] == 5
    # a trainer that never exchanges runs the local program only
    tr2, batch2 = _pod_trainer(sync=False)
    tr2.step(batch2(4))
    _, last = _pod_counts()
    assert last["local"] - after["local"] == 1 and last["sync"] == after["sync"]


def test_pod_compile_counter_names_the_step_that_recompiled():
    tr, batch = _pod_trainer()
    small, large = batch(4), batch(6)
    tr.step(small)
    tr.step(small)
    snap, _ = _pod_counts()
    compiles = snap["st_pod_compiles_total"]
    tr.step(small)  # nothing new: no compilation
    snap, _ = _pod_counts()
    assert snap["st_pod_compiles_total"] == compiles
    assert tr.steps == 3
    tr.step(large)  # a new batch shape: this step (number 3) recompiles
    snap, _ = _pod_counts()
    assert snap["st_pod_compiles_total"] > compiles
    assert snap["st_pod_last_compile_step"] == 3
    assert snap["st_pod_compile_seconds_total"] > 0.0


def test_pod_counters_are_in_the_schema_and_the_exposition():
    from shared_tensor_tpu.obs.schema import SCHEMA
    from shared_tensor_tpu.utils.profiling import pod_registry

    names = (
        "st_pod_steps_total", "st_pod_compiles_total", "st_pod_compile_seconds_total",
        "st_pod_cache_load_seconds_total", "st_pod_last_compile_step",
        "st_attn_traces_total", "st_attn_saved_bytes", "st_codec_kernel_traces_total",
        "st_codec_leaves_per_block_max", "st_codec_words_rows_per_block",
        "st_moe_combine_traces_total",
    )
    text = pod_registry().prometheus_text()
    for name in names:
        assert name in SCHEMA, name
        assert name in text, name
    assert 'st_pod_steps_total{program="sync"}' in text
    # a trace-time counter: both paths are there from the start, at 0 or more
    assert 'st_attn_traces_total{path="pallas"}' in text
    assert 'st_attn_traces_total{path="scan"}' in text
    assert 'st_attn_saved_bytes{kind="full"}' in text
    assert 'st_attn_saved_bytes{kind="window"}' in text
    assert 'st_codec_kernel_traces_total{kernel="quantize_rows"}' in text
    assert 'st_codec_kernel_traces_total{kernel="apply_rows_batch"}' in text
    assert 'st_codec_words_rows_per_block{kernel="quantize_rows"}' in text
    assert 'st_codec_words_rows_per_block{kernel="apply_rows_batch"}' in text
    assert 'st_moe_combine_traces_total{path="pallas"}' in text
    assert 'st_moe_combine_traces_total{path="xla"}' in text
    assert pod_registry() is pod_registry()


def test_traced_sync_step_counts_its_codec_kernels():
    """Tracing ``build_sync_step`` on the kernel tier counts one call of each
    codec kernel and leaves the most leaves one of their grid blocks meets,
    and the rows of packed words a grid step takes (32 at a block of 1 024
    table rows), in the gauges; the XLA tier traces no kernel."""
    import jax

    from shared_tensor_tpu.obs.schema import label_key
    from shared_tensor_tpu.ops.table import make_spec
    from shared_tensor_tpu.parallel import build_sync_step, init_state, make_mesh

    def counts():
        snap, _ = _pod_counts()
        return (
            [snap[label_key("st_codec_kernel_traces_total", "kernel", k)]
             for k in ("quantize_rows", "apply_rows_batch")],
            snap["st_codec_leaves_per_block_max"],
            [snap[label_key("st_codec_words_rows_per_block", "kernel", k)]
             for k in ("quantize_rows", "apply_rows_batch")],
        )

    mesh = make_mesh(2, 1)
    # five leaves of 8 rows and one of 1100: the first block meets all six
    tree = {f"l{i}": jnp.zeros(n) for i, n in enumerate([3, 1000, 70, 1024, 5, 1100 * 128])}
    spec = make_spec(tree)
    state = init_state(mesh, spec)
    before, _, _ = counts()
    build_sync_step(mesh, spec, impl="xla").lower(state)
    assert counts()[0] == before
    build_sync_step(mesh, spec, impl="pallas").lower(state)
    after, leaves, words_rows = counts()
    assert [a - b for a, b in zip(after, before)] == [1, 1]
    assert leaves == 6
    assert words_rows == [32, 32]  # 1140 rows in blocks of 1024
    build_sync_step(mesh, make_spec({"w": jnp.zeros(4096)}), impl="pallas").lower(
        jax.tree.map(lambda x: x[:, :4096], state)
    )
    assert counts()[1:] == (1, [1, 1])  # the newest traced table's: 32 rows, one block


# --- PR 38: the pod tier's host spans -----------------------------------------------


def _spans_since(t_ns, name=None):
    from shared_tensor_tpu.utils.profiling import pod_tier

    return [r for r in pod_tier().spans(since_ns=t_ns) if name is None or r.name == name]


def _series(name, span):
    from shared_tensor_tpu.obs.schema import label_key

    snap, _ = _pod_counts()
    return snap.get(label_key(name, "span", span), 0)


def test_span_logs_one_event_and_nests():
    """One event a span: its duration, the step, the span open around it;
    a parent's self time is its duration minus what its children cover."""
    from shared_tensor_tpu.utils.profiling import pod_tier, span_table

    pod = pod_tier()
    t = time.monotonic_ns()
    pod.step_now = 41
    with pod.span("t38.outer", program="sync"):
        time.sleep(0.02)
        with pod.span("t38.inner"):
            time.sleep(0.03)
    pod.step_now = 0
    rows = [r for r in _spans_since(t) if r.name.startswith("st:t38.")]
    assert [r.name for r in rows] == ["st:t38.outer", "st:t38.inner"]  # by start
    outer, inner = rows
    assert (outer.parent, inner.parent) == ("", "st:t38.outer")
    assert outer.step == inner.step == 41
    assert outer.attrs == {"program": "sync"} and inner.attrs == {}
    assert outer.t0_ns <= inner.t0_ns and inner.t1_ns <= outer.t1_ns
    assert 0.03e9 <= inner.t1_ns - inner.t0_ns < 3e9
    assert 0.05e9 <= outer.t1_ns - outer.t0_ns < 6e9
    table = span_table(rows)
    assert list(table) == ["st:t38.outer program=sync", "st:t38.inner"]  # largest first
    o, i = table.values()
    assert o["calls"] == i["calls"] == 1 and i["self_s"] == i["total_s"]
    assert o["self_s"] == pytest.approx(o["total_s"] - i["total_s"], abs=1e-9)
    assert 0.02 <= o["self_s"] < 3


def test_span_series_are_in_the_schema_and_the_exposition():
    from shared_tensor_tpu.obs.schema import SCHEMA
    from shared_tensor_tpu.utils.profiling import pod_registry, pod_tier

    with pod_tier().span("t38.series"):
        pass
    text = pod_registry().prometheus_text()
    for name in ("st_pod_span_seconds_total", "st_pod_span_calls_total",
                 "st_pod_trace_seconds_total", "st_pod_lower_seconds_total",
                 "st_pod_gc_seconds_total", "st_pod_gc_pause_seconds_max"):
        assert name in SCHEMA and name in text, name
    assert 'st_pod_span_calls_total{span="t38.series"} 1' in text
    assert 'st_pod_span_seconds_total{span="t38.series"}' in text


def test_span_with_obs_off_logs_nothing_and_still_counts():
    from shared_tensor_tpu import obs
    from shared_tensor_tpu.utils.profiling import pod_tier

    pod = pod_tier()
    t = time.monotonic_ns()
    calls = _series("st_pod_span_calls_total", "t38.off")
    was = obs.obs_enabled()
    obs.set_enabled(False)
    try:
        with pod.span("t38.off"):
            time.sleep(0.01)
        assert _spans_since(t) == []
    finally:
        obs.set_enabled(was)
    assert _spans_since(t, "st:t38.off") == []  # nor afterwards
    assert _series("st_pod_span_calls_total", "t38.off") == calls + 1
    assert _series("st_pod_span_seconds_total", "t38.off") >= 0.01


def test_pod_trainer_leaves_its_set_up_and_its_builds_as_spans():
    t = time.monotonic_ns()
    tr, batch = _pod_trainer()
    parent = {r.name: r.parent for r in _spans_since(t) if not r.name.startswith("st:build.")}
    assert parent["st:trainer_init"] == ""
    for child in ("st:make_spec", "st:init_state", "st:opt_init", "st:build_train_step"):
        assert parent[child] == "st:trainer_init", child
    for child in ("seed", "broadcast", "residual"):
        assert parent[f"st:init_state.{child}"] == "st:init_state", child
    assert parent["st:build_sync_step"] == "st:build_train_step"
    init = _spans_since(t, "st:trainer_init")[0]
    assert all(init.t0_ns <= r.t0_ns and r.t1_ns <= init.t1_ns
               for r in _spans_since(t) if r.parent in parent and r.parent)
    b = batch(4)
    seen = []
    for _ in range(3):
        t = time.monotonic_ns()
        tr.step(b)
        seen.append(_spans_since(t))
    first = {r.name: r for r in seen[0]}
    step = first["st:train.step"]
    assert step.attrs == {"program": "sync"} and step.step == 0
    for phase in ("trace", "lower", "compile"):
        build = first[f"st:build.{phase}"]
        assert build.attrs == {"program": "_step"}, build
        assert build.parent == "st:train.step" and build.step == 0
        assert step.t0_ns <= build.t0_ns and build.t1_ns <= step.t1_ns
    third = seen[2]
    assert [(r.name, r.step) for r in third if r.name != "st:gc"] == [("st:train.step", 2)]


def test_a_nested_trace_counts_once():
    """``inner`` is traced inside ``outer``'s trace: one ``st:build.trace``
    event, ``outer``'s, and its seconds once in the counter."""
    import jax

    from shared_tensor_tpu.utils.profiling import pod_tier

    pod_tier()  # the listeners exist from here

    @jax.jit
    def inner(x):
        time.sleep(0.05)
        return x + 1

    @jax.jit
    def outer(x):
        return inner(x) * 2

    x = jnp.ones(8)  # built before the reading starts
    snap, _ = _pod_counts()
    t = time.monotonic_ns()
    outer(x).block_until_ready()
    traces = [r for r in _spans_since(t, "st:build.trace")]
    assert [r.attrs["program"] for r in traces] == ["outer"]
    traced_s = (traces[0].t1_ns - traces[0].t0_ns) / 1e9
    assert traced_s >= 0.05
    after, _ = _pod_counts()
    counted = after["st_pod_trace_seconds_total"] - snap["st_pod_trace_seconds_total"]
    assert counted == pytest.approx(traced_s, abs=0.01)  # twice would be 0.05 more
    assert after["st_pod_lower_seconds_total"] > snap["st_pod_lower_seconds_total"]


def test_a_new_batch_shape_logs_its_build_at_that_step():
    tr, batch = _pod_trainer()
    small, large = batch(4), batch(6)
    for _ in range(3):
        tr.step(small)
    t = time.monotonic_ns()
    tr.step(large)  # step number 3 builds the program again
    builds = [r for r in _spans_since(t) if r.name.startswith("st:build.")]
    assert {r.name for r in builds} == {"st:build.trace", "st:build.lower", "st:build.compile"}
    assert all(r.step == 3 and r.parent == "st:train.step" for r in builds)
    snap, _ = _pod_counts()
    assert snap["st_pod_last_compile_step"] == 3


def test_a_pause_between_two_steps_is_the_gap_between_their_spans():
    tr, batch = _pod_trainer()
    b = batch(4)
    tr.step(b)  # builds
    t = time.monotonic_ns()
    tr.step(b)
    time.sleep(0.05)
    tr.step(b)
    one, two = _spans_since(t, "st:train.step")
    assert (one.step, two.step) == (1, 2)
    assert 0.05e9 <= two.t0_ns - one.t1_ns < 3e9


def test_a_collection_is_an_event_and_raises_the_gauge():
    import gc

    from shared_tensor_tpu.utils.profiling import pod_tier

    pod = pod_tier()
    snap, _ = _pod_counts()
    cycle = [[] for _ in range(1_000_000)]
    for a, b in zip(cycle, cycle[1:]):
        a.append(b)
    cycle[-1].append(cycle[0])
    t = time.monotonic_ns()
    with pod.span("t38.collect"):
        del cycle, a, b
        gc.collect()
    pauses = _spans_since(t, "st:gc")
    assert pauses and all(r.parent == "st:t38.collect" for r in pauses)
    longest = max(r.t1_ns - r.t0_ns for r in pauses)
    assert longest >= 1_000_000
    assert any(r.attrs == {"generation": "2"} for r in pauses)
    after, _ = _pod_counts()
    assert after["st_pod_gc_pause_seconds_max"] >= longest / 1e9
    assert after["st_pod_gc_seconds_total"] >= snap["st_pod_gc_seconds_total"] + longest / 1e9


def test_build_sync_step_logs_its_span():
    from shared_tensor_tpu.ops.table import make_spec
    from shared_tensor_tpu.parallel import build_sync_step, init_state, make_mesh

    mesh = make_mesh(2, 1)
    spec = make_spec({"w": jnp.zeros(4096)})
    t = time.monotonic_ns()
    step = build_sync_step(mesh, spec)
    state = init_state(mesh, spec)
    names = [r.name for r in _spans_since(t)]
    assert names[0] == "st:build_sync_step" and "st:init_state" in names
    assert "st:init_state.residual" in names and "st:init_state.seed" not in names
    t = time.monotonic_ns()
    step(state)
    builds = {r.name: r.attrs for r in _spans_since(t) if r.name.startswith("st:build.")}
    assert builds == {f"st:build.{p}": {"program": "sync_step"} for p in ("trace", "lower", "compile")}


def test_timeline_option_prints_the_span_table(tmp_path, capsys):
    from shared_tensor_tpu import obs
    from shared_tensor_tpu.utils.profiling import main, pod_tier

    pod = pod_tier()
    with pod.span("t38.exported"):
        with pod.span("t38.exported.child"):
            time.sleep(0.01)
    path = obs.hub().export_timeline(str(tmp_path / "timeline.json"))
    assert main(["--timeline", path]) == 0
    out = capsys.readouterr().out.splitlines()
    assert out[0].split() == ["calls", "total", "s", "self", "s", "span"]
    rows = {l.split()[-1]: l.split() for l in out[1:]}
    assert rows["st:t38.exported"][0] == "1"
    assert float(rows["st:t38.exported.child"][1]) >= 0.01
    assert float(rows["st:t38.exported"][2]) < float(rows["st:t38.exported"][1])
    with pytest.raises(SystemExit):
        main([])
