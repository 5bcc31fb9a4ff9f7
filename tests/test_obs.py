"""Unified cross-tier telemetry tests (r08 tentpole evidence).

Covers the three tentpole pieces end to end:

- the metrics registry (counters/gauges/fixed-bucket histograms, snapshot,
  Prometheus text exposition, JSONL sink) and the canonical key schema
  that supersedes the four ad-hoc metric surfaces;
- the native event ring (lock-free per-thread rings in sttransport.cpp,
  drained over ``st_obs_drain``) merged with Python-tier events on the
  shared CLOCK_MONOTONIC timebase — ONE ordered timeline spanning tiers;
- the flight recorder: under ``ST_FAULT_PLAN`` / FaultPlan chaos, every
  injected drop/dup/sever must appear in the merged timeline (exact
  counts on the Python tier, where the injector reports its tallies), and
  crash points / recv-thread exceptions / go-back-N teardowns must leave
  a postmortem dump.
"""

import json
import os
import time

import jax.numpy as jnp
import numpy as np
import pytest

from shared_tensor_tpu import obs
from shared_tensor_tpu.comm import faults, transport, wire
from shared_tensor_tpu.comm.peer import SharedTensorPeer, create_or_fetch
from shared_tensor_tpu.config import Config, FaultConfig, ObsConfig, TransportConfig
from shared_tensor_tpu.obs import events as obs_events
from shared_tensor_tpu.obs import schema

from tests._ports import free_port as _free_port


@pytest.fixture(scope="module", autouse=True)
def _built():
    transport.build_native()


def _cfg(fault: FaultConfig | None = None, engine: bool = True, **tkw):
    tkw.setdefault("peer_timeout_sec", 10.0)
    return Config(
        transport=TransportConfig(**tkw),
        faults=fault or FaultConfig(),
        native_engine=engine,
    )


def _fresh_hub():
    """Flush stale native events from earlier tests, then start clean."""
    h = obs.hub()
    h.poll_native()
    h.recorder.clear()
    return h


def _wait(pred, timeout=30.0, msg="condition"):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return
        time.sleep(0.05)
    raise AssertionError(f"timed out waiting for {msg}")


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


def test_registry_counter_gauge_histogram():
    r = obs.Registry()
    c = r.counter("st_test_total", help="a counter")
    c.inc()
    c.inc(4)
    with pytest.raises(ValueError):
        c.inc(-1)
    g = r.gauge("st_test_gauge")
    g.set(7.5)
    h = r.histogram("st_test_seconds", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.5, 5.0):
        h.observe(v)
    snap = r.snapshot()
    assert snap["st_test_total"] == 5
    assert snap["st_test_gauge"] == 7.5
    hs = snap["st_test_seconds"]
    assert hs["count"] == 4
    assert hs["sum"] == pytest.approx(5.555)
    # cumulative bucket counts; the +Inf bucket is implicit == count
    assert hs["buckets"] == {0.01: 1, 0.1: 2, 1.0: 3}
    # same-name re-registration returns the same instrument; a kind
    # mismatch is an error, not a silent shadow
    assert r.counter("st_test_total") is c
    with pytest.raises(TypeError):
        r.gauge("st_test_total")


def test_registry_collector_and_prometheus_text():
    r = obs.Registry()
    r.counter("st_c_total", help="help text").inc(3)
    r.histogram("st_h_seconds", buckets=(0.1, 1.0)).observe(0.05)
    r.register_collector(lambda: {"st_pulled": 11})
    snap = r.snapshot()
    assert snap["st_pulled"] == 11
    text = r.prometheus_text()
    assert "# TYPE st_c_total counter" in text
    assert "st_c_total 3" in text
    assert "# HELP st_c_total help text" in text
    assert 'st_h_seconds_bucket{le="0.1"} 1' in text
    assert 'st_h_seconds_bucket{le="+Inf"} 1' in text
    assert "st_h_seconds_count 1" in text
    assert "st_pulled 11" in text
    # a collector that raises must not take the scrape down
    r.register_collector(lambda: 1 / 0)
    assert r.snapshot()["st_c_total"] == 3


def test_registry_jsonl_sink(tmp_path):
    r = obs.Registry()
    r.counter("st_s_total").inc(2)
    path = str(tmp_path / "metrics.jsonl")
    r.start_jsonl_sink(path, interval_sec=0.05)
    time.sleep(0.2)
    r.stop_jsonl_sink()
    lines = [json.loads(l) for l in open(path) if l.strip()]
    assert lines, "sink wrote nothing"
    assert all("t_ns" in l and l["metrics"]["st_s_total"] == 2 for l in lines)
    # timestamps are the shared monotonic timebase
    assert lines[-1]["t_ns"] <= time.monotonic_ns()


# ---------------------------------------------------------------------------
# schema
# ---------------------------------------------------------------------------


def test_schema_covers_real_metrics_shape():
    """Every key the REAL peer.metrics() serves must be documented in the
    schema (satellite: one documented namespace — there is no legacy
    alias surface left to hide a stray name in)."""
    port = _free_port()
    seed = jnp.zeros((4096,), jnp.float32)
    m = create_or_fetch("127.0.0.1", port, seed, _cfg())
    c = create_or_fetch("127.0.0.1", port, seed, _cfg())
    try:
        m.add(jnp.ones((4096,), jnp.float32))
        _wait(
            lambda: c.metrics()["st_frames_in_total"] > 0,
            msg="frames to flow",
        )
        full = m.metrics()
        assert full, "metrics() produced nothing"
        # every key is in the documented schema (per-link keys strip
        # their {link=} label first)
        for k in full:
            base = k.split("{", 1)[0]
            assert base in schema.SCHEMA, f"{k} not documented in SCHEMA"
        # the delivery taxonomy plus the engine aggregates and per-link
        # wire gauges all ride the one surface
        for must in (
            "st_frames_out_total",
            "st_msgs_out_total",
            "st_inflight_msgs",
            "st_tx_slot_acquires_total",
            "st_transport_tx_acquires_total",
            "st_retransmit_msgs_total",
            "st_ack_rtt_seconds_count",
        ):
            assert must in full, f"metrics() lost {must}"
        assert any(k.startswith("st_link_wire_msgs_out_total{") for k in full)
        # the registry's Prometheus rendering includes collector metrics
        if m._obs is not None:
            text = m._obs.registry.prometheus_text()
            assert "st_frames_out_total" in text
    finally:
        m.close()
        c.close()


def test_schema_link_key_and_legacy_surface_removed():
    """r13 satellite: the r08 nested alias surface is GONE — the schema
    module no longer carries an alias table, and asking metrics() for the
    legacy shape raises instead of silently serving stale names."""
    assert not hasattr(schema, "DEPRECATED_ALIASES")
    assert not hasattr(schema, "canonicalize")
    assert schema.link_key("st_link_send_queue", 3) == 'st_link_send_queue{link="3"}'


def test_schema_lint_every_emitted_st_name_is_documented():
    """r09 satellite, HARD GATE: grep-collect every ``st_*`` name emitted
    anywhere — quoted string literals across the Python package AND the
    native sources' string tables — and fail if one is missing from
    obs/schema.py. A new cluster metric cannot ship undocumented: adding
    an instrument/collector key without a SCHEMA row fails here, by name,
    with the file that emits it."""
    import pathlib
    import re

    repo = pathlib.Path(__file__).resolve().parent.parent
    pat = re.compile(r'["\'](st_[a-z0-9_]+)["\']')
    # Non-metric st_* literals, each with a reason. ABI symbol names appear
    # as ctypes attributes (not strings), so almost none are needed — keep
    # this list honest: every entry must still occur in the scan.
    allowed_non_metrics: dict[str, str] = {
        "st_trace": "Chrome trace_event category tag (trace_export.py)",
        "st_quantize_rows": "Pallas kernel name (ops/codec_pallas.py), shown in device traces",
        "st_apply_rows_batch": "Pallas kernel name (ops/codec_pallas.py), shown in device traces",
        "st_attn_fwd": "Pallas kernel name (ops/attention_pallas.py), shown in device traces",
        "st_attn_bwd": "Pallas kernel name (ops/attention_pallas.py), shown in device traces",
        "st_moe_combine": "Pallas kernel name (ops/moe_pallas.py), shown in device traces",
    }
    emitted: dict[str, set[str]] = {}
    sources = list((repo / "shared_tensor_tpu").rglob("*.py")) + [
        p
        for ext in ("*.c", "*.cpp", "*.h")
        for p in (repo / "native").glob(ext)
    ]
    assert sources, "scan found no sources"
    for path in sources:
        for name in pat.findall(path.read_text(errors="replace")):
            emitted.setdefault(name, set()).add(str(path.relative_to(repo)))
    assert emitted, "scan found no st_* literals (pattern rot?)"
    undocumented = {
        name: sorted(files)
        for name, files in emitted.items()
        if name not in schema.SCHEMA and name not in allowed_non_metrics
    }
    assert not undocumented, (
        f"st_* names emitted but missing from obs/schema.py SCHEMA: "
        f"{undocumented}"
    )
    stale_allow = set(allowed_non_metrics) - set(emitted)
    assert not stale_allow, f"allowlist entries no longer emitted: {stale_allow}"
    # sanity: the r09 cluster names are among what the scan found
    for must in ("st_staleness_seconds", "st_update_hops", "st_cluster_nodes"):
        assert must in emitted, f"scan missed {must}"


def test_legacy_metrics_shape_removed():
    """r13 satellite: the r08 nested alias shape was kept "for one
    release" and overstayed three — it is now REMOVED, loudly. The
    default call serves the canonical schema; explicitly asking for the
    legacy shape raises with a pointer to the schema, and the canonical/
    cluster surfaces behave identically to before."""
    port = _free_port()
    m = create_or_fetch("127.0.0.1", port, jnp.zeros((256,), jnp.float32), _cfg())
    try:
        m.add(jnp.ones((256,), jnp.float32))
        full = m.metrics()
        assert full == m.metrics(canonical=True)
        assert "st_frames_out_total" in full
        assert "frames_out" not in full  # the alias keys are truly gone
        assert "delivery" not in full
        with pytest.raises(ValueError, match="removed"):
            m.metrics(canonical=False)
        assert isinstance(m.metrics(cluster=True), dict)
    finally:
        m.close()


# ---------------------------------------------------------------------------
# native event ring
# ---------------------------------------------------------------------------


def test_native_ring_emit_drain_and_clock_agreement():
    lib = transport._load()
    # flush anything earlier tests left behind
    obs_events.drain_native(lib=lib)
    t_py = time.monotonic_ns()
    t_c = obs_events.native_now_ns(lib=lib)
    # same CLOCK_MONOTONIC timebase: the two reads are microseconds apart
    assert abs(t_c - t_py) < 250_000_000, (t_c, t_py)
    lib.st_obs_emit(42, 14, 3, 1234)
    lib.st_obs_emit(42, 10, 3, 2)
    evs = obs_events.drain_native(lib=lib)
    mine = [e for e in evs if e.node == 42]
    assert [e.name for e in mine] == ["dedup_discard", "retransmit"]
    assert mine[0].link == 3 and mine[0].arg == 1234
    assert all(e.tier == "c" for e in mine)
    # stamped between our two clock reads and now
    assert t_py - 1_000_000 <= mine[0].t_ns <= time.monotonic_ns()
    # drained means gone
    assert not [e for e in obs_events.drain_native(lib=lib) if e.node == 42]


def test_native_ring_codes_match_python_names():
    """The numeric codes are ABI shared between sttransport.cpp and
    obs/events.py — membership codes must equal transport.EventKind."""
    assert obs_events.CODE_NAMES[int(transport.EventKind.LINK_UP)] == "link_up"
    assert obs_events.CODE_NAMES[int(transport.EventKind.LINK_DOWN)] == "link_down"
    assert obs_events.CODE_NAMES[int(transport.EventKind.BECAME_MASTER)] == "became_master"
    assert obs_events.NAME_CODES["fault_drop"] == 20
    assert obs_events.EVENT_BYTES == 32


# ---------------------------------------------------------------------------
# merged timeline under chaos (flight recorder satellite)
# ---------------------------------------------------------------------------


def test_python_tier_chaos_timeline_accounts_every_injection():
    """Every injected drop/dup/sever appears in the merged timeline, with
    both-tier timestamps in sorted order (the satellite's exact bar). The
    chaotic joiner drops/dups on its first uplink and severs it at frame
    25; go-back-N + carry re-graft then reconverge exactly."""
    hub = _fresh_hub()
    port = _free_port()
    n = 512
    seed = jnp.zeros((n,), jnp.float32)
    fc = FaultConfig(
        enabled=True, seed=8, drop_pct=0.15, dup_pct=0.15,
        sever_after_frames=25, only_link=1,
    )
    m = create_or_fetch("127.0.0.1", port, seed, _cfg(engine=False))
    c = SharedTensorPeer(
        "127.0.0.1", port, seed, _cfg(fc, engine=False, ack_timeout_sec=0.5)
    )
    try:
        c.wait_ready(30.0)
        total = np.zeros(n, np.float64)
        rng = np.random.default_rng(0)
        for _ in range(40):
            d = rng.uniform(-0.5, 0.5, n).astype(np.float32)
            c.add(jnp.asarray(d))
            total += d
            time.sleep(0.01)
        plan = c._faults
        assert plan is not None
        # stop injecting, then wait for exact reconvergence (retransmission
        # + the sever's carry re-graft re-deliver everything)
        _wait(
            lambda: np.allclose(np.asarray(m.read()), total, atol=1e-4),
            timeout=60.0, msg="master to reconverge after chaos",
        )
        injected = {k: int(v) for k, v in plan.counts.items()}
        assert injected.get("severed", 0) >= 1, injected
        assert injected.get("dropped", 0) >= 1, injected
        assert injected.get("duplicated", 0) >= 1, injected
        hub.poll_native()
        counts = hub.recorder.counts
        # exact accounting: every injected event of the three classes is on
        # the timeline (the recorder's totals are not bounded by the window)
        assert counts["fault_drop"] == injected["dropped"], (counts, injected)
        assert counts["fault_dup"] == injected["duplicated"], (counts, injected)
        assert counts["fault_sever"] == injected["severed"], (counts, injected)
        timeline = hub.recorder.timeline()
        tiers = {e.tier for e in timeline}
        assert tiers == {"c", "py"}, tiers
        # merged order is time order across tiers
        ts = [e.t_ns for e in timeline]
        assert ts == sorted(ts)
        # the native LINK_UP precedes its Python-tier handling twin
        c_up = min(e.t_ns for e in timeline
                   if e.tier == "c" and e.name == "link_up")
        py_up = min(e.t_ns for e in timeline
                    if e.tier == "py" and e.name == "link_up")
        assert c_up < py_up
        # the sever's recovery left a trace too: the transport's link_down
        # and the re-graft's second link_up are on the same timeline
        assert counts["link_down"] >= 1
    finally:
        m.close()
        c.close()


def test_native_tier_chaos_events_reach_the_timeline(monkeypatch):
    """The NATIVE injector (ST_FAULT_PLAN, C sender loop) now reports every
    hit through the event ring: a drop schedule on the engine tier must
    surface fault_drop events — and the go-back-N retransmissions that
    repair them — in the merged timeline."""
    hub = _fresh_hub()
    port = _free_port()
    n = 4096
    seed = jnp.zeros((n,), jnp.float32)
    m = create_or_fetch(
        "127.0.0.1", port, seed, _cfg(ack_timeout_sec=0.5)
    )
    env = faults.to_env(FaultConfig(enabled=True, seed=9, drop_pct=0.3,
                                    only_link=1))
    monkeypatch.setenv("ST_FAULT_PLAN", env["ST_FAULT_PLAN"])
    c = SharedTensorPeer(
        "127.0.0.1", port, seed, _cfg(ack_timeout_sec=0.5)
    )
    monkeypatch.delenv("ST_FAULT_PLAN")
    try:
        c.wait_ready(30.0)
        total = np.zeros(n, np.float64)
        rng = np.random.default_rng(1)
        for _ in range(30):
            d = rng.uniform(-0.5, 0.5, n).astype(np.float32)
            c.add(jnp.asarray(d))
            total += d
            time.sleep(0.01)
        _wait(
            lambda: np.allclose(np.asarray(m.read()), total, atol=1e-4),
            timeout=60.0, msg="master to reconverge through native drops",
        )
        hub.poll_native()
        counts = hub.recorder.counts
        assert counts["fault_drop"] >= 1, dict(counts)
        assert counts["retransmit"] >= 1, dict(counts)
        timeline = hub.recorder.timeline()
        assert {e.tier for e in timeline} == {"c", "py"}
        drops = [e for e in timeline if e.name == "fault_drop"]
        assert all(e.tier == "c" and e.link == 1 for e in drops)
        ts = [e.t_ns for e in timeline]
        assert ts == sorted(ts)
    finally:
        m.close()
        c.close()


# ---------------------------------------------------------------------------
# postmortem dumps
# ---------------------------------------------------------------------------


def test_crash_point_dumps_postmortem(tmp_path, monkeypatch):
    """The default crash action dumps the flight recorder BEFORE os._exit
    — chaos deaths leave an explainable trace, not just exit code 17."""
    monkeypatch.setenv("ST_OBS_POSTMORTEM_DIR", str(tmp_path))
    exits = []
    monkeypatch.setattr(faults.os, "_exit", lambda code: exits.append(code))
    hub = _fresh_hub()
    hub.emit("link_up", node=1, link=1)
    reg = obs.Registry()
    reg.counter("st_test_total").inc(3)
    hub.register_registry("test-peer", reg)
    try:
        plan = faults.FaultPlan(
            FaultConfig(enabled=True, crash_point="mid-burst")
        )
        plan.point("mid-burst")
        assert exits == [faults.CRASH_EXIT_CODE]
        dumps = list(tmp_path.glob("st_postmortem_*crash_point*.json"))
        assert len(dumps) == 1, dumps
        doc = json.loads(dumps[0].read_text())
        assert doc["reason"] == "crash_point:mid-burst"
        assert doc["registries"]["test-peer"]["st_test_total"] == 3
        names = [e["name"] for e in doc["timeline"]]
        assert "link_up" in names and "crash_point" in names
        assert doc["event_counts"]["crash_point"] == 1
        # timeline entries carry the merged-clock timestamps, sorted
        ts = [e["t_ns"] for e in doc["timeline"]]
        assert ts == sorted(ts)
    finally:
        hub.unregister_registry("test-peer")


def test_recv_thread_exception_dumps_postmortem(tmp_path, monkeypatch):
    """An unhandled recv-thread exception (the wedged-peer class) dumps a
    postmortem and the loop restarts — the peer keeps working after."""
    monkeypatch.setenv("ST_OBS_POSTMORTEM_DIR", str(tmp_path))
    hub = _fresh_hub()
    port = _free_port()
    seed = jnp.zeros((256,), jnp.float32)
    m = create_or_fetch("127.0.0.1", port, seed, _cfg())
    try:
        boom = {"armed": True}
        orig = m._handle_events

        def exploding():
            if boom.pop("armed", False):
                raise RuntimeError("injected recv-thread failure")
            return orig()

        monkeypatch.setattr(m, "_handle_events", exploding)
        _wait(
            lambda: list(tmp_path.glob("st_postmortem_*recv_thread*")),
            timeout=15.0, msg="postmortem dump",
        )
        doc = json.loads(
            list(tmp_path.glob("st_postmortem_*recv_thread*"))[0].read_text()
        )
        assert doc["reason"] == "recv_thread_exception"
        # the guarded restart kept the peer alive
        assert m._recv_thread.is_alive()
    finally:
        m.close()


def test_goback_teardown_dumps_postmortem(tmp_path, monkeypatch):
    """A Python-tier black-hole teardown (zero ACK progress through every
    retransmission round) leaves a postmortem + timeline events."""
    monkeypatch.setenv("ST_OBS_POSTMORTEM_DIR", str(tmp_path))
    hub = _fresh_hub()
    port = _free_port()
    n = 256
    seed = jnp.zeros((n,), jnp.float32)
    # stall EVERY frame from the start: the ledger strands, the delivery
    # timer retransmits (stalled too), and the retry limit tears down
    fc = FaultConfig(enabled=True, stall_after_frames=0, only_link=1)
    m = create_or_fetch("127.0.0.1", port, seed, _cfg(engine=False))
    c = SharedTensorPeer(
        "127.0.0.1", port, seed,
        _cfg(fc, engine=False, ack_timeout_sec=0.2, ack_retry_limit=2),
    )
    try:
        c.wait_ready(30.0)
        c.add(jnp.ones((n,), jnp.float32))
        _wait(
            lambda: list(tmp_path.glob("st_postmortem_*goback*")),
            timeout=30.0, msg="teardown postmortem",
        )
        assert hub.recorder.counts["blackhole_teardown"] >= 1
        assert hub.recorder.counts["fault_stall"] >= 1
    finally:
        m.close()
        c.close()


# ---------------------------------------------------------------------------
# kill switch
# ---------------------------------------------------------------------------


def test_obs_disabled_is_inert():
    was = obs.obs_enabled()
    obs.set_enabled(False)
    try:
        hub = obs.hub()
        hub.recorder.clear()
        hub.emit("link_up", node=1)
        assert not hub.recorder.counts
        assert hub.dump("disabled-test") is None
        port = _free_port()
        m = create_or_fetch(
            "127.0.0.1", port, jnp.zeros((64,), jnp.float32), _cfg()
        )
        try:
            assert m._obs is None  # peer pays one None-check per site
            # the canonical metrics surface is independent of obs (the
            # collector serves the schema without a registry)
            assert "st_frames_out_total" in m.metrics()
        finally:
            m.close()
        # the native ring's emission flag was flipped too
        lib = transport._load()
        obs_events.drain_native(lib=lib)
        lib.st_obs_emit(99, 14, 1, 1)
        assert not [e for e in obs_events.drain_native(lib=lib) if e.node == 99]
    finally:
        obs.set_enabled(was)


def test_peer_obs_config_disabled():
    port = _free_port()
    cfg = Config(
        transport=TransportConfig(peer_timeout_sec=10.0),
        obs=ObsConfig(enabled=False),
    )
    m = create_or_fetch("127.0.0.1", port, jnp.zeros((64,), jnp.float32), cfg)
    try:
        assert m._obs is None
        # canonical view still works without a registry (pure schema map)
        assert "st_frames_out_total" in m.metrics(canonical=True)
    finally:
        m.close()


def test_jsonl_sink_config_wires_through(tmp_path):
    path = str(tmp_path / "peer_metrics.jsonl")
    port = _free_port()
    cfg = Config(
        transport=TransportConfig(peer_timeout_sec=10.0),
        obs=ObsConfig(jsonl_path=path, jsonl_interval_sec=0.05),
    )
    m = create_or_fetch("127.0.0.1", port, jnp.zeros((64,), jnp.float32), cfg)
    try:
        _wait(lambda: os.path.exists(path) and os.path.getsize(path) > 0,
              timeout=10.0, msg="jsonl sink output")
    finally:
        m.close()
    lines = [json.loads(l) for l in open(path) if l.strip()]
    assert lines and "st_frames_out_total" in lines[-1]["metrics"]


def test_corrupt_scale_counter():
    before = wire.corrupt_scales_zeroed()
    from shared_tensor_tpu.ops.table import make_spec

    spec = make_spec(np.zeros(64, np.float32))
    w = spec.total // 32
    frame = (
        b"\x00" + b"\x01\x00\x00\x00"
        + np.full(spec.num_leaves, np.inf, "<f4").tobytes()
        + b"\x00" * (4 * w)
    )
    f = wire.decode_frame(frame, spec)
    assert float(np.asarray(f.scales)[0]) == 0.0
    assert wire.corrupt_scales_zeroed() == before + 1
