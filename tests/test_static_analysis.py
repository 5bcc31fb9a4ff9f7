"""Cross-tier lints + the clang analyze build (r13 tentpole).

Each lint gets the r09 schema-lint negative-test discipline: it must pass
on the real tree AND fail, by name, on a seeded violation written to a
temp copy — a lint that cannot go red is decoration, not a gate. The
seeded trees copy only the files each lint reads (tools/lint_*.py parse
fixed relative paths under --repo).

The analyze smoke compiles all three native files under clang's
-Wthread-safety -Werror (the st_annotations.h contract) and runs the
checked-in .clang-tidy; both skip when clang is absent (this image ships
gcc only — the TSan arm in test_sanitizers.py is the dynamic half that
always runs).
"""

import pathlib
import shutil
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
TOOLS = REPO / "tools"
sys.path.insert(0, str(TOOLS))

import analyze_clang  # noqa: E402
import lint_abi  # noqa: E402
import lint_events  # noqa: E402
import lint_locks  # noqa: E402
import lint_metrics  # noqa: E402
import lint_spec  # noqa: E402
import lint_wire  # noqa: E402

#: every file any lint reads, relative to the repo root
_LINT_INPUTS = [
    "native/stengine.cpp",
    "native/sttransport.cpp",
    "shared_tensor_tpu/comm/wire.py",
    "shared_tensor_tpu/comm/engine.py",
    "shared_tensor_tpu/comm/transport.py",
    "shared_tensor_tpu/compat.py",
    "shared_tensor_tpu/obs/events.py",
    "shared_tensor_tpu/obs/schema.py",
    "shared_tensor_tpu/shard/node.py",
    "shared_tensor_tpu/shard/engine_lane.py",
    "shared_tensor_tpu/obs/health.py",
]


def _seed_tree(tmp_path: pathlib.Path, full_package: bool = False):
    root = tmp_path / "repo"
    for rel in _LINT_INPUTS:
        dst = root / rel
        dst.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(REPO / rel, dst)
    if full_package:  # lint_metrics rglobs the whole package + native/
        for src in (REPO / "shared_tensor_tpu").rglob("*.py"):
            rel = src.relative_to(REPO)
            dst = root / rel
            dst.parent.mkdir(parents=True, exist_ok=True)
            shutil.copy(src, dst)
        for ext in ("*.c", "*.cpp", "*.h"):
            for src in (REPO / "native").glob(ext):
                dst = root / "native" / src.name
                dst.parent.mkdir(parents=True, exist_ok=True)
                shutil.copy(src, dst)
    return root


def _edit(root: pathlib.Path, rel: str, old: str, new: str) -> None:
    p = root / rel
    text = p.read_text()
    assert old in text, f"seed-edit anchor missing from {rel}: {old!r}"
    p.write_text(text.replace(old, new))


def _cli(tool: str, repo: pathlib.Path) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, str(TOOLS / tool), "--repo", str(repo)],
        capture_output=True, text=True, timeout=120,
    )


# ---- green on the real tree (importable form + the CLI wiring) ------------


@pytest.mark.parametrize(
    "mod", [lint_abi, lint_wire, lint_events, lint_metrics, lint_locks]
)
def test_lint_passes_on_tree(mod):
    findings = mod.run(REPO)
    assert findings == [], findings


def test_lint_cli_green_exit_codes():
    for tool in ("lint_abi.py", "lint_wire.py", "lint_events.py",
                 "lint_metrics.py", "lint_locks.py"):
        r = _cli(tool, REPO)
        assert r.returncode == 0, (tool, r.stdout, r.stderr)
        assert "OK" in r.stdout


# ---- red on seeded violations ---------------------------------------------


def test_wire_lint_flags_renumbered_kind(tmp_path):
    root = _seed_tree(tmp_path)
    _edit(root, "native/stengine.cpp",
          "constexpr uint8_t kAck = 6;", "constexpr uint8_t kAck = 5;")
    findings = lint_wire.run(root)
    assert any("kAck" in f and "ACK" in f for f in findings), findings
    r = _cli("lint_wire.py", root)
    assert r.returncode == 1 and "kAck" in r.stdout


def test_wire_lint_flags_fault_injector_kind_set(tmp_path):
    # a data kind the fault injector no longer matches: chaos silently
    # stops covering it at the native wire boundary
    root = _seed_tree(tmp_path)
    _edit(root, "native/sttransport.cpp",
          "kind0 == 11", "kind0 == 7")
    findings = lint_wire.run(root)
    assert any("is_data" in f for f in findings), findings


def test_wire_lint_flags_fwd_missing_from_injector(tmp_path):
    # r16: the sharded tree's WHOLE data plane rides FWD frames — an
    # is_data set that loses kind 17 silently exempts every sharded
    # cluster from wire chaos
    root = _seed_tree(tmp_path)
    _edit(root, "native/sttransport.cpp",
          "kind0 == 17", "kind0 == 11")
    findings = lint_wire.run(root)
    assert any("is_data" in f for f in findings), findings


def test_wire_lint_flags_shard_hello_flag_drift(tmp_path):
    # r16: the shard capability bit's wire/compat twin declaration — a
    # drift silently degrades every sharded join to the full-replica
    # fallback (same class as the shm flag below)
    root = _seed_tree(tmp_path)
    _edit(root, "shared_tensor_tpu/compat.py",
          "SYNC_FLAG_SHARD = 0x10", "SYNC_FLAG_SHARD = 0x20")
    findings = lint_wire.run(root)
    assert any("SYNC_FLAG_SHARD" in f and "SHARD_FLAG" in f
               for f in findings), findings


def test_wire_lint_flags_fwd_header_drift(tmp_path):
    # r16: FWD's fixed header (kind + five u32) — a drifted constant
    # desyncs decode_fwd's length check and fwd_restamp's offset
    root = _seed_tree(tmp_path)
    _edit(root, "shared_tensor_tpu/comm/wire.py",
          "FWD_HDR = 21", "FWD_HDR = 25")
    findings = lint_wire.run(root)
    assert any("FWD_HDR" in f for f in findings), findings


def test_abi_lint_flags_shard_queue_depth_drift(tmp_path):
    # r16: the transport send-queue depth is declared three times (native
    # config default, TransportNode default, shard/node.py QUEUE_DEPTH);
    # the shard pump's control-traffic headroom math reads the last one,
    # and a drift re-opens the ACK-starvation wedge
    root = _seed_tree(tmp_path)
    _edit(root, "shared_tensor_tpu/shard/node.py",
          "QUEUE_DEPTH = 8", "QUEUE_DEPTH = 4")
    findings = lint_abi.run(root)
    assert any("queue-depth drift" in f for f in findings), findings


def test_wire_lint_flags_shard_fwd_kind_drift(tmp_path):
    # r17: the engine-tier shard plane re-declares wire.FWD as kFwd — a
    # renumbered kind makes the native receiver treat every FWD as an
    # unknown control message (whole data plane deferred to Python)
    root = _seed_tree(tmp_path)
    _edit(root, "native/stengine.cpp",
          "constexpr uint8_t kFwd = 17;", "constexpr uint8_t kFwd = 18;")
    findings = lint_wire.run(root)
    assert any("kFwd" in f and "FWD" in f for f in findings), findings


def test_wire_lint_flags_shard_fwd_header_drift(tmp_path):
    # r17: kFwdHdr is the verbatim relay's restamp geometry — a size
    # drift shifts the re-stamped seq into the word_lo field
    root = _seed_tree(tmp_path)
    _edit(root, "native/stengine.cpp",
          "constexpr size_t kFwdHdr = 21;", "constexpr size_t kFwdHdr = 25;")
    findings = lint_wire.run(root)
    assert any("kFwdHdr" in f for f in findings), findings


def test_abi_lint_flags_shard_counter_width_drift(tmp_path):
    # r17: the st_shard_counters out-array widening class (the exact
    # st_engine_counters 8->22 history, now on the shard plane's ABI):
    # a python buffer narrower than the native out14 promise reads
    # garbage past the allocation
    root = _seed_tree(tmp_path)
    _edit(root, "shared_tensor_tpu/shard/engine_lane.py",
          "out = np.zeros(14, np.uint64)", "out = np.zeros(12, np.uint64)")
    findings = lint_abi.run(root)
    assert any("st_shard_counters" in f and "14" in f
               for f in findings), findings


def test_abi_lint_flags_shard_abi_signature_drift(tmp_path):
    # r17: a dropped argtypes parameter on the shard ABI reads stack
    # garbage (the silent-mismatch class the lint exists for)
    root = _seed_tree(tmp_path)
    _edit(root, "shared_tensor_tpu/shard/engine_lane.py",
          "lib.st_shard_member_attach.argtypes = [\n"
          "        ctypes.c_void_p, ctypes.c_int32, ctypes.c_uint64, ctypes.c_uint64,\n"
          "    ]",
          "lib.st_shard_member_attach.argtypes = [\n"
          "        ctypes.c_void_p, ctypes.c_int32, ctypes.c_uint64,\n"
          "    ]")
    findings = lint_abi.run(root)
    assert any("st_shard_member_attach" in f and "count" in f
               for f in findings), findings


def test_wire_lint_flags_v3_header_drift(tmp_path):
    # r14: the aligned v3 header is ONE size on both tiers; a drifted
    # kHdrV3 makes every exact-length framing test reject v3 messages
    root = _seed_tree(tmp_path)
    _edit(root, "native/stengine.cpp",
          "constexpr size_t kHdrV3 = 24;", "constexpr size_t kHdrV3 = 32;")
    findings = lint_wire.run(root)
    assert any("kHdrV3" in f and "HDR_V3" in f for f in findings), findings


def test_wire_lint_flags_switch_marker_drift(tmp_path):
    # r14: the in-stream SWITCH marker length — a drift means an
    # upgraded receiver parses the marker as a (huge) frame length
    root = _seed_tree(tmp_path)
    _edit(root, "native/sttransport.cpp",
          "constexpr uint32_t kShmSwitchLen = 0xFFFFFFFDu;",
          "constexpr uint32_t kShmSwitchLen = 0xFFFFFFFEu;")
    findings = lint_wire.run(root)
    assert any("kShmSwitchLen" in f for f in findings), findings


def test_wire_lint_flags_sendmmsg_batch_drift(tmp_path):
    root = _seed_tree(tmp_path)
    _edit(root, "native/sttransport.cpp",
          "constexpr int kCoalesce = 16;", "constexpr int kCoalesce = 64;")
    findings = lint_wire.run(root)
    assert any("kCoalesce" in f and "SENDMMSG_BATCH" in f
               for f in findings), findings


def test_wire_lint_flags_shm_hello_flag_drift(tmp_path):
    # the wire/compat twin declaration: the runtime assert catches this
    # on import, but the lint must catch it statically (a seeded tree is
    # never imported — and neither is a broken branch in CI until the
    # suite runs)
    root = _seed_tree(tmp_path)
    _edit(root, "shared_tensor_tpu/compat.py",
          "SYNC_FLAG_SHM = 0x08", "SYNC_FLAG_SHM = 0x10")
    findings = lint_wire.run(root)
    assert any("SYNC_FLAG_SHM" in f and "SHM_FLAG" in f
               for f in findings), findings


def test_event_lint_flags_unknown_and_drifted_code(tmp_path):
    root = _seed_tree(tmp_path)
    # stengine re-declares kEvQuarantine; renumbering it yields BOTH an
    # unknown code and a cross-file drift — the lint must name both
    _edit(root, "native/stengine.cpp",
          "constexpr uint32_t kEvQuarantine = 12;",
          "constexpr uint32_t kEvQuarantine = 55;")
    findings = lint_events.run(root)
    assert any("55" in f and "CODE_NAMES" in f for f in findings), findings
    assert any("drifted" in f for f in findings), findings


def test_event_lint_flags_renamed_shm_event(tmp_path):
    # r14: the shm chaos tallies key on the EXACT names shm_lane_up /
    # shm_fallback — a rename keeps the numeric code valid (no unknown-
    # code finding) yet silently zeroes every tally; the lint must red
    root = _seed_tree(tmp_path)
    _edit(root, "shared_tensor_tpu/obs/events.py",
          '34: "shm_lane_up"', '34: "shm_lane_went_up"')
    findings = lint_events.run(root)
    assert any("shm_lane_up" in f for f in findings), findings


def test_event_lint_flags_renamed_health_event(tmp_path):
    # r18: the fleet_health bench tallies key on the EXACT names in
    # HEALTH_EVENT_NAMES — a rename on the declaring side must red
    root = _seed_tree(tmp_path)
    _edit(root, "shared_tensor_tpu/obs/events.py",
          '"slo_alert_fire"', '"slo_alert_fired"')
    findings = lint_events.run(root)
    assert any("slo_alert_fire" in f for f in findings), findings


def test_event_lint_flags_health_emit_outside_set(tmp_path):
    # r18, the other direction: the analyzer emitting an event name the
    # pinned set does not know means nothing downstream can tally it
    root = _seed_tree(tmp_path)
    _edit(root, "shared_tensor_tpu/obs/health.py",
          'self._event(\n                "hot_shard"',
          'self._event(\n                "hot_shard_named"')
    findings = lint_events.run(root)
    assert any("hot_shard_named" in f and "HEALTH_EVENT_NAMES" in f
               for f in findings), findings


def test_abi_lint_flags_dropped_shm_declaration(tmp_path):
    # r14 bidirectional-family rule: a native st_node_shm_* entry point
    # with no ctypes declaration = the lane silently never negotiates
    root = _seed_tree(tmp_path)
    _edit(root, "shared_tensor_tpu/comm/transport.py",
          "lib.st_node_shm_join.restype", "lib.st_node_shm_join_x.restype")
    _edit(root, "shared_tensor_tpu/comm/transport.py",
          "lib.st_node_shm_join.argtypes",
          "lib.st_node_shm_join_x.argtypes")
    findings = lint_abi.run(root)
    assert any(
        "st_node_shm_join" in f and "bidirectional" in f for f in findings
    ), findings
    # ...and the renamed python-side declaration is itself flagged as
    # having no native definition (the pre-existing direction)
    assert any("st_node_shm_join_x" in f for f in findings), findings


def test_abi_lint_flags_shm_stats_width_drift(tmp_path):
    # the out-array discipline covers the new shm stats: native writing
    # past the promised out8 width must red exactly like st_engine_counters
    root = _seed_tree(tmp_path)
    _edit(root, "native/sttransport.cpp",
          "out8[7] = sl->rx_waits.load();",
          "out8[7] = sl->rx_waits.load();\n  out8[8] = 0;")
    findings = lint_abi.run(root)
    assert any(
        "st_node_shm_stats" in f and "out8" in f for f in findings
    ), findings


def test_abi_lint_flags_narrowed_counter_buffer(tmp_path):
    # the recurring widening class: native writes out22[21], python
    # allocates fewer slots -> garbage reads beyond the buffer
    root = _seed_tree(tmp_path)
    _edit(root, "shared_tensor_tpu/comm/engine.py",
          "out = np.zeros(22, np.uint64)", "out = np.zeros(18, np.uint64)")
    findings = lint_abi.run(root)
    assert any("st_engine_counters" in f and "18" in f for f in findings), (
        findings
    )


def test_abi_lint_flags_dropped_argtype(tmp_path):
    root = _seed_tree(tmp_path)
    _edit(root, "shared_tensor_tpu/comm/engine.py",
          "ctypes.c_void_p, ctypes.c_int32, ctypes.c_void_p,\n"
          "            ctypes.c_int32, ctypes.c_uint64,",
          "ctypes.c_void_p, ctypes.c_int32, ctypes.c_void_p,\n"
          "            ctypes.c_int32,")
    findings = lint_abi.run(root)
    assert any(
        "st_engine_attach" in f and "count" in f for f in findings
    ), findings


def test_abi_lint_flags_retyped_struct_field(tmp_path):
    root = _seed_tree(tmp_path)
    _edit(root, "shared_tensor_tpu/comm/transport.py",
          '("bandwidth_cap_bps", ctypes.c_int64)',
          '("bandwidth_cap_bps", ctypes.c_int32)')
    findings = lint_abi.run(root)
    assert any("StConfigC" in f for f in findings), findings


def test_metrics_lint_flags_undocumented_name(tmp_path):
    root = _seed_tree(tmp_path, full_package=True)
    _edit(root, "shared_tensor_tpu/comm/peer.py",
          "def metrics(",
          'UNDOC = "st_totally_undocumented_metric"\n    def metrics(')
    findings = lint_metrics.run(root)
    assert any("st_totally_undocumented_metric" in f for f in findings), (
        findings
    )


def test_metrics_lint_flags_legacy_alias_reintroduction(tmp_path):
    root = _seed_tree(tmp_path, full_package=True)
    _edit(root, "shared_tensor_tpu/comm/peer.py",
          "def metrics(",
          'LEGACY = {"frames_out": 0}\n    def metrics(')
    findings = lint_metrics.run(root)
    assert any("frames_out" in f and "legacy" in f for f in findings), (
        findings
    )


def test_metrics_lint_flags_dynamic_fstring_name(tmp_path):
    # r15: a dynamically-built st_* name never appears verbatim in any
    # source line, so the literal grep is blind to it — the emitted
    # metric ships undocumented. The f-string form is the one the
    # labeled-gauge code would most naturally grow into.
    root = _seed_tree(tmp_path, full_package=True)
    _edit(root, "shared_tensor_tpu/comm/peer.py",
          "def metrics(",
          'DYN = f"st_dyn_gauge_{0}"\n    def metrics(')
    findings = lint_metrics.run(root)
    assert any(
        "st_dyn_gauge_" in f and "dynamically-built" in f for f in findings
    ), findings


def test_metrics_lint_flags_dynamic_concat_name(tmp_path):
    root = _seed_tree(tmp_path, full_package=True)
    _edit(root, "shared_tensor_tpu/comm/peer.py",
          "def metrics(",
          'DYN = "st_dyn_" + "suffix"\n    def metrics(')
    findings = lint_metrics.run(root)
    assert any(
        "st_dyn_" in f and "dynamically-built" in f for f in findings
    ), findings


def test_locks_lint_flags_blocking_send_under_ledger_lock(tmp_path):
    # the deadlock shape r13's native annotations forbid, at the python
    # tier: a blocking wire send under _ack_mu — the recv thread pops
    # ACKs under the same lock, so a full send buffer can never drain
    root = _seed_tree(tmp_path, full_package=True)
    _edit(root, "shared_tensor_tpu/comm/peer.py",
          "with self._ack_mu:\n            mo = sum(self._acked.values())",
          "with self._ack_mu:\n"
          "            self._send_blocking(1, b'x')\n"
          "            mo = sum(self._acked.values())")
    findings = lint_locks.run(root)
    assert any(
        "_send_blocking" in f and "_ack_mu" in f for f in findings
    ), findings


def test_locks_lint_flags_engine_abi_call_under_lock(tmp_path):
    root = _seed_tree(tmp_path, full_package=True)
    _edit(root, "shared_tensor_tpu/comm/peer.py",
          "with self._ack_mu:\n            mo = sum(self._acked.values())",
          "with self._ack_mu:\n"
          "            self._engine.pause(True)\n"
          "            mo = sum(self._acked.values())")
    findings = lint_locks.run(root)
    assert any(
        "engine-ABI" in f and "_ack_mu" in f for f in findings
    ), findings


def test_locks_lint_skips_closures_under_lock(tmp_path):
    # a closure DEFINED under a lock runs later — flagging it would
    # make the lint unadoptable (callbacks are registered under locks
    # all over the obs tier)
    root = _seed_tree(tmp_path, full_package=True)
    _edit(root, "shared_tensor_tpu/comm/peer.py",
          "with self._ack_mu:\n            mo = sum(self._acked.values())",
          "with self._ack_mu:\n"
          "            cb = lambda: self._send_blocking(1, b'x')\n"
          "            mo = sum(self._acked.values())")
    findings = lint_locks.run(root)
    assert findings == [], findings


# ---- spec/mutation registry drift lint (r19) ------------------------------


def _seed_spec_tree(tmp_path: pathlib.Path) -> pathlib.Path:
    """Everything lint_spec reads: the spec modules, the committed MODEL
    artifacts, and README's mutation table."""
    root = tmp_path / "repo"
    (root / "tools" / "protospec").mkdir(parents=True)
    for src in (REPO / "tools" / "protospec").glob("spec_*.py"):
        shutil.copy(src, root / "tools" / "protospec" / src.name)
    for src in REPO.glob("MODEL_r*.json"):
        shutil.copy(src, root / src.name)
    shutil.copy(REPO / "README.md", root / "README.md")
    return root


def test_spec_lint_green_on_tree():
    assert lint_spec.run(REPO) == []
    r = _cli("lint_spec.py", REPO)
    assert r.returncode == 0 and "OK" in r.stdout, (r.stdout, r.stderr)


def test_spec_lint_flags_phantom_mutation(tmp_path):
    # a MODEL artifact citing a mutation the spec no longer codes: the
    # committed red-team coverage claim would be a lie
    import json
    root = _seed_spec_tree(tmp_path)
    p = root / "MODEL_r19.json"
    doc = json.loads(p.read_text())
    doc["mutations"]["reshard_split.ghost_never_coded"] = (
        doc["mutations"]["reshard_split.split_during_fwd"]
    )
    p.write_text(json.dumps(doc))
    findings = lint_spec.run(root)
    assert any(
        "phantom mutation" in f and "ghost_never_coded" in f
        for f in findings
    ), findings
    r = _cli("lint_spec.py", root)
    assert r.returncode == 1 and "ghost_never_coded" in r.stdout


def test_spec_lint_flags_phantom_spec(tmp_path):
    import json
    root = _seed_spec_tree(tmp_path)
    p = root / "MODEL_r19.json"
    doc = json.loads(p.read_text())
    doc["mutations"]["reshard_teleport.any_mutation"] = (
        doc["mutations"]["reshard_split.split_during_fwd"]
    )
    p.write_text(json.dumps(doc))
    findings = lint_spec.run(root)
    assert any(
        "phantom spec" in f and "reshard_teleport" in f for f in findings
    ), findings


def test_spec_lint_flags_undocumented_mutation(tmp_path):
    # a coded mutation README never cites: invisible red-team coverage —
    # seeded as a new Spec subclass so the dict-literal arm is exercised
    root = _seed_spec_tree(tmp_path)
    p = root / "tools" / "protospec" / "spec_reshard.py"
    p.write_text(
        p.read_text()
        + "\n\nclass _SeededSpec(Spec):\n"
        + '    name = "reshard_seeded"\n'
        + '    mutations = {"sneaky_uncited_mutation": None}\n'
    )
    findings = lint_spec.run(root)
    assert any(
        "undocumented mutation" in f
        and "reshard_seeded.sneaky_uncited_mutation" in f
        for f in findings
    ), findings


def test_spec_lint_resolves_dict_extension_idiom():
    # shard_engine extends shard's mutations via dict(Base.mutations,
    # extra=...) — the static resolution must see through it (the tree
    # being green already proves the base keys; pin the extension key)
    registry, findings = lint_spec._coded_registry(REPO)
    assert findings == []
    assert "relay_restamp_identity" in registry["shard_engine"]
    assert "no_dedup_transfer" in registry["shard_engine"]
    assert "split_during_fwd" in registry["reshard_split"]


# ---- libclang thread-safety gate (r19, probe-gated) -----------------------

_LIBCLANG_REASON = analyze_clang.probe()


@pytest.mark.skipif(
    _LIBCLANG_REASON is not None, reason=str(_LIBCLANG_REASON)
)
def test_analyze_clang_green_on_tree():
    """The r13 -Wthread-safety contract, actually executed: all three
    native TUs parse clean under the libclang front-end."""
    assert analyze_clang.run(REPO) == []


@pytest.mark.skipif(
    _LIBCLANG_REASON is not None, reason=str(_LIBCLANG_REASON)
)
def test_analyze_clang_flags_unguarded_access(tmp_path):
    # drop the lock guard around a ST_GUARDED_BY(mu) field init — the
    # gate must red on the exact class it exists for
    root = _seed_tree(tmp_path, full_package=True)
    _edit(root, "native/stengine.cpp",
          "    StLockGuard lk(e->mu);\n    e->values.assign",
          "    e->values.assign")
    findings = analyze_clang.run(root)
    assert any(
        "values" in f and ("warning" in f or "error" in f)
        for f in findings
    ), findings


def test_analyze_clang_probe_cli_is_honest():
    r = subprocess.run(
        [sys.executable, str(TOOLS / "analyze_clang.py"), "--probe"],
        capture_output=True, text=True, timeout=60,
    )
    if _LIBCLANG_REASON is None:
        assert r.returncode == 0 and "usable" in r.stdout
    else:
        # the SKIPPED path must print the provisioning command, not
        # silently pass
        assert r.returncode == 1 and "pip install libclang" in r.stdout


# ---- clang analyze / clang-tidy smoke (skipped without clang) -------------


def _have(tool: str) -> bool:
    return shutil.which(tool) is not None


@pytest.mark.skipif(not _have("clang"), reason="clang not installed")
def test_native_analyze_build_is_clean():
    """All three native files must compile clean under
    -Wthread-safety -Werror — the st_annotations.h lock contract is a
    build gate wherever clang exists, not documentation."""
    r = subprocess.run(
        ["make", "-C", str(REPO / "native"), "analyze"],
        capture_output=True, text=True, timeout=300,
    )
    assert r.returncode == 0, r.stdout + r.stderr


@pytest.mark.skipif(not _have("clang-tidy"), reason="clang-tidy not installed")
def test_native_clang_tidy_is_clean():
    r = subprocess.run(
        ["make", "-C", str(REPO / "native"), "tidy"],
        capture_output=True, text=True, timeout=600,
    )
    assert r.returncode == 0, r.stdout + r.stderr


def test_tsan_supp_entries_are_justified():
    """The suppressions file's target state is empty; any entry must carry
    the written (a)/(b)/(c) justification block the header demands."""
    text = (REPO / "native" / "tsan.supp").read_text()
    entries = [
        l for l in text.splitlines()
        if l.strip() and not l.strip().startswith("#")
    ]
    for entry in entries:
        kind, _, pat = entry.partition(":")
        assert kind in ("race", "mutex", "signal", "deadlock", "thread",
                        "called_from_lib"), f"malformed suppression {entry!r}"
        # justification discipline: the pattern must be discussed in a
        # comment block naming report, reason and removal condition
        assert pat.strip() in text.split(entry)[0], (
            f"suppression {entry!r} has no written justification above it"
        )
    # the file documents the policy itself
    assert "TARGET STATE: EMPTY" in text


@pytest.mark.parametrize("kernel", ["quantize_rows", "apply_rows_batch"])
def test_codec_kernel_has_one_caller(kernel):
    """The row kernels' operands are built in one place, ops/table.py's row
    codec: a change to what a kernel takes is a change to one module."""
    import ast

    callers = []
    for path in sorted((REPO / "shared_tensor_tpu").rglob("*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.ImportFrom) and (node.module or "").endswith("codec_pallas"):
                assert kernel not in [a.name for a in node.names], path
            if (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == kernel
                and isinstance(node.func.value, ast.Name)
                and node.func.value.id == "codec_pallas"
            ):
                callers.append(f"{path.relative_to(REPO)}:{node.lineno}")
    assert len(callers) == 1 and callers[0].startswith("shared_tensor_tpu/ops/table.py:"), callers
