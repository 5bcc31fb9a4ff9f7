"""7-node cluster observability chaos run (r09 acceptance artifact).

Builds a 7-node loopback tree (binary fan-out, native-engine tier), puts a
deterministic ST_FAULT_PLAN drop schedule under ONE node's C sender, and
streams multi-origin updates (root + the chaotic deep leaf) through the
chaos. After exact reconvergence and a full drain, it asserts the r09
acceptance bar:

- **trace-path contiguity**: >= 99% of delivered update generations
  reconstruct a contiguous hop path from the trace_apply records (a node
  only re-stamps hop k+1 after applying hop k, so a gap means lost
  telemetry — ring overflow, which the artifact also reports);
- **digest exactness**: after bottom-up digest pushes at the quiesced
  instant, the root's cluster totals equal the SUM of the 7 per-node
  registries EXACTLY for every quiesce-stable counter;
- chaos actually fired (injected drops >= 1) and was repaired
  (retransmits >= 1, exact convergence).

Also exports the run's merged timeline as a Perfetto-loadable Chrome
trace, optional via ST_CLUSTER_TRACE_OUT.

r10 ``--subscribers N`` arm: N read-only serve-tier leaves graft DIRECTLY
under the chaotic node (whose drop schedule then covers their unledgered
links too — ``only_link=0``). The serving contract under chaos: reads
either verify their ``max_staleness`` bound or raise (never silently
stale), a swallowed delta is a seq gap repaired by resync, and the WRITER
tree is never wedged by any of it (exact convergence + full drain with the
subscribers attached). Emits the subscriber tallies alongside the r09
telemetry checks.

r12 ``--kill-restore`` arm (the cluster-lifecycle acceptance artifact):
mid-soak — updates still in flight under the chaotic node's 25% drop
schedule — the root takes a consistent-cut snapshot (the barrier
completes THROUGH the chaos: markers/acks ride the control plane, which
the r06 rule keeps outside every chaos class), then the WHOLE tree is
killed, restarted from its shards (one node deliberately restarted with
v1 wire emission — the version-skew chaos arm: old and new nodes must
interop mid-upgrade), soaked further under the same chaos, and compared
against an UNINTERRUPTED arm that applies the identical add schedule.
Gates: the restored tree re-converges to the pre-kill mass inside
ST_RESTORE_BUDGET_S (default 45 s), both arms' final replicas agree
within the chaos-proportional bound (drop chaos + go-back-N converge
exactly, so the bound is float-accumulation slack), the snapshot barrier
itself stays sub-budget, chaos fired and was repaired in the restored
tree, and the version skew was real (mixed st_wire_version mid-restart).
Writes CHAOS_r12.json; wired into suite_load.sh as the lifecycle gate.

r11 ``--stripes N`` arm: every link in the tree runs striped over N
sockets, and the chaotic node's plan SEVERS ONE STRIPE SOCKET of its
uplink mid-stream (``only_stripe`` + ``sever_after_frames`` on top of the
drop schedule) — the striping contract under chaos: the link must degrade
to the surviving stripes (stripe_stats deaths >= 1 with the link still
converging) or, if reassembly wedged on a swallowed stripe seq, take the
clean go-back-N black-hole teardown into carry/re-graft — either way the
tree reaches the exact total; a wedged link shows up as a convergence
timeout and fails the run. Stripe telemetry (deaths, reroutes, live vs
negotiated counts) is tallied in the artifact.

r14 ``--shm`` arm (implies kill-restore): the 7-node tree runs with every
writer link's data plane on same-host SHARED-MEMORY rings (the r14 lane —
the normal state of a loopback cluster), under the same 25% drop schedule
and whole-tree kill-restore. On top of the r12 gates it asserts the lanes
were actually LIVE (st_shm_active == 2 at both ends of every writer link,
real ring traffic) before the kill AND after the restart's from-scratch
re-negotiation, and that the root's in-band digest is EXACT at the
post-restore quiesce — the lane sits below the wire-seq layer, so no
counter the digest aggregates may drift because of it.

Emits one JSON document and writes it to argv[1] (default CHAOS_r09.json).
Run:  JAX_PLATFORMS=cpu python benchmarks/cluster_chaos.py CHAOS_r09.json
      JAX_PLATFORMS=cpu python benchmarks/cluster_chaos.py CHAOS_r10.json \
          --subscribers 2
      JAX_PLATFORMS=cpu python benchmarks/cluster_chaos.py CHAOS_r11.json \
          --stripes 4
      JAX_PLATFORMS=cpu python benchmarks/cluster_chaos.py CHAOS_r14.json \
          --shm
Knobs: ST_CLUSTER_NODES (default 7), ST_CLUSTER_N (2048),
ST_CLUSTER_ADDS (40), ST_CLUSTER_SEED (9), ST_CLUSTER_SUBSCRIBERS (0),
ST_CLUSTER_STRIPES (1), ST_CLUSTER_SHM (0).
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

NODES = int(os.environ.get("ST_CLUSTER_NODES", "7"))
N = int(os.environ.get("ST_CLUSTER_N", "2048"))
ADDS = int(os.environ.get("ST_CLUSTER_ADDS", "40"))
SEED = int(os.environ.get("ST_CLUSTER_SEED", "9"))
SUBS = int(os.environ.get("ST_CLUSTER_SUBSCRIBERS", "0"))
if "--subscribers" in sys.argv:
    i = sys.argv.index("--subscribers")
    SUBS = int(sys.argv[i + 1])
    del sys.argv[i : i + 2]
STRIPES = int(os.environ.get("ST_CLUSTER_STRIPES", "1"))
if "--stripes" in sys.argv:
    i = sys.argv.index("--stripes")
    STRIPES = int(sys.argv[i + 1])
    del sys.argv[i : i + 2]
KILL_RESTORE = os.environ.get("ST_CLUSTER_KILL_RESTORE", "0") == "1"
if "--kill-restore" in sys.argv:
    KILL_RESTORE = True
    sys.argv.remove("--kill-restore")
# r14 ``--shm`` arm: the kill-restore chaos run additionally ASSERTS the
# same-host shm lanes are live across the whole tree (every writer link's
# data plane on rings, real shm message traffic), and that the root's
# in-band digest is EXACT at the post-restore quiesce — the lane must be
# invisible to every counter the digest aggregates. Implies kill-restore.
SHM_ARM = os.environ.get("ST_CLUSTER_SHM", "0") == "1"
if "--shm" in sys.argv:
    SHM_ARM = True
    sys.argv.remove("--shm")
if SHM_ARM:
    KILL_RESTORE = True
# r16 ``--sharded`` arm: the 7-node tree runs the CLUSTER-SHARDED tensor
# (shared_tensor_tpu/shard — one shard per node, owner-routed FWD frames
# instead of the flood) under the same 25% drop schedule, kill-restore
# included via the sharded checkpoint path. The acceptance bar it gates:
# a model >= ST_SHARD_FACTOR x bigger than any single node's allowance
# converges EXACTLY under the chaos (the per-node alloc bound is
# enforced at every sample throughout the soak), and per-node
# steady-state resident memory is ~1/N of the full-replica arm's
# (structurally: a full replica is the whole table per node).
SHARDED_ARM = os.environ.get("ST_CLUSTER_SHARDED", "0") == "1"
if "--sharded" in sys.argv:
    SHARDED_ARM = True
    sys.argv.remove("--sharded")
#: Sharded-arm table size (elements) and the memory factor: the model is
#: FACTOR x bigger than the per-node alloc allowance (the ISSUE's N >= 2).
SHARD_N = int(os.environ.get("ST_SHARD_N", "16384"))
SHARD_FACTOR = int(os.environ.get("ST_SHARD_FACTOR", "2"))
#: Wall-clock budget for the full-cluster restore: first restarted create
#: to every node re-converged on the pre-kill mass.
RESTORE_BUDGET_S = float(os.environ.get("ST_RESTORE_BUDGET_S", "45"))
#: Snapshot-barrier budget (marker flood + drain-to-quiesce + shard I/O).
SNAP_BUDGET_S = float(os.environ.get("ST_SNAP_BUDGET_S", "30"))
# frames the chaotic node's targeted stripe carries before its sever fires
# (one constant: both the injected FaultConfig and the artifact cite it)
SEVER_AFTER = 4
#: Staleness bound subscriber reads must verify (or raise) under chaos.
SUB_BOUND = float(os.environ.get("ST_CLUSTER_SUB_BOUND", "0.75"))

STABLE_COUNTERS = (
    "st_frames_out_total", "st_frames_in_total", "st_updates_total",
    "st_msgs_out_total", "st_msgs_in_total",
    "st_retransmit_msgs_total", "st_dedup_discards_total",
    "st_traced_msgs_in_total",
)


def _free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _conformance(hub) -> dict:
    """r15 trace-conformance gate: drain the native ring one last time
    and replay the run's merged timeline through the protocol specs'
    trace acceptors (tools/protospec). The explorer proves the model;
    this proves the live run still matches the model — a violation here
    fails the chaos arm exactly like a convergence failure would.
    ST_CLUSTER_TIMELINE_OUT additionally pins the raw timeline to a
    file (the committed conformance regression fixtures)."""
    sys.path.insert(
        0,
        os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools",
        ),
    )
    from protospec.conformance import check_timeline

    hub.poll_native()
    timeline_out = os.environ.get("ST_CLUSTER_TIMELINE_OUT", "")
    if timeline_out:
        hub.export_timeline(timeline_out)
    report = check_timeline(hub.recorder.timeline())
    if timeline_out:
        report["timeline_out"] = timeline_out
    return report


def run_kill_restore(art_path: str) -> int:
    """The r12 lifecycle acceptance arm (module docstring)."""
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    jax.config.update("jax_platforms", "cpu")

    from shared_tensor_tpu import obs
    from shared_tensor_tpu.comm import faults
    from shared_tensor_tpu.comm.peer import create_or_fetch
    from shared_tensor_tpu.config import (
        Config, FaultConfig, LifecycleConfig, ObsConfig, TransportConfig,
    )

    # full-run event capture for the r15 trace-conformance gate (the
    # default postmortem window would roll early barrier events out and
    # fake pause/resume imbalances)
    hub = obs.hub()
    hub.poll_native()
    hub.recorder.clear()
    hub.recorder.set_capacity(500_000)

    chaos_idx = NODES - 1
    skew_idx = 1  # restarted with v1 emission (the version-skew arm)
    seed = jnp.zeros((N,), jnp.float32)
    env = faults.to_env(
        FaultConfig(enabled=True, seed=SEED, drop_pct=0.25, only_link=1)
    )
    rng = np.random.default_rng(SEED)
    # ONE add schedule, shared by both arms: phase 1 (pre-snapshot) and
    # phase 2 (post-restore). The uninterrupted arm applies the identical
    # deltas, so "same mass as an uninterrupted run" is a pairwise replica
    # comparison, not just totals.
    p1 = [rng.uniform(-0.5, 0.5, N).astype(np.float32) for _ in range(ADDS)]
    p2 = [
        rng.uniform(-0.5, 0.5, N).astype(np.float32)
        for _ in range(max(4, ADDS // 2))
    ]
    total1 = np.sum(p1, axis=0, dtype=np.float64)
    total_all = total1 + np.sum(p2, axis=0, dtype=np.float64)

    def cfg(i: int, restore: str = "", skew: bool = False) -> Config:
        return Config(
            lifecycle=LifecycleConfig(
                node_name=f"n{i}", restore_path=restore,
            ),
            transport=TransportConfig(
                peer_timeout_sec=20.0, ack_timeout_sec=0.4
            ),
            obs=ObsConfig(digest_interval_sec=0.2, trace_wire=not skew),
        )

    def build(port, restore_dir=None, skew=False):
        peers = []
        for i in range(NODES):
            if i == chaos_idx:
                os.environ["ST_FAULT_PLAN"] = env["ST_FAULT_PLAN"]
            try:
                peers.append(
                    create_or_fetch(
                        "127.0.0.1", port, seed,
                        cfg(
                            i,
                            restore=(
                                os.path.join(restore_dir, f"shard_n{i}.npz")
                                if restore_dir
                                else ""
                            ),
                            skew=skew and i == skew_idx,
                        ),
                        timeout=60.0,
                    )
                )
            finally:
                os.environ.pop("ST_FAULT_PLAN", None)
        return peers

    def soak(peers, deltas, origin_a=0, origin_b=chaos_idx):
        for i, d in enumerate(deltas):
            peers[origin_a if i % 2 else origin_b].add(jnp.asarray(d))
            time.sleep(0.015)

    def converge(peers, total, budget):
        deadline = time.time() + budget
        while time.time() < deadline:
            if all(
                np.allclose(np.asarray(p.read()), total, atol=1e-3)
                for p in peers
            ):
                return True
            time.sleep(0.05)
        return False

    out = {
        "bench": "cluster_chaos_kill_restore",
        "nodes": NODES,
        "n": N,
        "adds": {"phase1": len(p1), "phase2": len(p2)},
        "seed": SEED,
        "chaos": {"drop_pct": 0.25, "node_index": chaos_idx},
        "skew_node": skew_idx,
        "budgets": {
            "restore_sec": RESTORE_BUDGET_S, "snapshot_sec": SNAP_BUDGET_S,
        },
    }
    def shm_tally(peers):
        """(links_live, msgs, fallbacks) across the tree — a link counts
        once per endpoint whose data plane is on the rings (state 2)."""
        live, msgs = 0, 0
        for p in peers:
            m = p.metrics(canonical=True)
            live += sum(
                1 for k, v in m.items()
                if k.startswith("st_shm_active") and v == 2
            )
            msgs += int(m.get("st_shm_msgs_out_total", 0))
        return live, msgs

    snapdir = tempfile.mkdtemp(prefix="st_snap_r12_")
    # ---- kill-restore arm -------------------------------------------------
    peers = build(_free_port())
    try:
        out["engine_tier"] = all(p._engine is not None for p in peers)
        soak(peers, p1)
        if SHM_ARM:
            live, msgs = shm_tally(peers)
            out["shm"] = {"pre_kill_lanes_live": live, "pre_kill_msgs": msgs}
        # snapshot MID-SOAK: in-flight residual mass under active drop
        # chaos — the barrier must drain and capture through it
        t0 = time.monotonic()
        res = peers[0].snapshot_cluster(snapdir, timeout=SNAP_BUDGET_S)
        snap_dur = time.monotonic() - t0
        out["snapshot"] = {
            "ok": res["ok"], "nodes": res["nodes"],
            "duration_sec": snap_dur,
        }
    finally:
        for p in peers:
            p.close()  # the whole-cluster kill
    t0 = time.monotonic()
    peers = build(_free_port(), restore_dir=snapdir, skew=True)
    try:
        restored = converge(peers, total1, RESTORE_BUDGET_S)
        restore_dur = time.monotonic() - t0
        out["restore"] = {
            "reconverged_pre_kill_mass": restored,
            "duration_sec": restore_dur,
        }
        # version skew is live mid-restart: one v1 emitter among v2 peers
        versions = sorted({p._wire_version for p in peers})
        out["restore"]["wire_versions"] = versions
        soak(peers, p2)
        kr_converged = converge(peers, total_all, 120.0)
        kr_final = np.asarray(peers[0].read(), np.float64)
        drained = all(p.drain(timeout=30.0, tol=1e-30) for p in peers)
        snaps = [p.metrics(canonical=True) for p in peers]
        retx = sum(int(s.get("st_retransmit_msgs_total", 0)) for s in snaps)
        out["restored_arm"] = {
            "converged": kr_converged,
            "drained": drained,
            "retransmits": retx,
            "restore_total": sum(
                int(s.get("st_restore_total", 0)) for s in snaps
            ),
        }
        if SHM_ARM:
            # the RESTARTED tree re-negotiated its lanes from scratch, and
            # the root's in-band digest must be EXACT at this quiesced
            # instant — the lane is below the wire-seq layer, so no
            # counter the digest aggregates may drift because of it
            live, msgs = shm_tally(peers)
            out["shm"]["restored_lanes_live"] = live
            out["shm"]["restored_msgs"] = msgs
            for _ in range(4):
                for p in peers:
                    if p._uplink is not None:
                        p.push_digest()
                time.sleep(0.4)
            cluster = peers[0].metrics(cluster=True)
            snaps = [p.metrics(canonical=True) for p in peers]
            digest_exact = len(cluster["nodes"]) == NODES
            dig = {}
            for name in STABLE_COUNTERS:
                want = sum(s.get(name, 0) for s in snaps)
                got = cluster["counters"].get(name, 0)
                dig[name] = {"cluster": got, "sum_of_registries": want}
                digest_exact = digest_exact and got == want
            out["shm"]["digest_exact_at_quiesce"] = bool(digest_exact)
            out["shm"]["digest_counters"] = dig
    finally:
        for p in peers:
            p.close()
    # ---- uninterrupted arm (identical schedule, no kill) ------------------
    peers = build(_free_port())
    try:
        soak(peers, p1)
        soak(peers, p2)
        un_converged = converge(peers, total_all, 120.0)
        un_final = np.asarray(peers[0].read(), np.float64)
        out["uninterrupted_arm"] = {"converged": un_converged}
    finally:
        for p in peers:
            p.close()
    # ---- verdict ----------------------------------------------------------
    # drop chaos + go-back-N converge EXACTLY, so the arms' bound is float
    # accumulation slack, not a chaos allowance (chaos_soak's corrupt-class
    # bounds don't apply — no corrupt faults here)
    conf = _conformance(hub)
    out["conformance"] = conf
    dev = float(np.max(np.abs(kr_final - un_final)))
    out["arms_max_deviation"] = dev
    out["bound"] = 1e-3
    out["pass"] = bool(
        conf["pass"]
        # >= 1 ROUTED event: a timeline none of whose events reaches an
        # acceptor (e.g. after an event rename) verifies nothing
        and conf["routed_events"] >= 1
        and out["snapshot"]["ok"]
        and out["snapshot"]["duration_sec"] <= SNAP_BUDGET_S
        and out["restore"]["reconverged_pre_kill_mass"]
        and out["restore"]["duration_sec"] <= RESTORE_BUDGET_S
        and len(out["restore"]["wire_versions"]) == 2  # skew was real
        and out["restored_arm"]["converged"]
        and out["restored_arm"]["drained"]
        and out["restored_arm"]["retransmits"] >= 1  # chaos repaired
        and out["uninterrupted_arm"]["converged"]
        and dev <= out["bound"]
    )
    if SHM_ARM:
        # every writer link's data plane on rings at BOTH ends (2 per
        # link), before the kill and again after the restart's fresh
        # negotiation; real lane traffic; digest exact at quiesce
        want_lanes = 2 * (NODES - 1)
        out["shm"]["want_lanes"] = want_lanes
        out["pass"] = bool(
            out["pass"]
            and out["shm"]["pre_kill_lanes_live"] >= want_lanes
            and out["shm"]["restored_lanes_live"] >= want_lanes
            and out["shm"]["pre_kill_msgs"] >= 1
            and out["shm"]["restored_msgs"] >= 1
            and out["shm"]["digest_exact_at_quiesce"]
        )
        out["bench"] = "cluster_chaos_kill_restore_shm"
    doc = json.dumps(out, indent=2)
    print(doc)
    if not os.path.isabs(art_path):
        art_path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            art_path,
        )
    with open(art_path, "w") as f:
        f.write(doc + "\n")
    print(
        f"cluster_chaos --kill-restore: snapshot "
        f"{out['snapshot']['duration_sec']:.2f}s, restore "
        f"{out['restore']['duration_sec']:.2f}s, arms max dev {dev:.2e}, "
        f"conformance {conf['events']} events/"
        f"{len(conf['violations'])} violations -> "
        f"{'PASS' if out['pass'] else 'FAIL'}",
        file=sys.stderr,
    )
    return 0 if out["pass"] else 1


def run_sharded(art_path: str) -> int:
    """The r16 cluster-sharded acceptance arm (module docstring): 7-node
    sharded tree, 25% drop chaos on the deep node's uplink (the native
    injector's is_data set covers wire.FWD), whole-tree kill-restore
    through the sharded checkpoint path, a per-node alloc bound enforced
    at every soak sample, and the steady-state memory ratio against the
    full-replica baseline recorded."""
    import tempfile

    import numpy as np

    from shared_tensor_tpu.comm import faults
    from shared_tensor_tpu.config import (
        Config, FaultConfig, LifecycleConfig, ShardConfig, TransportConfig,
    )
    from shared_tensor_tpu.ops.table import make_spec
    from shared_tensor_tpu.shard import ShardGather, create_or_fetch_sharded
    from shared_tensor_tpu.utils import checkpoint as ckpt

    tmpl = {"t": np.zeros(SHARD_N, np.float32)}
    spec = make_spec(tmpl)
    full_bytes = spec.total * 4  # any full-replica node's model floor
    bound = full_bytes // SHARD_FACTOR  # per-node allowance (model is
    # SHARD_FACTOR x bigger than one node — the harness enforces this at
    # EVERY sample below, chaos included)
    chaos_idx = NODES - 1
    env = faults.to_env(
        FaultConfig(enabled=True, seed=SEED, drop_pct=0.25, only_link=1)
    )

    def cfg(i: int, restore: str = "") -> Config:
        return Config(
            shard=ShardConfig(
                n_shards=NODES, shard_index=i, restore_dir=restore
            ),
            lifecycle=LifecycleConfig(node_name=f"s{i}"),
            transport=TransportConfig(
                peer_timeout_sec=20.0, ack_timeout_sec=0.4
            ),
        )

    def build(port, restore_dir=""):
        handles = []
        for i in range(NODES):
            if i == chaos_idx:
                os.environ["ST_FAULT_PLAN"] = env["ST_FAULT_PLAN"]
            try:
                handles.append(
                    create_or_fetch_sharded(
                        "127.0.0.1", port, tmpl, cfg(i, restore_dir),
                        timeout=60.0,
                    )
                )
            finally:
                os.environ.pop("ST_FAULT_PLAN", None)
        return handles

    # SPARSE adds (embedding-style windows spanning ~one shard): the
    # whole point of the sharded tensor is that no single writer needs
    # the full table resident — a dense delta would itself be O(full)
    rng = np.random.default_rng(SEED)
    win = max(64, SHARD_N // NODES)

    def mk_deltas(count):
        out = []
        for _ in range(count):
            lo = int(rng.integers(0, SHARD_N - win))
            d = np.zeros(SHARD_N, np.float32)
            d[lo : lo + win] = rng.uniform(-0.5, 0.5, win).astype(np.float32)
            out.append(d)
        return out

    p1 = mk_deltas(ADDS)
    p2 = mk_deltas(max(4, ADDS // 2))
    total1 = np.sum(p1, axis=0, dtype=np.float64)
    total_all = total1 + np.sum(p2, axis=0, dtype=np.float64)

    alloc = {"violations": 0, "peak": 0, "samples": 0, "stalls": 0}
    # one shard slice's resident bytes — the admission unit below
    slice_bytes = (spec.total // NODES + 32) * 4

    def soak(handles, deltas):
        for i, d in enumerate(deltas):
            h = handles[0 if i % 2 else chaos_idx]
            # flow control: a writer ADMITS a new update only once its
            # resident state has room for another outbox slice — the
            # backpressure a training step's sync point provides. Without
            # it a producer outrunning the chaotic link's drain would
            # accumulate one outbox per remote shard and the "model
            # bigger than the node" bound would be unachievable by ANY
            # implementation that keeps error feedback per target shard.
            deadline = time.time() + 30.0
            while (
                # room for TWO slices: a window can straddle a shard
                # boundary and allocate two outboxes in one add
                h.node.alloc_bytes() > bound - 2 * slice_bytes
                and time.time() < deadline
            ):
                alloc["stalls"] += 1
                time.sleep(0.005)
            h.add({"t": d})
            for hh in handles:
                b = hh.node.alloc_bytes()
                alloc["samples"] += 1
                alloc["peak"] = max(alloc["peak"], b)
                if b > bound:
                    alloc["violations"] += 1
            time.sleep(0.015)

    def gathered(handles, total, budget, atol=1e-3):
        deadline = time.time() + budget
        while time.time() < deadline:
            if all(h.node.drained() for h in handles):
                with ShardGather(handles[0].node, tmpl) as g:
                    got = np.asarray(g.read_tree(max_staleness=60.0)["t"])
                if np.allclose(got, total, atol=atol):
                    return True, float(np.max(np.abs(got - total)))
            time.sleep(0.25)
        with ShardGather(handles[0].node, tmpl) as g:
            got = np.asarray(g.read_tree(max_staleness=60.0)["t"])
        return False, float(np.max(np.abs(got - total)))

    out = {
        "bench": "cluster_chaos_sharded",
        "nodes": NODES,
        "n_shards": NODES,
        "n": SHARD_N,
        "adds": {"phase1": len(p1), "phase2": len(p2)},
        "seed": SEED,
        "chaos": {"drop_pct": 0.25, "only_link": 1, "node_index": chaos_idx},
        "memory_model": {
            # the harness-enforced contract: the model is FACTOR x bigger
            # than any node's allowance, checked at every soak sample
            "full_replica_bytes_per_node": full_bytes,
            "per_node_alloc_bound": bound,
            "model_over_node_factor": SHARD_FACTOR,
        },
    }
    from shared_tensor_tpu import obs

    hub = obs.hub()
    hub.poll_native()
    hub.recorder.clear()
    hub.recorder.set_capacity(500_000)

    snapdir = tempfile.mkdtemp(prefix="st_snap_r16_")
    handles = build(_free_port())
    try:
        assert all(h.sharded for h in handles), "a join fell back"
        soak(handles, p1)
        ok1, dev1 = gathered(handles, total1, 120.0)
        out["pre_kill"] = {"converged": ok1, "max_dev": dev1}
        # steady state: outboxes drained AND FREED — resident is the
        # owned slice (+ empty maps); the 1/N memory claim is measured
        # here, not mid-soak. The gather's subscriber legs tear down
        # ASYNCHRONOUSLY (each owner drops the sub residual when its loop
        # processes the LINK_DOWN), so wait for the teardown to settle —
        # sampling immediately can catch owned slice + one lingering sub
        # residual and trip the 2/N gate with no real regression
        settle = time.time() + 5.0
        while time.time() < settle:
            steady = max(h.node.alloc_bytes() for h in handles)
            if steady <= full_bytes * 2.0 / NODES:
                break
            time.sleep(0.05)
        out["memory_model"]["steady_state_max_bytes"] = steady
        out["memory_model"]["steady_over_full_ratio"] = steady / full_bytes
        owned = sorted(
            (i, h.node.owned_shards()) for i, h in enumerate(handles)
        )
        out["ownership_pre_kill"] = {str(i): s for i, s in owned}
        entries = [
            e
            for e in (h.node.save_shards(snapdir) for h in handles)
            if e is not None
        ]
        ckpt.write_manifest(snapdir, "chaos-r16", entries)
        coverage = ckpt.verify_shard_coverage(snapdir, NODES)
        out["snapshot"] = {
            "nodes": len(entries), "coverage_problems": coverage,
        }
    finally:
        for h in handles:
            h.close()  # the whole-cluster kill
    t0 = time.monotonic()
    handles = build(_free_port(), restore_dir=snapdir)
    try:
        ok_r, dev_r = gathered(handles, total1, RESTORE_BUDGET_S)
        out["restore"] = {
            "reconverged_pre_kill_mass": ok_r,
            "max_dev": dev_r,
            "duration_sec": time.monotonic() - t0,
        }
        soak(handles, p2)
        ok2, dev2 = gathered(handles, total_all, 120.0)
        out["restored_arm"] = {"converged": ok2, "max_dev": dev2}
        owned = sorted(
            (i, h.node.owned_shards()) for i, h in enumerate(handles)
        )
        out["ownership_restored"] = {str(i): s for i, s in owned}
        snaps = [h.node.metrics() for h in handles]
        out["fwd"] = {
            k: int(sum(s.get(k, 0) for s in snaps))
            for k in (
                "st_shard_fwd_msgs_out_total",
                "st_shard_fwd_msgs_in_total",
                "st_shard_fwd_relayed_total",
                "st_shard_fwd_dedup_total",
                "st_shard_park_drops_total",
            )
        }
        hub.poll_native()
        counts = hub.recorder.counts
        out["injected"] = {"fault_drop": counts.get("fault_drop", 0)}
        out["alloc"] = dict(alloc)
    finally:
        for h in handles:
            h.close()
    conf = _conformance(hub)
    out["conformance"] = conf
    out["pass"] = bool(
        conf["pass"]
        and out["pre_kill"]["converged"]
        and out["snapshot"]["coverage_problems"] == []
        and out["snapshot"]["nodes"] == NODES
        and out["restore"]["reconverged_pre_kill_mass"]
        and out["restore"]["duration_sec"] <= RESTORE_BUDGET_S
        and out["restored_arm"]["converged"]
        # every node re-owned its pre-kill shards after the restore
        and out["ownership_restored"] == out["ownership_pre_kill"]
        # chaos actually fired (the injector's is_data set covers FWD)
        and out["injected"]["fault_drop"] >= 1
        and out["fwd"]["st_shard_fwd_msgs_out_total"] >= 1
        and out["fwd"]["st_shard_park_drops_total"] == 0  # no silent loss
        # the memory contract: bound held at EVERY sample (model
        # FACTOR x bigger than one node), steady state ~1/N of the
        # full-replica arm (2x slack for padding + dict overheads)
        and alloc["violations"] == 0
        and out["memory_model"]["steady_over_full_ratio"] <= 2.0 / NODES
    )
    doc = json.dumps(out, indent=2)
    print(doc)
    if not os.path.isabs(art_path):
        art_path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            art_path,
        )
    with open(art_path, "w") as f:
        f.write(doc + "\n")
    print(
        f"cluster_chaos --sharded: steady/full "
        f"{out['memory_model']['steady_over_full_ratio']:.3f} "
        f"(bound {2.0 / NODES:.3f}), alloc violations "
        f"{alloc['violations']}/{alloc['samples']}, drops "
        f"{out['injected']['fault_drop']}, fwd dedup "
        f"{out['fwd']['st_shard_fwd_dedup_total']} -> "
        f"{'PASS' if out['pass'] else 'FAIL'}",
        file=sys.stderr,
    )
    return 0 if out["pass"] else 1


def main() -> int:
    art_path = sys.argv[1] if len(sys.argv) > 1 else "CHAOS_r09.json"
    if SHARDED_ARM:
        return run_sharded(
            sys.argv[1] if len(sys.argv) > 1 else "CHAOS_r16.json"
        )
    if KILL_RESTORE:
        return run_kill_restore(
            sys.argv[1] if len(sys.argv) > 1 else "CHAOS_r12.json"
        )
    import jax
    import jax.numpy as jnp
    import numpy as np

    jax.config.update("jax_platforms", "cpu")

    from shared_tensor_tpu import obs
    from shared_tensor_tpu.comm import faults
    from shared_tensor_tpu.comm.peer import create_or_fetch
    from shared_tensor_tpu.config import (
        Config, FaultConfig, ObsConfig, TransportConfig,
    )
    from shared_tensor_tpu.obs import trace_export

    hub = obs.hub()
    hub.poll_native()
    hub.recorder.clear()
    hub.recorder.set_capacity(500_000)

    cfg = Config(
        transport=TransportConfig(
            peer_timeout_sec=20.0, ack_timeout_sec=0.4,
            stripe_count=max(1, min(8, STRIPES)),
        ),
        obs=ObsConfig(digest_interval_sec=0.2),
    )
    port = _free_port()
    seed = jnp.zeros((N,), jnp.float32)
    chaos_idx = NODES - 1  # the deep leaf that also originates adds
    # with subscribers attached, the chaotic node's drop schedule covers
    # ALL its links (only_link=0) so the unledgered subscriber links face
    # the same 25% drops as its uplink; the r09-compatible run keeps the
    # original uplink-only schedule. The r11 striped arm additionally
    # SEVERS one stripe socket of the chaotic node's uplink mid-stream —
    # the per-stripe chaos the satellite task names.
    env = faults.to_env(
        FaultConfig(
            enabled=True, seed=SEED, drop_pct=0.25,
            only_link=0 if SUBS > 0 else 1,
            only_stripe=STRIPES - 1 if STRIPES > 1 else -1,
            sever_after_frames=SEVER_AFTER if STRIPES > 1 else 0,
        )
    )
    peers = []
    for i in range(NODES):
        if i == chaos_idx:
            os.environ["ST_FAULT_PLAN"] = env["ST_FAULT_PLAN"]
        try:
            peers.append(
                create_or_fetch("127.0.0.1", port, seed, cfg, timeout=60.0)
            )
        finally:
            os.environ.pop("ST_FAULT_PLAN", None)

    # r10 subscriber arm: read-only leaves grafted DIRECTLY under the
    # chaotic node, so every delta they receive crosses its drop schedule
    subs = []
    if SUBS > 0:
        from shared_tensor_tpu import serve

        chaos_port = peers[chaos_idx].node.listen_port
        for _ in range(SUBS):
            subs.append(
                serve.subscribe(
                    "127.0.0.1", chaos_port, seed, cfg, timeout=60.0
                )
            )

    out = {
        "bench": "cluster_chaos",
        "nodes": NODES,
        "n": N,
        "adds": ADDS,
        "seed": SEED,
        "engine_tier": all(p._engine is not None for p in peers),
        "chaos": {"drop_pct": 0.25, "only_link": 1, "node_index": chaos_idx},
    }
    if SUBS > 0:
        out["chaos"]["only_link"] = 0
        out["subscribers"] = {
            "count": SUBS, "max_staleness_sec": SUB_BOUND,
        }
    if STRIPES > 1:
        out["chaos"]["severed_stripe"] = STRIPES - 1
        out["chaos"]["sever_after_frames"] = SEVER_AFTER
        out["stripes"] = {"count": STRIPES}
    try:
        from shared_tensor_tpu.serve import StalenessError

        reads_ok = reads_refused = 0  # mid-chaos tallies (the adds loop)
        q_ok = q_refused = 0  # post-quiesce convergence-loop tallies
        total = np.zeros(N, np.float64)
        rng = np.random.default_rng(0)
        for i in range(ADDS):
            d = rng.uniform(-0.5, 0.5, N).astype(np.float32)
            peers[0 if i % 2 else chaos_idx].add(jnp.asarray(d))
            total += d
            # the serving contract, exercised mid-chaos: every read either
            # verifies its bound or raises — silent staleness is
            # structurally impossible, and this tallies which happened
            for s in subs:
                try:
                    s.read(max_staleness=SUB_BOUND)
                    reads_ok += 1
                except StalenessError:
                    reads_refused += 1
            time.sleep(0.015)

        deadline = time.time() + 120.0
        converged = [False] * NODES
        while time.time() < deadline and not all(converged):
            for i, p in enumerate(peers):
                if not converged[i]:
                    converged[i] = bool(
                        np.allclose(np.asarray(p.read()), total, atol=1e-4)
                    )
            time.sleep(0.05)
        drained = all(p.drain(timeout=30.0, tol=1e-30) for p in peers)

        # subscriber convergence: once the writers quiesce, every
        # subscriber's VERIFIED read must reach the same total (resyncs
        # repair whatever the chaos swallowed; FRESH marks — control
        # plane, outside the chaos classes — keep the bound verifiable
        # on the idle tree)
        sub_converged = [False] * len(subs)
        sub_deadline = time.time() + 90.0
        while time.time() < sub_deadline and not all(sub_converged):
            for i, s in enumerate(subs):
                if not sub_converged[i]:
                    try:
                        v = np.asarray(s.read(max_staleness=SUB_BOUND))
                        sub_converged[i] = bool(
                            np.allclose(v, total, atol=1e-3)
                        )
                        q_ok += 1
                    except StalenessError:
                        q_refused += 1
            time.sleep(0.05)

        hub.poll_native()
        timeline = hub.recorder.timeline()
        paths = trace_export.trace_paths(timeline)
        stats = trace_export.path_stats(paths)
        counts = hub.recorder.counts

        # quiesced-instant digest: push bottom-up rounds so every level's
        # exact totals reach the root regardless of the tree's shape
        for _ in range(4):
            for p in peers:
                if p._uplink is not None:
                    p.push_digest()
            time.sleep(0.4)
        cluster = peers[0].metrics(cluster=True)
        snaps = [p.metrics(canonical=True) for p in peers]
        digest = {"nodes_seen": len(cluster["nodes"]), "counters": {}}
        # writers must all be visible; subscriber digests ride the same
        # control plane but on their own beat, so their visibility is
        # recorded, not required, at the quiesce instant
        digest_exact = NODES <= len(cluster["nodes"]) <= NODES + len(subs)
        for name in STABLE_COUNTERS:
            want = sum(s.get(name, 0) for s in snaps)
            got = cluster["counters"].get(name, 0)
            digest["counters"][name] = {
                "cluster": got, "sum_of_registries": want,
            }
            digest_exact = digest_exact and got == want

        staleness = [
            v for s in snaps for k, v in s.items()
            if k.startswith("st_staleness_seconds")
        ]
        # r11 striped arm: the sever killed ONE socket of the chaotic
        # node's uplink. Acceptable outcomes, both of which the exact
        # convergence above already survived: (a) the link DEGRADED to
        # the survivors — some live link reports deaths >= 1 with
        # live < negotiated; (b) reassembly wedged on a stripe seq the
        # dead socket swallowed and go-back-N tore the LINK down into
        # carry/re-graft (stripe_down/link_down in the ring, the
        # re-grafted link reporting a full stripe set). A wedged link is
        # the one outcome that cannot reach this point (convergence
        # times out and fails the run first).
        if STRIPES > 1:
            per_link = []
            for i, p in enumerate(peers):
                for link in list(p.node.links or ()):
                    ss = p.node.stripe_stats(link)
                    if ss is not None and ss["stripes"] > 1:
                        per_link.append({"node": i, "link": link, **ss})
            deaths = sum(s["deaths"] for s in per_link)
            reroutes = sum(s["reroutes"] for s in per_link)
            degraded = [
                s for s in per_link if s["deaths"] >= 1
                and s["live"] == s["stripes"] - s["deaths"]
            ]
            stripe_down_events = counts.get("stripe_down", 0)
            teardowns = counts.get("blackhole_teardown", 0)
            out["stripes"].update(
                links_striped=len(per_link),
                deaths=deaths,
                reroutes=reroutes,
                degraded_links=len(degraded),
                stripe_down_events=stripe_down_events,
                gbn_teardowns=teardowns,
                outcome=(
                    "degraded-to-survivors" if degraded
                    else "gbn-teardown-regraft" if teardowns >= 1
                    else "none-observed"
                ),
            )
        if subs:
            sm = [s.metrics() for s in subs]
            out["subscribers"].update(
                converged_all=all(sub_converged),
                reads_ok_mid_chaos=reads_ok,
                reads_refused_mid_chaos=reads_refused,
                reads_ok_at_quiesce=q_ok,
                reads_refused_at_quiesce=q_refused,
                resyncs=sum(int(m["st_sub_resyncs_total"]) for m in sm),
                gap_discards=sum(
                    int(m["st_sub_gap_discards_total"]) for m in sm
                ),
                stale_reads_raised=sum(
                    int(m["st_read_stale_total"]) for m in sm
                ),
            )
        out.update(
            converged_all=all(converged),
            drained_all=drained,
            injected={
                "fault_drop": counts.get("fault_drop", 0),
                "retransmit": counts.get("retransmit", 0),
            },
            trace_paths=stats,
            trace_events=counts.get("trace_apply", 0),
            native_ring_dropped=int(
                next(iter(snaps), {}).get("st_obs_events_dropped_total", 0)
            ),
            staleness_seconds={
                "max": max(staleness, default=0.0),
                "observed_links": len(staleness),
            },
            digest=digest,
            digest_exact=digest_exact,
        )
        trace_out = os.environ.get("ST_CLUSTER_TRACE_OUT", "")
        if trace_out:
            trace_export.export_file(trace_out, timeline)
            out["trace_export"] = trace_out
        conf = _conformance(hub)
        out["conformance"] = conf
        out["pass"] = bool(
            conf["pass"]
            # >= 1 ROUTED event: a timeline none of whose events
            # reaches an acceptor (after an event rename, say)
            # verifies nothing
            and conf["routed_events"] >= 1
            and all(converged)
            and drained
            and out["injected"]["fault_drop"] >= 1
            and out["injected"]["retransmit"] >= 1
            and stats["paths"] >= ADDS // 2
            and stats["contiguous_frac"] >= 0.99
            and digest_exact
            # r10 arm: the writer tree was never wedged (the criteria
            # above, evaluated WITH subscribers attached), every
            # subscriber's verified read reached the exact total, and at
            # least one read VERIFIED somewhere in the run (mid-chaos
            # reads may legitimately all refuse under heavy drops — the
            # artifact records both tallies separately)
            and (not subs or (all(sub_converged) and reads_ok + q_ok >= 1))
            # r11 striped arm: the injected stripe sever must actually
            # have fired AND resolved into one of the two clean outcomes
            # (degrade-to-survivors or go-back-N teardown) — never a
            # wedged link (which the convergence deadline above catches)
            and (
                STRIPES <= 1
                or out["stripes"]["outcome"] != "none-observed"
            )
        )
    finally:
        for s in subs:
            s.close()
        for p in peers:
            p.close()

    doc = json.dumps(out, indent=2)
    print(doc)
    if not os.path.isabs(art_path):
        art_path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            art_path,
        )
    with open(art_path, "w") as f:
        f.write(doc + "\n")
    print(
        f"cluster_chaos: {out.get('trace_paths', {}).get('paths', 0)} paths, "
        f"contiguous {out.get('trace_paths', {}).get('contiguous_frac', 0):.3f}, "
        f"digest_exact={out.get('digest_exact')}, conformance "
        f"{len(out.get('conformance', {}).get('violations', []))} "
        f"violations -> "
        f"{'PASS' if out['pass'] else 'FAIL'}",
        file=sys.stderr,
    )
    return 0 if out["pass"] else 1


if __name__ == "__main__":
    sys.exit(main())
