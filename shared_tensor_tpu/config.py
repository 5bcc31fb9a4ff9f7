"""Typed configuration for shared-tensor-tpu.

The reference has no config system at all — its total configuration surface is
the three positional args of ``createOrFetch(host, port, tensor)`` plus
hard-coded constants (reference src/sharedtensor.c:349-352, :323; SURVEY.md
§5.6). This module realizes the survey's build note: a small typed config
covering rendezvous, mesh axes, codec policy, pacing (the reference README's
bandwidth-limit TODO), and fault timeouts (its disconnect-handling TODO).
"""

from __future__ import annotations

import dataclasses
import enum
from typing import Optional


class ScalePolicy(enum.Enum):
    """How the per-frame quantization scale is chosen from the residual.

    POW2_RMS is the reference policy: ``2^floor(log2(rms(residual)))``
    (reference src/sharedtensor.c:153-159). RMS skips the power-of-2 floor
    (slightly faster convergence, loses the cheap-to-compare property);
    ABS_MEAN uses mean(|r|) like signSGD-EF literature.
    """

    POW2_RMS = "pow2_rms"
    RMS = "rms"
    ABS_MEAN = "abs_mean"


@dataclasses.dataclass(frozen=True)
class CodecConfig:
    """Approximate-delta codec configuration.

    The reference codec is fixed: 1 sign bit per element, one global scale per
    frame chosen by POW2_RMS, error feedback via a per-link residual
    (reference src/sharedtensor.c:106-111, :145-177; SURVEY.md App. B). Those
    are the defaults here. ``per_leaf_scale`` realizes the reference README's
    "table sync" TODO (README.md:41): one scale per pytree leaf instead of one
    for the whole flat buffer, fixing the 1000:1 mixed-magnitude degradation
    measured in BASELINE.md.
    """

    scale_policy: ScalePolicy = ScalePolicy.POW2_RMS
    per_leaf_scale: bool = True
    #: Skip sending when scale == 0 (fixes reference quirk Q2, which sleeps 1s
    #: but still transmits an idle frame). Safe in wire-compat mode too: the
    #: native transport emits a zero-scale compat keepalive frame per
    #: keepalive interval when a link is idle — the reference's own idle
    #: behavior, which its peers' liveness expects — so the codec layer never
    #: needs to synthesize idle frames itself.
    suppress_zero_frames: bool = True
    #: r11 telemetry-adaptive link precision (native engine, native
    #: framing): the per-link residual-RMS telemetry (st_residual_norm's
    #: source) drives each link's wire precision — a link whose residual
    #: stops decaying upshifts to the sign2 2-bit codec (sign + magnitude
    #: bit selecting +/-s or +/-3s; the measured-best lab codec, promoted
    #: from parallel/ici_lab.py), a quiet link downshifts back to 1-bit.
    #: Emission is capability-gated per link (compat.SYNC_FLAG_SIGN2 /
    #: WELCOME flags), so mixed trees with pre-r11 or python-tier peers
    #: stay 1-bit toward those peers automatically; decoders on this
    #: release accept both widths unconditionally. ST_SIGN2=0 in the
    #: environment force-disables (the A/B / escape hatch, like
    #: ST_WIRE_TRACE).
    adaptive_precision: bool = True
    #: Governor thresholds/beat: upshift after 2 consecutive beats where
    #: the link's residual RMS GROWS past up_ratio * previous (the link is
    #: falling behind the mass arriving — chaos, retransmission storms, a
    #: saturated peer); downshift after 2 beats below down_ratio *
    #: previous (or quiesced). A healthy saturated link (flat rms at the
    #: wire's equilibrium) deliberately stays 1-bit.
    precision_up_ratio: float = 1.05
    precision_down_ratio: float = 0.5
    precision_interval_sec: float = 0.1
    #: r11 cascade quantize (native engine): frames quantized per MEMORY
    #: PASS over the residual. Frame 0's scales are measured as always;
    #: frames 1..k-1 take the halving schedule the measured sequence
    #: converges to, so K frames cost one table read + one write instead
    #: of K (the measured 1 Mi wall was the pass count, not bandwidth).
    #: Scales ride the wire — receivers are oblivious, any peer decodes.
    #: 1 = the r10 per-frame re-measured schedule. The committed sweep
    #: (ENGINE_SWEEP_r11.json, 1 Mi loopback) reads 47.5 GB/s equiv @1,
    #: 71.7 @8, then flat within box noise through 32 — the amortization
    #: saturates by ~8; 32 stays the default for the finer drain lattice
    #: (the extra sub-rms refinement levels are free in the same pass and
    #: the endgame merges them in fewer single-frame passes).
    cascade_frames: int = 32


@dataclasses.dataclass(frozen=True)
class TransportConfig:
    """Host (DCN/TCP) transport configuration — the peer tier.

    The reference transport is hand-rolled blocking TCP with no pacing,
    backlog 5, and exit(-1) on any error (SURVEY.md §2.3, quirks Q8/Q10).
    """

    #: Max outgoing wire bytes/sec per link; 0 = unlimited. Realizes the
    #: reference README.md:31 bandwidth-limiting TODO (token bucket in the
    #: native transport).
    bandwidth_cap_bytes_per_sec: int = 0
    #: Listen backlog (reference uses 5; quirk Q10 — join storms get refused).
    listen_backlog: int = 128
    #: Seconds of link silence before a peer is declared dead and the link
    #: torn down + re-grafted (fixes reference README.md:33 / quirk Q8 —
    #: reference kills the whole process instead).
    peer_timeout_sec: float = 30.0
    #: Reconnect/rejoin attempts before giving up.
    max_rejoin_attempts: int = 8
    #: Speak the reference's exact wire format: raw host-endian float scale +
    #: LSB-first bitmask frames, 'Y'/'N'+sockaddr join protocol
    #: (SURVEY.md §2.3 wire spec). Enables interop A/B against C peers. Idle
    #: links emit one zero-scale keepalive frame per keepalive interval (the
    #: reference's quirk-Q2 behavior, which C peers' liveness relies on) —
    #: handled inside the native transport.
    wire_compat: bool = False
    #: Tree fan-out: children per node before the listener redirects joiners
    #: down the tree (the reference hard-codes 2 — its binary tree,
    #: src/sharedtensor.c:201-231). 1 builds a chain (interop tests route a
    #: joiner THROUGH an interior node this way); 1..16 (0 would silently
    #: close every join; >16 would be silently clamped by the native layer).
    max_children: int = 2
    #: Per-attempt bound on connect() AND on the join-walk reply read. The
    #: reference (and this framework before r06) used a blocking connect: a
    #: rendezvous that silently drops packets — or accepts and never speaks —
    #: blocked the joiner FOREVER. 0 = legacy blocking connect.
    connect_timeout_sec: float = 5.0
    #: Total budget for the create-time join-or-become-master loop
    #: (exponential backoff with +/-50% jitter between attempts, so joiner
    #: herds and the two master-election races don't re-collide in
    #: lockstep). Past the budget, creation fails with a ConnectionError
    #: instead of retrying forever. 0 = default (30 s).
    join_timeout_sec: float = 30.0
    #: Go-back-N delivery timer (native framing only; see comm/wire.py's
    #: tx_seq docstring). When the OLDEST unacked DATA/BURST message on a
    #: live link goes unacknowledged this long, the sender retransmits the
    #: whole unacked tail byte-identical (same seqs — the receiver dedups,
    #: so a spurious retransmit is harmless). On a healthy TCP link ACKs
    #: arrive in milliseconds and this never fires; it exists for
    #: boundaries that can swallow a message whole (fault injection, dying
    #: proxies). After ``ack_retry_limit`` fruitless rounds the link is
    #: torn down into the LINK_DOWN -> rollback -> carry -> re-graft path.
    #: 0 = disabled (a silently-lost message then strands its ledger
    #: entries until the link dies).
    ack_timeout_sec: float = 5.0
    #: Retransmission rounds with zero ACK progress before the link is
    #: declared a black hole and torn down for re-graft. Values <= 0
    #: coerce to 1 round, identically on both data planes.
    ack_retry_limit: int = 8
    #: r11 multi-socket link striping (native framing only): each logical
    #: link runs over this many TCP connections, with messages round-robin
    #: striped across them (a per-message stripe sequence reassembles the
    #: stream in order at the receiver) and per-stripe sender/receiver
    #: threads — on fat pipes / loopback one stream's kernel path is a
    #: single-core bottleneck. A dead stripe degrades the link to the
    #: survivors when its loss is visible to the SENDER: messages still in
    #: hand re-route to the surviving sockets. A stripe that dies with
    #: already-written-but-undelivered wire data leaves a stripe-seq hole
    #: no survivor can fill — that link tears down cleanly via the
    #: engine's go-back-N (quarantine -> carry -> re-graft), it does not
    #: wedge; the LAST stripe's death is the link's either way. Joining
    #: with stripe_count > 1 uses the STT4 hello, which a
    #: pre-r11 acceptor rejects — keep 1 (the default; wire-identical to
    #: r10) to join older trees. 1..8.
    stripe_count: int = 1
    #: Per-link send quarantine: after this many CONSECUTIVE failed send
    #: attempts (~0.1 s each — i.e. ~N/10 seconds of a full send queue with
    #: zero drained bytes) the link is torn down and re-grafted instead of
    #: retried hot. A peer that stops draining but keeps its socket open
    #: would otherwise wedge our sender until peer_timeout_sec with frames
    #: pinned in its dead queue; quarantine converts the stall into the
    #: LINK_DOWN -> carry -> re-graft path the ledger already handles
    #: losslessly. 0 = never quarantine (retry until liveness timeout).
    quarantine_send_failures: int = 100
    #: r14 same-host shared-memory lane. When both ends of a link are on
    #: one host (boot-id match, advertised through the tolerant SYNC/
    #: WELCOME capability extension — compat.SYNC_FLAG_SHM), the link's
    #: DATA plane moves into SPSC rings in a mapped /dev/shm segment and
    #: the TCP connection stays up as the control/liveness/teardown
    #: channel — join, go-back-N seq accounting, SNAP/RESUME, quarantine/
    #: carry/re-graft semantics are untouched. Negotiation is fail-safe:
    #: any mismatch (pre-r14 peer, cross-host, /dev/shm unavailable,
    #: validation failure) silently keeps the link on TCP, with a
    #: ``shm_fallback`` timeline event recording why. ``ST_SHM=0`` in the
    #: environment force-disables the lane (the A/B escape hatch, like
    #: ST_SIGN2/ST_WIRE_TRACE).
    shm_enabled: bool = True
    #: CAP on bytes per shm ring DIRECTION (two rings per link). The peer
    #: sizes each link's rings to its table — twice the max traced sign2
    #: burst, floored at 1 MiB — and this cap bounds that (the sizing
    #: matters both ways on one memory system: a ring smaller than a
    #: burst runs the lane in lockstep — measured -9% at 16 Mi elements —
    #: while one much larger than needed cycles through DRAM instead of
    #: staying cache-resident — measured -8% at 1 Mi). Messages larger
    #: than the ring still STREAM through it correctly; tmpfs allocates
    #: pages lazily, so links touch only their high-water mark. Clamped
    #: to 64 KiB .. 1 GiB and page-rounded by the native layer.
    shm_ring_bytes: int = 1 << 26

    def __post_init__(self):
        if not 1 <= self.max_children <= 16:
            raise ValueError(
                f"max_children must be in 1..16, got {self.max_children}"
            )
        if not 1 <= self.stripe_count <= 8:
            raise ValueError(
                f"stripe_count must be in 1..8, got {self.stripe_count}"
            )
        if self.shm_ring_bytes < (1 << 16):
            raise ValueError(
                f"shm_ring_bytes must be >= 64 KiB, got {self.shm_ring_bytes}"
            )


@dataclasses.dataclass(frozen=True)
class FaultConfig:
    """Deterministic, seedable fault injection at the wire boundary
    (``comm/faults.py``; disabled by default — production pays only a
    None-check per send).

    The same fault classes exist on BOTH tiers, with tier-specific
    injection: on the Python wire tier this config injects directly (the
    peer consults a :class:`~shared_tensor_tpu.comm.faults.FaultPlan` in
    its send path); on the native-engine tier the engine's C send path
    never traverses that boundary, so the WIRE knobs must be rendered into
    the ``ST_FAULT_PLAN`` / ``ST_FAULT_CRASH`` environment hook table
    around node creation
    (:func:`~shared_tensor_tpu.comm.faults.to_env` renders this config
    into those strings; the peer logs a loud warning if wire faults are
    configured on an engine-tier peer with no env table set). The crash
    points fire on both tiers either way. Faults apply to DATA/BURST frames only — handshake and
    ACK traffic stays clean, so every injected fault exercises the recovery
    machinery (ledger rollback, carry, re-graft, quarantine) rather than
    wedging a join. The reference's only failure story is exit(-1) on any
    socket error; this layer exists to drive every recovery path this
    framework claims, deterministically, in tests and the chaos soak.
    """

    #: Master switch; False = zero injection, identical to no plan at all.
    enabled: bool = False
    #: RNG seed — the whole schedule is a pure function of (seed, per-link
    #: frame sequence), so runs are reproducible.
    seed: int = 0
    #: Probability a data frame is silently dropped at the wire (sender
    #: believes it delivered; its ledger entry stays unacked).
    drop_pct: float = 0.0
    #: Probability a data frame is sent twice (the receiver's tx_seq dedup
    #: discards the echo — exactly-once; see comm/wire.py).
    dup_pct: float = 0.0
    #: Probability a data frame is truncated to a random shorter length
    #: (well-framed short message: the receiver's decode rejects it without
    #: consuming its seq, and the sender's go-back-N retransmit re-delivers
    #: it whole — exact recovery). Native framing only; compat framing is
    #: fixed-size and would shear.
    truncate_pct: float = 0.0
    #: Probability a payload bit is flipped. PYTHON tier: the flip is
    #: geometry-aware (faults.corrupt) and lands in a frame's packed sign
    #: words — mis-applies ONE element by 2*scale, the bounded fault class
    #: convergence bounds are built on. NATIVE tier: the C injector is
    #: geometry-blind (it can hit seq/scale bytes, and a flipped finite
    #: scale EXPONENT rescales a whole frame by up to 2^127) — survival /
    #: decode-guard chaos only, never use it under a convergence-bound
    #: assertion.
    corrupt_pct: float = 0.0
    #: Probability a data frame send is delayed by ``delay_sec``.
    delay_pct: float = 0.0
    delay_sec: float = 0.005
    #: >= 0: every data frame past the Nth (per link) is silently swallowed
    #: — a stalled link whose sender keeps ledgering. Deterministic; the
    #: rollback/carry tests are built on this.
    stall_after_frames: int = -1
    #: > 0: hard-kill the link at its Nth data frame (transport-level sever
    #: -> LINK_DOWN -> carry -> re-graft).
    sever_after_frames: int = 0
    #: > 0: restrict ALL faults to this one link id — "stall or sever an
    #: individual link". Link ids are per-node and allocated from 1, so a
    #: joiner's first uplink is link 1; a re-grafted uplink gets a fresh id
    #: and runs clean, which is how the deterministic carry tests let the
    #: recovery path prove itself. 0 = every link.
    only_link: int = 0
    #: >= 0: restrict ALL (native-tier) faults to this stripe index of each
    #: striped link — the r11 per-stripe chaos arm. ``sever_after_frames``
    #: then kills just that SOCKET: the link must degrade to the surviving
    #: stripes (messages re-route) instead of dying. -1 = every stripe.
    only_stripe: int = -1
    #: Named protocol point at which to kill the peer process (os._exit):
    #: "mid-join-walk" (SYNC sent, snapshot not), "mid-burst" (frames
    #: ledgered, message not yet on the wire), "between-apply-and-ack"
    #: (mass applied + flooded, ACK not sent — the at-least-once window).
    #: "" = never. Tests may override the kill action via FaultPlan(on_crash=...).
    crash_point: str = ""
    #: Fire the crash on the Nth arrival at the point (1 = first).
    crash_after: int = 1


@dataclasses.dataclass(frozen=True)
class ObsConfig:
    """Unified telemetry (r08; ``shared_tensor_tpu/obs``). The subsystem is
    ON by default — the native event ring only records rare protocol /
    recovery / fault events and the OBS_r08 gate holds the hot-path cost
    under 2% — and ``ST_OBS=0`` in the environment force-disables it
    process-wide regardless of this config (the bench's A/B knob)."""

    #: Master switch for THIS peer's Python-tier instrumentation (registry
    #: histograms, event emission, native-ring draining). The native ring
    #: itself is process-wide (env ST_OBS).
    enabled: bool = True
    #: How often this peer's recv loop drains the native event ring into
    #: the process flight recorder. Small enough that a 2048-event
    #: per-thread ring survives chaos bursts; large enough to stay off the
    #: drain mutex.
    native_drain_interval_sec: float = 0.2
    #: Background JSONL metrics sink: one snapshot line per interval
    #: appended to this path ("" = no sink).
    jsonl_path: str = ""
    jsonl_interval_sec: float = 5.0
    #: r09 trace propagation: stamp outgoing DATA/BURST messages with the
    #: v2 wire framing's 13-byte trace context (origin node, origin
    #: monotonic ns, hop count — compat.WIRE_VERSION). Decoders accept
    #: both framings regardless; ST_WIRE_TRACE=0 force-pins v1 emission
    #: (e.g. to join a tree of pre-r09 peers). The obs-overhead gate holds
    #: the stamping cost inside the same <2% budget (OBS_r09).
    trace_wire: bool = True
    #: r09 in-band metric aggregation: how often this peer piggybacks its
    #: subtree's bounded metrics digest up the tree on the existing link
    #: (counters merged by sum, histograms by bucket-add, gauges by
    #: labeled max/min — obs/aggregate.py). The root's
    #: ``peer.metrics(cluster=True)`` / Prometheus exposition then serve a
    #: live whole-tree view. 0 = digests off. Native framing only (the
    #: reference compat protocol has no typed control messages).
    digest_interval_sec: float = 0.5
    #: Root-side live cluster view: when set, a peer with no uplink (the
    #: tree root) writes the merged cluster digest JSON to this path every
    #: digest interval — the file ``python -m shared_tensor_tpu.obs.top``
    #: tails for its terminal dashboard. "" = don't write.
    cluster_json_path: str = ""
    #: r18 fleet health plane (root-side, obs/health.py): when set, the
    #: tree root runs the health analyzer every digest beat — time-series
    #: store, per-shard heat, staleness SLO burn-rate alerts — and writes
    #: the machine-readable health document to this path (atomic replace,
    #: same discipline as cluster_json_path). "" = analyzer off.
    health_json_path: str = ""
    #: Ring depth per time-series (beats kept). 256 beats at the default
    #: 0.5s digest interval is ~2 minutes of history.
    health_history: int = 256
    #: Staleness SLO objective: a digest beat is "bad" when the fleet's
    #: worst offset-corrected staleness exceeds this many seconds.
    staleness_slo_sec: float = 1.0
    #: SLO error budget: the tolerated bad-beat fraction (burn rate 1.0
    #: means burning exactly the budget).
    slo_budget: float = 0.01
    #: Multi-window burn-rate severities: (name, long_sec, short_sec,
    #: threshold). A severity fires when BOTH windows burn past the
    #: threshold and clears when the short window recovers.
    slo_windows: tuple = (
        ("page", 60.0, 5.0, 14.4),
        ("ticket", 300.0, 30.0, 6.0),
    )
    #: Zipf-skew naming bar: the hot shard must out-rate the mean of the
    #: other shards by this factor before health.json names it.
    heat_skew_ratio: float = 3.0
    #: r18 clock plane: how often a non-root node probes its uplink with a
    #: wire.CLOCK offset sample (obs/clock.py; chaos-exempt control op).
    #: 0 = clock sync off (staleness stays raw).
    clock_sync_interval_sec: float = 1.0
    #: TEST/BENCH ONLY — simulated clock skew in seconds applied to this
    #: node's cross-node-comparable stamps (trace stamps, clock probes).
    #: Lets a single-host harness prove the offset estimator recovers a
    #: known skew. Env ``ST_CLOCK_SKEW_SEC`` overrides. 0 = off.
    clock_skew_sim_sec: float = 0.0


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Read-path serving tier (r10; ``shared_tensor_tpu/serve``): read-only
    subscriber leaves with bounded-staleness reads, verified against the
    r09 origin stamps. Consumed by :class:`serve.Subscriber` and by the
    WRITER side's FRESH-beat pacing for subscriber links."""

    #: Default staleness bound for ``Subscriber.read()`` when the call
    #: passes none: the read raises StalenessError unless the subscriber
    #: can PROVE its state is at most this many seconds behind (latest
    #: applied origin stamp, or the parent's FRESH drain mark — same-host
    #: CLOCK_MONOTONIC semantics, the r09 staleness caveat).
    max_staleness_sec: float = 1.0
    #: How often a writer sends a FRESH mark on an IDLE subscriber link
    #: (residual fully drained — "as of t you have everything"). Without
    #: it, a quiet tree would read as ever-staler even though the
    #: subscriber is exactly current. Bounds the staleness floor an idle
    #: subscriber can verify.
    fresh_interval_sec: float = 0.25
    #: Minimum seconds between subscriber resync handshakes (a seq gap on
    #: the unledgered subscriber link triggers a fresh SYNC/DONE re-seed;
    #: under sustained drop chaos this caps the re-seed storm).
    resync_min_interval_sec: float = 0.25
    #: Element range [lo, hi) to subscribe to (page/embedding-style reads);
    #: rounded outward to 32-element word boundaries on the wire. None =
    #: the full table.
    range: Optional[tuple[int, int]] = None


@dataclasses.dataclass(frozen=True)
class LifecycleConfig:
    """r12 cluster lifecycle: consistent-cut snapshot/restore, bounded-time
    restart, drain-node, and the ``python -m shared_tensor_tpu.ctl``
    operator surface. The snapshot barrier is root-initiated
    (``peer.snapshot_cluster``): a quiesce marker (wire.SNAP) floods down
    the tree on the control plane, every node pauses NEW production,
    drains its in-flight ledgers to empty, writes a per-node shard file
    and acks up; the root assembles ``MANIFEST.json`` with per-node sha256
    digests and releases the barrier (wire.RESUME)."""

    #: Stable node name used for shard files (``shard_<name>.npz``) and as
    #: the ``ctl drain`` target. "" = ``node-<obs_id>`` (process-unique but
    #: NOT stable across restarts — set explicit names in any deployment
    #: that intends to restore).
    node_name: str = ""
    #: Shard file to restore from BEFORE joining the tree (the full-cluster
    #: restart path): values load into the replica, and a non-master node's
    #: checkpointed uplink residual (+ old carry) becomes the re-graft
    #: carry, so the join's diff handshake re-delivers exactly the owed
    #: mass — no retransmission storm, no double-apply (README "Cluster
    #: lifecycle"). "" = fresh start.
    restore_path: str = ""
    #: Root-side operator command channel: when set, a peer with no uplink
    #: polls ``<ctl_dir>/cmd.json`` for commands written by
    #: ``python -m shared_tensor_tpu.ctl`` (snapshot / restore / drain) and
    #: writes ``<ctl_dir>/result.json`` back. File-based like
    #: ObsConfig.cluster_json_path, so the CLI needs no socket into the
    #: cluster. "" = disabled.
    ctl_dir: str = ""
    #: Root-side budget for one whole-cluster snapshot/restore barrier
    #: (marker flood + drain-to-quiesce + shard I/O + acks). Past it the
    #: root RESUMEs the tree anyway and reports failure — a lifecycle
    #: operation may fail, but it must never leave the cluster paused.
    snapshot_timeout_sec: float = 60.0
    #: Safety net on every non-root node: if a barrier's RESUME never
    #: arrives (root died mid-barrier), unpause after this long and log —
    #: same never-leave-paused rule as the root's timeout.
    pause_timeout_sec: float = 30.0
    #: leave() budget for a routed ``ctl drain <node>`` (seal + drain +
    #: close on the target node).
    drain_grace_sec: float = 30.0


@dataclasses.dataclass(frozen=True)
class ShardConfig:
    """r16 cluster-sharded tensor (``shared_tensor_tpu/shard``): the table
    is partitioned into contiguous word ranges, each owned by exactly one
    cluster node. Per-node memory is the owned slice plus transient
    outboxes — O(total / n_shards) at steady state — instead of a full
    replica; a writer's out-of-shard delta rides owner-routed wire.FWD
    frames toward the shard's owner (no per-hop re-quantization), and
    readers assemble views by subscribing to owner shards (shard.gather).
    """

    #: Number of contiguous shards the master partitions the word space
    #: into at creation. 0 = sharding off (the classic full-replica
    #: protocol; ``create_or_fetch_sharded`` then returns a classic peer).
    n_shards: int = 0
    #: The shard index this node claims at join (the master claims its own
    #: index locally). -1 = a member that owns no shard: it still joins
    #: the tree, routes FWD traffic and may write/read, but holds no
    #: slice. Claims are arbitrated by the master; a taken index is
    #: DENIED and creation fails.
    shard_index: int = -1
    #: The address OTHER nodes (gather legs, takeover peers) should dial
    #: to reach THIS node's listener — recorded in the node's OwnerEntry
    #: at claim/handoff time. "" = advertise the rendezvous host argument,
    #: which is correct exactly when every node shares one host (the
    #: loopback cluster); multi-host deployments must set each node's
    #: reachable address here or every gather toward a non-master owner
    #: dials the wrong machine.
    advertise_host: str = ""
    #: Restart path: directory holding a sharded-snapshot MANIFEST.json
    #: (utils/checkpoint.write_manifest ``shards`` entries). The node
    #: loads its shard's slice/outboxes/dedup state BEFORE joining and
    #: claims with takeover semantics (the master re-grants the index at
    #: a higher epoch). "" = fresh start.
    restore_dir: str = ""
    #: Tree fan-out for sharded nodes (SEPARATE from
    #: TransportConfig.max_children): owner nodes also serve read-only
    #: subscriber leaves on the same listener, so they need slots beyond
    #: the tree's writer fan-out. This matters more than for classic
    #: trees: the transport redirects joiners DOWN the tree when slots
    #: fill, which is harmless for a full-replica subscription (any node
    #: serves the whole table) but breaks a gather leg that must land on
    #: one specific owner — so the sharded default sits near the
    #: transport's cap (16) and shard.gather documents the residual
    #: limit.
    max_children: int = 12
    #: Budget for the join-time claim round trip (SYNC -> map -> claim ->
    #: grant flood). Past it, creation fails instead of waiting forever.
    claim_timeout_sec: float = 20.0
    #: Bound on FWD messages parked while a shard's route is unknown
    #: (owner not yet granted, route purged by a LINK_DOWN, owner being
    #: restored). Overflow drops the OLDEST parked message and counts it
    #: (st_shard_park_drops_total) — loud bounded loss, never unbounded
    #: memory.
    park_cap: int = 4096
    #: r17 engine-tier shard plane: run the FWD hot loop (outbox pump,
    #: verbatim relay, owner dedup+apply, go-back-N) in the native engine
    #: (shard/engine_lane.py) when the lib is available. False pins the
    #: r16 python-tier plane — the semantic reference, wire-identical;
    #: the ST_SHARD_ENGINE=0 env escape hatch pins it process-wide.
    engine_lane: bool = True
    #: r17 library-side writer admission control (ROADMAP 1(d)): bound on
    #: resident per-target-shard outbox bytes. An add() whose
    #: out-of-shard deposits would exceed it waits for the FWD plane to
    #: drain room (outbox_overflow="block") or raises ShardBackpressure
    #: ("raise") — the backpressure that previously lived only in the
    #: chaos harness's alloc-polling loop. 0 = unlimited (the r16
    #: behavior: one outbox per remote shard can accumulate). The
    #: projection is conservative at slice granularity: each target shard
    #: of the delta counts one full outbox slice.
    outbox_limit_bytes: int = 0
    #: "block" (wait up to outbox_block_timeout_sec, then raise) or
    #: "raise" (fail the add() immediately).
    outbox_overflow: str = "block"
    #: How long a blocking add() waits for outbox room before raising
    #: ShardBackpressure (a stalled link should fail the writer loudly,
    #: never wedge it forever).
    outbox_block_timeout_sec: float = 30.0


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Pod-tier (intra-slice) configuration: how the shared array is laid out
    across the local device mesh and which collective strategy syncs it."""

    #: Mesh axis name over which the shared array is sharded.
    shard_axis: str = "shard"
    #: Mesh axis name over which data-parallel peers (devices acting as
    #: independent workers) exchange compressed deltas.
    peer_axis: str = "peer"


@dataclasses.dataclass(frozen=True)
class Config:
    """Top-level config. ``rendezvous`` replaces the reference's
    (host, port) positional pair; everything else is new surface the
    reference hard-codes."""

    rendezvous_host: str = "127.0.0.1"
    rendezvous_port: int = 50000
    codec: CodecConfig = dataclasses.field(default_factory=CodecConfig)
    transport: TransportConfig = dataclasses.field(default_factory=TransportConfig)
    mesh: MeshConfig = dataclasses.field(default_factory=MeshConfig)
    #: Deterministic fault injection (tests / chaos soak); disabled default.
    faults: FaultConfig = dataclasses.field(default_factory=FaultConfig)
    #: Unified telemetry (metrics registry + event timeline + flight
    #: recorder); enabled default, <2% hot-path cost (OBS_r08 gate).
    obs: ObsConfig = dataclasses.field(default_factory=ObsConfig)
    #: Read-path serving tier (r10): subscriber staleness bounds, FRESH
    #: beat pacing, range subscription.
    serve: ServeConfig = dataclasses.field(default_factory=ServeConfig)
    #: Cluster lifecycle (r12): node naming, restart-restore, operator
    #: command channel, barrier timeouts.
    lifecycle: LifecycleConfig = dataclasses.field(
        default_factory=LifecycleConfig
    )
    #: Cluster-sharded tensor (r16): shard count, this node's claim,
    #: restart-restore, routing bounds. n_shards=0 keeps the classic
    #: full-replica protocol.
    shard: ShardConfig = dataclasses.field(default_factory=ShardConfig)
    #: Background sync frame pacing: target seconds between frames per link;
    #: 0 = free-running (reference behavior: fill all bandwidth, README.md:31).
    sync_interval_sec: float = 0.0
    #: Outstanding quantized-but-unsent frames per link in the sender
    #: pipeline. Each is dispatched on device and its device->host copy
    #: started asynchronously before older frames finish sending, so frame
    #: transfers overlap compute AND each other — on a high-latency
    #: device link (a PCIe queue) throughput is bounded by
    #: bandwidth instead of round-trip latency. 1 = plain double buffering.
    send_pipeline_depth: int = 8
    #: Warm slots the r07 zero-copy frame pool keeps per peer (wire.FramePool
    #: ``keep``): released send slots beyond this are freed, bounding an
    #: idle peer's high-water memory while keeping steady-state sends
    #: allocation-free. The pool itself is bounded by the go-back-N send
    #: window (peer.SEND_WINDOW live slots per link, worst case); slots are
    #: wire-message-sized (up to ~16 MiB at the largest burst), so ``keep``
    #: trades idle memory against re-allocation on bursty duty cycles.
    frame_pool_keep: int = 4
    #: Frames per wire message on the host (CPU) tier, native mode only.
    #: Successive codec frames are successive halvings of the same residual,
    #: so a sender can quantize K frames back-to-back and ship them as ONE
    #: message; the receiver's batched apply delivers them in one pass. For
    #: small tables the per-message engine cost (Python dispatch, framing,
    #: ACK) dominates the O(n) codec math, and bursting restores the frame
    #: rate (the reference's best case: its bare C loop hits 78k frames/s at
    #: 4 Ki elements, BASELINE.md). 0 = auto (burst small tables, stream
    #: big ones); 1 = always single-frame messages; K>1 = force K.
    frame_burst: int = 0
    #: Frames per wire message on the DEVICE tier (accelerator-backed
    #: peers), native mode only. K successive halvings quantize in ONE
    #: jitted dispatch and fetch with ONE device->host sync, so the
    #: device link's round trip is paid once per K frames instead of once
    #: per frame. 0 = auto (16, wire-capped); 1 = single-frame messages
    #: (the pure pipelined path). The 16 was never sized against a local
    #: chip's round trip (ROADMAP S7).
    device_frame_burst: int = 0
    #: Run the host-tier steady-state loop (quantize, encode, send, receive,
    #: flood apply, ACK ledger) in the native engine (native/stengine.cpp) —
    #: two C threads calling the same stcodec.c loops, no per-message
    #: interpreter cost. Python keeps handshakes and membership. Applies to
    #: host-tier native-protocol nodes only; the numpy tier remains the
    #: fallback (and ST_NATIVE_ENGINE=0 pins it, e.g. for parity tests).
    native_engine: bool = True


DEFAULT = Config()
