"""The peer engine: a complete shared-tensor node.

Composes the three lower layers into the reference's user-facing object
(reference src/sharedtensor.c:347-465 — createOrFetch / copyToTensor /
addFromTensor):

  - :class:`~shared_tensor_tpu.core.SharedTensor` — replica + per-link
    residuals + codec (device-side, functional JAX);
  - :class:`~shared_tensor_tpu.comm.transport.TransportNode` — the native C++
    TCP binary-tree overlay (host-side);
  - :mod:`~shared_tensor_tpu.comm.wire` — typed message encoding between them.

Where the reference runs 2 threads per link all doing O(n) float loops on the
CPU (src/sharedtensor.c:113-189; measured codec-CPU-bound, SURVEY.md §6), this
engine runs exactly two host threads per node — a sender and a receiver — that
only move opaque bytes and dispatch device work; the O(n) math executes on the
accelerator via the jitted table codec. Sends are event-driven (woken by
``add()`` and by incoming frames) and quiesce when residuals hit exact zero —
the reference instead burns 1 frame/s/link forever when idle (quirk Q2).

Threading model: the receive thread is the only consumer of transport events
(LINK_UP/LINK_DOWN) and the only writer of handshake state; the send thread
only reads ``SharedTensor.link_ids`` (created exactly at handshake
completion), so no lock beyond SharedTensor's own is needed.

Join/rejoin semantics (native mode) are the SYNC handshake documented in
wire.py. Wire-compat mode skips the handshake and speaks the reference's raw
protocol for interop with C peers (SURVEY.md §2.3).
"""

from __future__ import annotations

import logging
import os
import sys
import threading
import time
from collections import deque
from typing import Any, Optional

import jax.numpy as jnp
import numpy as np

from .. import obs as _obs
from ..config import Config
from ..core import DuplicateLink, SharedTensor
from ..obs import schema as _schema
from ..ops.table import make_spec
from . import faults, wire
from .transport import EventKind, TransportNode

log = logging.getLogger("shared_tensor_tpu.peer")

_HOST_ID: Optional[bytes] = None


def _shm_host_id() -> bytes:
    """16-byte host identity for the r14 same-host shm-lane negotiation.
    The Linux boot id is per-boot-unique ACROSS containers sharing a
    kernel only when the container runtime namespaces it — but two
    processes that CAN open the same /dev/shm path validate the segment
    token anyway, so a boot-id collision can at worst cost one failed
    attach (shm_fallback event) before the link keeps TCP."""
    global _HOST_ID
    if _HOST_ID is None:
        try:
            import uuid

            with open("/proc/sys/kernel/random/boot_id") as f:
                _HOST_ID = uuid.UUID(f.read().strip()).bytes
        except (OSError, ValueError):
            import hashlib
            import socket as _socket

            _HOST_ID = hashlib.sha256(
                _socket.gethostname().encode()
            ).digest()[:16]
    return _HOST_ID

#: Pseudo-link id holding the re-graft carry as a LIVE slot in the Python
#: tier's SharedTensor (the engine keeps its carry internally): a dead
#: uplink's rolled-back residual parks here and keeps receiving add()/flood
#: mass while the node is orphaned. Without a live slot, an add made with
#: no links lives only in the replica; the re-join snapshot then presents
#: it as tree-known state and the parent's diff seed erases it tree-wide
#: (the reference avoids this by accumulating into unconnected slots,
#: src/sharedtensor.c:124-126/:338-342). Never a transport link id
#: (transport ids start at 1); the send loop and drain skip it.
CARRY_LINK = -1

#: Go-back-N send window: max unacked DATA/BURST messages per link before
#: the send loop stops producing new frames for it. Bounds the retained
#: retransmission payloads (a stalled link would otherwise grow its ledger
#: — and the retransmittable tail — without limit until teardown) while
#: leaving a healthy link's pipeline far deeper than its ms-scale ACK
#: latency ever needs. The native engine enforces the same window.
SEND_WINDOW = 32

#: Max messages re-sent per retransmission round: go-back-N only needs the
#: HEAD of the unacked tail to restore in-order progress at the receiver
#: (everything behind a hole is discarded until the hole fills); resending
#: a short prefix repairs it without re-shipping the whole window's bytes
#: every round. Ditto in the native engine.
RETX_PREFIX = 4

def _python_tier_auto_burst(spec) -> int:
    """Auto burst for the PYTHON fallback tier: each burst frame is a full
    synchronous numpy rescan under the state lock, so only small tables —
    where per-message dispatch dominates — come out ahead."""
    if spec.total <= (1 << 15):
        return max(24, min(128, (1 << 19) // max(1, spec.total)))
    return 1


class _PeerObs:
    """One peer's observability bundle (r08 tentpole): a metrics registry
    publishing the canonical schema (obs/schema.py) — live histograms for
    the Python tier's per-message latencies, everything else sampled at
    snapshot time via a collector — plus the peer's handle on the process
    hub (flight recorder, native event-ring drain, postmortems).

    Hot-path cost when enabled: one ``time.monotonic()`` pair + one
    histogram observe per wire message on the PYTHON tier only; the native
    engine's data plane exports aggregates through the counters ABI and
    never calls into Python. Disabled (Config.obs.enabled=False or
    ST_OBS=0): the peer holds ``_obs = None`` and pays one None-check."""

    def __init__(self, peer: "SharedTensorPeer"):
        self.hub = _obs.hub()
        self.registry = _obs.Registry()
        h = self.registry.histogram
        self.ack_rtt = h(
            "st_ack_rtt_seconds",
            help="ledger-append to cumulative-ACK-pop round trip",
        )
        self.encode = h(
            "st_encode_seconds", help="wire-encode latency per DATA/BURST"
        )
        self.apply = h(
            "st_apply_seconds", help="decode+apply latency per received batch"
        )
        # Delivery counters exist as LIVE instruments only on the Python
        # tier: an engine peer's retransmit/dedup truth lives in the C
        # counters ABI and arrives via the collector — registering a
        # never-incremented instrument under the same name would shadow
        # the collector's real value in every snapshot/scrape (instrument
        # values take precedence), reporting 0 while a link black-holes.
        # Ditto the r09 st_update_hops histogram: the engine tier exports
        # sum/count through the widened counters ABI instead.
        self.retransmits = self.dedup = self.hops = None
        if peer._engine is None:
            self.retransmits = self.registry.counter(
                "st_retransmit_msgs_total",
                help="go-back-N messages re-sent byte-identical",
            )
            self.dedup = self.registry.counter(
                "st_dedup_discards_total",
                help="duplicate/out-of-order data messages discarded unapplied",
            )
            self.hops = self.registry.histogram(
                "st_update_hops",
                buckets=(1, 2, 3, 4, 6, 8, 12, 16, 24, 32),
                help="tree hops traversed by applied traced updates",
            )
        # r09 in-band digest plumbing (python-side on BOTH tiers: digests
        # ride the control plane, never the C data path)
        self.digest_out = self.registry.counter(
            "st_digest_sends_total",
            help="cluster metrics digests sent up the tree",
        )
        self.digest_in = self.registry.counter(
            "st_digest_msgs_in_total",
            help="cluster metrics digests received from subtree links",
        )
        self.cluster_nodes = self.registry.gauge(
            "st_cluster_nodes",
            help="nodes represented in the latest merged cluster digest",
        )
        self.registry.register_collector(peer._obs_collect)
        self.label = f"peer-{peer.node.obs_id}"
        self.hub.register_registry(self.label, self.registry)
        ocfg = peer.config.obs
        self.drain_interval = ocfg.native_drain_interval_sec
        if ocfg.jsonl_path:
            self.registry.start_jsonl_sink(
                ocfg.jsonl_path, ocfg.jsonl_interval_sec
            )
        # r18: engine-tier origin attribution. The C receiver's trace_apply
        # ring events carry (origin << 8 | hop) in extra; a drain tap picks
        # this peer's out of each batch so _stale_origin stays current on
        # engine links too (the python tier writes it in _note_trace).
        self._peer = peer
        self._tap = self._on_native_batch if peer._engine is not None else None
        if self._tap is not None:
            self.hub.add_tap(self._tap)

    def _on_native_batch(self, batch) -> None:
        peer = self._peer
        me = peer.node.obs_id
        for e in batch:
            if e.name == "trace_apply" and e.node == me:
                peer._stale_origin[e.link] = e.extra >> 8

    def event(
        self, name: str, node: int = 0, link: int = 0, arg: int = 0,
        detail: str = "", extra: int = 0,
    ) -> None:
        self.hub.emit(
            name, node=node, link=link, arg=arg, detail=detail, extra=extra
        )

    def close(self) -> None:
        self.registry.stop_jsonl_sink()
        if self._tap is not None:
            self.hub.remove_tap(self._tap)
        self.hub.poll_native()  # final drain: close() must not strand events
        self.hub.unregister_registry(self.label)


class SpecMismatch(ConnectionError):
    """Peer tried to sync a different table layout (the reference's
    THError("Not the right size!"), src/sharedtensor.c:335, made explicit
    at join time instead of corrupting the stream)."""


class SharedTensorPeer:
    """One node of the shared tensor: join the tree at (host, port) — or
    become master if nobody answers — then stream codec frames forever.

    The reference equivalent is ``sharedtensor.createOrFetch(host, port, t)``
    (src/sharedtensor.c:347-391): master seeds the shared state from
    ``template``; a joiner's ``template`` only defines the table *layout* and
    its values are ignored, with real state streaming in from the tree.
    """

    def __init__(
        self,
        host: str,
        port: int,
        template: Any,
        config: Config | None = None,
    ):
        self.config = config or Config()
        codec = self.config.codec
        tcfg = self.config.transport
        spec = make_spec(template)
        # r09 cross-hop trace propagation: which DATA/BURST framing this
        # peer EMITS (compat.WIRE_VERSION; decoders accept both). Lazy
        # import — compat.py imports this module at its top level. Decided
        # BEFORE the fault plan: corrupt()'s bounded-flip geometry must
        # skip the v2 trace bytes too.
        from ..compat import wire_protocol_version

        self._wire_version = (
            1 if tcfg.wire_compat else wire_protocol_version(self.config)
        )
        self._trace_wire = self._wire_version >= 2
        # Python-tier fault injection (Config.faults): consulted at the
        # send boundary and at named protocol points. None when disabled —
        # the production path pays one None-check per send. The NATIVE data
        # planes (transport sender loop, engine) read the same schedule
        # from the ST_FAULT_PLAN/ST_FAULT_CRASH env table instead
        # (faults.to_env), parsed at node-create time. scale_bytes/
        # trace_bytes hand the plan the frame geometry so corrupt() flips
        # land in sign words, not scale exponents or trace fields (the
        # bounded fault class).
        self._faults: Optional[faults.FaultPlan] = (
            faults.FaultPlan(
                self.config.faults,
                scale_bytes=4 * spec.num_leaves,
                wire_compat=tcfg.wire_compat,
                trace_bytes=wire.TRACE_BYTES if self._trace_wire else 0,
            )
            if self.config.faults.enabled
            else None
        )
        # pending trace stamp (origin node, origin monotonic ns, hops):
        # re-seeded by add(), advanced at every traced apply; read by the
        # send path when stamping outgoing messages. Tuple assignment —
        # atomic under the GIL, no lock on the hot path.
        self._trace_stamp: Optional[tuple[int, int, int]] = None
        # per-link (origin generation stamp ns, hops) of the latest traced
        # apply (python tier; the engine tier serves st_engine_link_obs
        # instead). r18: the GENERATION is stored, not a frozen age — the
        # collector ages it live so stalls are visible to the SLO.
        self._staleness: dict[int, tuple[int, int]] = {}
        self._traced_in = 0
        # r09 in-band digest aggregation: each child link's latest digest
        # (replaced wholesale per arrival; merged on demand)
        self._child_digests: dict[int, dict] = {}
        # digests ride the native control plane AND presume an r09 peer on
        # the other end: a peer pinned to v1 emission (ST_WIRE_TRACE=0 —
        # the join-a-pre-r09-tree escape hatch) must not spray kind-8
        # messages a pre-r09 parent would log as unknown every beat
        self._digest_interval = (
            0.0
            if tcfg.wire_compat or self._wire_version < 2
            else self.config.obs.digest_interval_sec
        )
        self._digest_last = 0.0
        # r18 fleet health plane. _skew_ns simulates a skewed host clock
        # (tests/benches only — env ST_CLOCK_SKEW_SEC overrides config):
        # applied via _now_ns() at every cross-node-comparable stamp site
        # (trace stamps, clock probes, digest t_ns), so the offset
        # estimator has a real skew to recover on a single host. _clock is
        # the per-node offset estimator (obs/clock.py); a node probes its
        # UPLINK every clock_sync_interval_sec with a wire.CLOCK message
        # (chaos-exempt control plane) — master peers are roots (offset
        # pinned 0). _stale_origin tracks the origin node of each link's
        # freshest traced apply, feeding the health analyzer's
        # offset-corrected staleness. _health exists only at a root with
        # health_json_path set; it is beaten from _publish_digest.
        skew_env = os.environ.get("ST_CLOCK_SKEW_SEC", "")
        self._skew_ns = int(
            float(skew_env if skew_env else self.config.obs.clock_skew_sim_sec)
            * 1e9
        )
        self._clock_interval = (
            0.0
            if tcfg.wire_compat or self._wire_version < 2
            else self.config.obs.clock_sync_interval_sec
        )
        self._clock_last = 0.0
        self._stale_origin: dict[int, int] = {}
        from ..core import host_tier_active

        # Burst sizing (Config.frame_burst): host tier only — the device
        # tier pipelines async dispatches (and has its own
        # device_frame_burst). Auto policy: the native engine fills the
        # wire message budget at every size; the Python fallback tier
        # bursts only small tables and never in compat mode (its compat
        # path sends one reference frame per message). Compat bursts exist
        # only on the engine: K fixed-size reference frames concatenate
        # into one wire message — protocol-identical to K sequential sends
        # for any reference peer (stengine.cpp compat-burst note).
        burstable = (
            host_tier_active()
            and self.config.codec.suppress_zero_frames  # the burst path has
            # no idle frames to send; honor the knob by streaming instead
        )
        from .engine import engine_eligible

        engine_ok = burstable and engine_eligible(self.config)
        if not burstable:
            self._burst = 1
        elif tcfg.wire_compat:
            if not engine_ok:
                self._burst = 1
            else:
                # the same wire-message byte budget as native mode bounds
                # BOTH the auto fill and an explicit Config.frame_burst —
                # without it a 255-frame burst on a 16 Mi tensor would
                # build single ~535 MB payloads
                cap = wire.compat_burst_frames_cap(spec.total_n)
                if self.config.frame_burst == 0:
                    self._burst = cap
                else:
                    self._burst = min(max(1, self.config.frame_burst), cap)
        elif self.config.frame_burst == 0:
            if engine_ok:
                # auto (engine): FILL the wire message budget — throughput
                # is monotone in K up to the per-spec cap at every measured
                # size (ENGINE_SWEEP_r07.json, the committed re-measure the
                # round-5 verdict asked for: 710 k f/s at 4 Ki, 52 k at
                # 64 Ki, 7.2 k at 1 Mi — all at their per-spec caps). The
                # engine's fused quantize+partials makes marginal frames
                # one memory pass, and a burst is one ledger entry/ACK.
                self._burst = wire.burst_frames_cap(spec)
            else:
                self._burst = _python_tier_auto_burst(spec)
        else:
            self._burst = max(1, self.config.frame_burst)
        if not tcfg.wire_compat:
            # wire-level invariant (native framing): every peer sizes its
            # receive buffer for burst_frames_cap(spec) frames
            # (frame_wire_bytes), so a sender must never burst beyond that
            # regardless of Config.frame_burst. Compat needs no cap-by-spec:
            # each frame is its own fixed-size wire message on the receive
            # side.
            self._burst = min(self._burst, wire.burst_frames_cap(spec))
        # Device-tier burst (Config.device_frame_burst): any size — the
        # point is amortizing the device-link round trip, which is paid at
        # every table size.
        dev_burstable = (
            not tcfg.wire_compat
            and not host_tier_active()
            and self.config.codec.suppress_zero_frames
        )
        if not dev_burstable:
            self._burst_device = 1
        elif self.config.device_frame_burst == 0:
            self._burst_device = min(16, wire.burst_frames_cap(spec))
        else:
            self._burst_device = max(
                1,
                min(
                    wire.burst_frames_cap(spec), self.config.device_frame_burst
                ),
            )
        if tcfg.wire_compat:
            if spec.num_leaves != 1:
                raise ValueError(
                    "wire-compat mode syncs one flat tensor per port "
                    "(reference README.md:26); use native mode for tables"
                )
            frame_bytes = wire.compat_frame_bytes(spec.total_n)
        else:
            # covers the worst-case incoming BURST from ANY peer (shared
            # spec via the layout handshake), not just our own burst size
            frame_bytes = wire.frame_wire_bytes(spec)
        self.node = TransportNode(
            host,
            port,
            tcfg,
            frame_bytes=frame_bytes,
            max_children=tcfg.max_children,
            keepalive_sec=min(1.0, max(0.05, tcfg.peer_timeout_sec / 4)),
        )
        self.is_master = self.node.is_master
        # r18 clock plane: master peers are tree roots (offset pinned to
        # 0/0); everyone else converges by probing the uplink. The health
        # analyzer exists only at a root with health_json_path set and is
        # beaten from _publish_digest on the recv thread.
        from ..obs.clock import ClockSync

        self._clock = ClockSync(self._now_ns, is_root=self.is_master)
        self._health = None
        if self.is_master and self.config.obs.health_json_path:
            from ..obs.health import HealthAnalyzer

            ocfg = self.config.obs
            self._health = HealthAnalyzer(
                path=ocfg.health_json_path,
                history=ocfg.health_history,
                objective_sec=ocfg.staleness_slo_sec,
                budget=ocfg.slo_budget,
                windows=ocfg.slo_windows,
                skew_ratio=ocfg.heat_skew_ratio,
                emit=self._health_event,
            )
        # Native engine (stengine.cpp): on the host tier the full
        # steady-state cycle — quantize, encode, send, receive, flood apply,
        # ACK ledger — runs in two C threads against the same stcodec.c
        # loops; Python keeps the handshakes and membership. Closes the
        # ~3 ms/message interpreter floor (round-3 verdict item 2).
        self._engine = None
        self._engine_links: set[int] = set()
        from .engine import EngineTensor, engine_eligible

        # r11 adaptive precision: on iff the engine owns the data plane,
        # native framing, and the config/env policy allows it
        # (compat.sign2_mode — ST_SIGN2=0 is the escape hatch). The
        # capability is advertised in SYNC/WELCOME; emission additionally
        # gates per link on the PEER's advertisement.
        from ..compat import sign2_mode

        self._sign2_mode = (
            sign2_mode(self.config)
            if engine_eligible(self.config) and not tcfg.wire_compat
            else 0
        )
        self._sign2 = self._sign2_mode != 0
        if engine_eligible(self.config):
            try:
                self.st = EngineTensor(
                    template,
                    codec,
                    seed_values=self.is_master,
                    node=self.node,
                    burst=self._burst,
                    recv_cap=frame_bytes,
                    # compat: the engine speaks the reference's raw frames
                    # directly (no ACK ledger — the protocol has none)
                    compat_frame_bytes=frame_bytes if tcfg.wire_compat else 0,
                    quarantine_send_failures=tcfg.quarantine_send_failures,
                    ack_timeout_sec=tcfg.ack_timeout_sec,
                    ack_retry_limit=tcfg.ack_retry_limit,
                    trace_wire=self._trace_wire,
                    precision_mode=self._sign2_mode,
                    precision_up_ratio=codec.precision_up_ratio,
                    precision_down_ratio=codec.precision_down_ratio,
                    precision_interval_sec=codec.precision_interval_sec,
                    cascade_frames=(
                        codec.cascade_frames if not tcfg.wire_compat else 1
                    ),
                )
                self._engine = self.st
                # Vacuous-chaos guard: Config.faults WIRE knobs inject in
                # the PYTHON tier's send path, which engine links never
                # traverse — on this tier the same classes come from the
                # ST_FAULT_PLAN env table (faults.to_env), parsed by
                # st_node_create above. A chaos test that forgot the env
                # render would pass green having injected nothing.
                import os as _os

                fcfg = self.config.faults
                if (
                    fcfg.enabled
                    and not _os.environ.get("ST_FAULT_PLAN")
                    and any((
                        fcfg.drop_pct, fcfg.dup_pct, fcfg.truncate_pct,
                        fcfg.corrupt_pct, fcfg.delay_pct,
                        fcfg.stall_after_frames >= 0,
                        fcfg.sever_after_frames,
                    ))
                ):
                    log.warning(
                        "FaultConfig wire faults are configured but the "
                        "NATIVE engine owns this peer's data plane — they "
                        "will inject NOTHING on engine links; render them "
                        "into the env with faults.to_env() around node "
                        "creation (crash_point still fires)"
                    )
            except Exception as e:
                log.warning("native engine unavailable, using python tier: %s", e)
        if self._engine is None:
            self._sign2 = False  # the python tier neither decodes nor
            # advertises sign2 — peers stay 1-bit toward us automatically
            # the burst was sized for the engine (fill the wire budget);
            # if the engine did not actually construct, the Python tier
            # must re-size — at the cap it would pay up to 255 synchronous
            # numpy rescans per message under the state lock. Its compat
            # path has no burst at all (one reference frame per message).
            if tcfg.wire_compat:
                self._burst = 1
            elif self.config.frame_burst == 0 and self._burst > 1:
                self._burst = min(self._burst, _python_tier_auto_burst(spec))
            self.st = SharedTensor(template, codec, seed_values=self.is_master)
        # r12 cluster lifecycle (consistent-cut snapshot/restore, drain,
        # operator surface). All barrier state is owned by the RECV thread
        # (_lc_tick / the SNAP/SNAP_ACK/RESUME handlers); public APIs
        # enqueue requests and wait on _lc_done. _paused gates NEW data
        # production on both tiers (engine: st_engine_pause; python: the
        # send loop) while in-flight delivery keeps draining — the
        # consistent cut is "paused + every ledger empty".
        self._lc_requests: deque = deque()
        self._lc_api_mu = threading.Lock()  # serializes _lc_request callers
        self._lc_op: Optional[dict] = None
        self._lc_done = threading.Event()
        self._lc_result: Optional[dict] = None
        self._paused = False
        self._pause_deadline = 0.0
        self._snap_total = 0
        self._snap_acks = 0
        self._snap_last_dur = 0.0
        self._restore_total = 0
        self._drain_total = 0
        self._draining = False
        self._lc_errors = 0
        self._ctl_last_poll = 0.0
        self._restored_from: Optional[str] = None
        # consistent-cut ordering state (python data plane): the send
        # loop's pass counter (pause is synchronous across one in-flight
        # pass — a pass already quantizing when the flag lands may still
        # enqueue, and a barrier marker must never overtake its data) and
        # the device pipeline's queued-frame gauge (markers only flood
        # once the paused pipeline has fully drained into the sockets)
        self._send_pass = 0
        self._pipe_frames = 0
        if self.config.lifecycle.restore_path:
            # full-cluster restart path: load this node's shard BEFORE the
            # data plane starts (threads are not running yet, so no lock
            # ordering to worry about)
            self._restore_at_startup(self.config.lifecycle.restore_path)
        self._ready = threading.Event()
        self._error: Optional[Exception] = None
        if self.is_master:
            self._ready.set()
        self._stop = threading.Event()
        self._wake = threading.Event()
        # close() is reached from the caller, from leave() and from the
        # drain helper thread; the lock serialises them and the flag makes
        # every call after the first a no-op
        self._close_lock = threading.Lock()
        self._closed = False
        # parent-side handshake state: link_id -> snapshot being received
        self._pending: dict[int, bytearray] = {}
        # child-side re-graft accounting. Invariant: the snapshot we send a
        # prospective parent is "state the tree already has from/for us" =
        # replica - carried_residual, so the parent's diff seed never
        # subtracts updates we still owe the tree. _sent_snapshot is kept
        # until WELCOME so the uplink residual can be seeded with
        # replica_now - sent_snapshot (= carry + everything added or flooded
        # in during the handshake).
        self._sent_snapshot: Optional[jnp.ndarray] = None
        # set when the uplink died BEFORE the handshake finished (no codec
        # link existed to stash): the carry is then values - this base,
        # computed lazily at re-join so orphan-period adds are included
        self._mid_handshake_base: Optional[jnp.ndarray] = None
        self._compat_reset_on_regraft = False
        self._sealed = False  # leave() in progress: discard unACKed ingress
        self._uplink: Optional[int] = None
        # r10 serving tier, WRITER side. _sub_links: attached read-only
        # subscriber links -> their word range (None = full table). These
        # links are UNLEDGERED: the send loop never appends to _unacked for
        # them (no ACKs will come — compat.SYNC_FLAG_READ_ONLY), loss is
        # the subscriber's seq-gap detector + resync handshake to repair,
        # and LINK_DOWN discards their residual without a carry (a
        # read-only leaf owes the tree nothing). _pending_sub: handshake
        # state between a read-only SYNC and its DONE (value = the RANGE
        # subscription received so far, None = full). _sub_fresh: last
        # FRESH drain-mark time per link (python-tier beat; the engine
        # tier beats in C).
        self._sub_links: dict[int, Optional[tuple[int, int]]] = {}
        self._pending_sub: dict[int, Optional[tuple[int, int]]] = {}
        self._sub_fresh: dict[int, float] = {}
        # r11 sign2 capability flags gathered during handshakes, consumed
        # at attach time (link id -> the peer advertised sign2 decode)
        self._peer_sign2: dict[int, bool] = {}
        # r14 same-host shm lane: whether this peer may negotiate it at
        # all, our host identity, and per-link whether the JOINER's SYNC
        # advertised a matching host (consumed at WELCOME time, when the
        # parent serves the segment). Negotiation is fail-safe — every
        # mismatch keeps the link on TCP.
        self._shm_ok = (
            self.config.transport.shm_enabled
            and not self.config.transport.wire_compat
            and sys.platform.startswith("linux")
            and os.path.isdir("/dev/shm")
            and os.environ.get("ST_SHM", "1") != "0"
        )
        self._shm_host = _shm_host_id() if self._shm_ok else b""
        self._peer_shm: dict[int, bool] = {}
        # r14 capability per link (the peer advertised the SYNC/WELCOME
        # shm flag at all — host match or not): gates the aligned v3
        # framing toward it (engine.link_wire_v3)
        self._peer_r14: dict[int, bool] = {}
        # replica state_version at each ranged link's last residual mask
        # (skip the full-table mask copy on idle passes)
        self._sub_mask_ver: dict[int, int] = {}
        self._sub_msgs_out = 0
        self._sub_fresh_out = 0
        # delivery accounting (see _send_loop): per link, the in-order list
        # of sent-but-unacked messages as (ledger_seq, wire_seq, payload)
        # — the payload is kept so an ACK timeout can retransmit it
        # byte-identical (go-back-N; wire.py tx_seq docstring). Send thread
        # appends, recv thread pops on wire.ACK (entries with
        # wire_seq <= ack count). Plus cumulative TX/RX/ACK counters and
        # the per-link retransmission timer state.
        self._ack_mu = threading.Lock()
        # (ledger_seq, wire_seq, payload, pool_slot, sent_at) — payload is
        # a memoryview over pool_slot's pooled buffer (r07: the ledger
        # entry IS its send buffer; pool_slot is None only for legacy
        # bytes payloads), released back to _tx_pool when the entry pops;
        # sent_at (r08) feeds the st_ack_rtt_seconds histogram at ACK pop
        self._unacked: dict[int, list[tuple[int, int, Any, Any, float]]] = {}
        # r07 zero-copy send plane (native framing only): encode writes
        # into a pooled wire-sized slot; the slot then serves as ledger
        # payload and byte-identical retransmission source. Slots are
        # allocated lazily on first acquire, so an engine-tier peer (whose
        # C data plane has its own tx ring) never pays for this pool.
        self._tx_pool: Optional[wire.FramePool] = None
        if not tcfg.wire_compat:
            per = wire.frame_payload_bytes(spec)
            # slots sized for the v2 (traced) headers either way — 13
            # bytes of slack on a v1 peer, never an overrun on a v2 one
            self._tx_pool = wire.FramePool(
                max(
                    wire.DATA_HDR_T + per,
                    wire.BURST_HDR_T
                    + max(self._burst, self._burst_device, 1) * per,
                ),
                keep=max(1, int(self.config.frame_pool_keep)),
            )
        # per-link decode destination pools (r07 satellite): steady-state
        # decode reuses (scales, words) arrays; recycled after each applied
        # batch, dropped on LINK_DOWN
        self._rx_scratch: dict[int, wire.DecodeScratch] = {}
        self._tx_seq: dict[int, int] = {}  # wire seq of last data msg sent
        self._acked: dict[int, int] = {}
        self._rx_count: dict[int, int] = {}
        self._ack_sent: dict[int, int] = {}  # highest ACK actually delivered
        # time.monotonic() of the link's last delivery progress (ACK moved,
        # or the unacked list became non-empty), and fruitless
        # retransmission rounds since — both guarded by _ack_mu
        self._ack_progress: dict[int, float] = {}
        self._retx_rounds: dict[int, int] = {}
        # r08 observability: per-peer registry + the process hub (flight
        # recorder, native event-ring drain). None when disabled — every
        # hot-path call site pays one None-check, like the fault plan.
        # Created LAST, after every attribute the registry collector reads
        # exists and nothing below can raise: registering a half-built
        # peer with the process hub would leak its registry (and a JSONL
        # sink thread) if __init__ died before close() became reachable.
        self._obs: Optional[_PeerObs] = None
        if _obs.obs_enabled() and self.config.obs.enabled:
            self._obs = _PeerObs(self)
        self._recv_thread = threading.Thread(
            target=self._recv_loop, daemon=True, name="st-recv"
        )
        self._send_thread = threading.Thread(
            target=self._send_loop, daemon=True, name="st-send"
        )
        self._recv_thread.start()
        self._send_thread.start()

    # -- user API (the reference's three calls) -----------------------------

    def read(self) -> Any:
        """Snapshot of the shared state (reference copyToTensor)."""
        return self.st.read()

    def add(self, delta: Any) -> None:
        """Merge an additive update into the shared state; it becomes visible
        locally at once and streams to every peer asynchronously (reference
        addFromTensor)."""
        self.st.add(delta)
        if self._trace_wire and self._engine is None:
            # a local update is a fresh generation: re-seed the pending
            # trace stamp (the engine tier stamps inside st_engine_add)
            self._trace_stamp = (self.node.obs_id, self._now_ns(), 0)
        self._wake.set()

    def wait_ready(self, timeout: float = 30.0) -> None:
        """Block until joined and the state stream is flowing. Replaces the
        reference's busy-wait-until-nonzero (quirk Q4: spins a core and hangs
        forever on an all-zero tensor) with an explicit handshake."""
        if not self._ready.wait(timeout):
            if self._error is not None:
                raise self._error
            raise TimeoutError(f"not ready after {timeout}s")
        if self._error is not None:
            raise self._error

    def drain(self, timeout: float = 60.0, tol: float = 0.0) -> bool:
        """Block until every outgoing link residual is down to ``tol`` RMS,
        the transport send queues are empty, AND every sent frame has been
        acknowledged by its receiver — i.e. all local updates now live in our
        neighbors' replicas (they apply + flood atomically on receive). After
        a successful drain, close() loses nothing. Use before :meth:`close`
        to leave gracefully (the reference has no flush concept at all; a
        leaving node takes its undelivered residuals down with the whole
        process, quirk Q8). A crash without drain instead falls under the
        bounded-loss arm of the delivery contract (core.SharedTensor).

        ``tol=0`` caveat: the pow2 scale policy flushes SUBNORMAL rms to
        scale 0 (idle), so residual dust below the smallest normal f32
        (~1.2e-38) can never drain — after long add sequences use a tiny
        nonzero tol (e.g. 1e-30) unless the workload is known to cancel
        exactly."""
        deadline = time.time() + timeout
        # the native engine quiesces in microseconds once residuals hit
        # zero; the Python tier needs the coarser poll to stay off its lock
        poll = 0.005 if self._engine is not None else 0.05
        while time.time() < deadline and not self._stop.is_set():
            # the carry pseudo-slot (CARRY_LINK) is excluded: an orphan by
            # definition has nobody to deliver to — its owed mass rides the
            # next re-graft, not this drain
            links = [l for l in self.st.link_ids if l >= 0]
            if all(self.st.residual_rms(l) <= tol for l in links):
                stats = [self.node.stats(l) for l in self.node.links]
                if (
                    all(s is None or s.send_queue == 0 for s in stats)
                    and self.st.inflight_total() == 0
                ):
                    return True
            time.sleep(poll)
        return False

    def leave(self, timeout: float = 60.0, tol: float = 1e-30) -> bool:
        """Graceful exit that loses nothing even MID-STREAM: (1) seal
        ingress — further incoming frames are discarded unACKed, so their
        senders keep them ledgered and re-deliver after our departure's
        re-graft; (2) drain everything we owe; (3) close. Returns the drain
        verdict.

        A bare ``drain(); close()`` has a loss window this closes: a frame
        that lands (and is applied + ACKed, flooding into our other links'
        residuals) in the instant between drain's last check and close dies
        with those residuals, and its sender — holding our ACK — never
        re-sends. Sealing first makes new arrivals un-ACKed, so the
        interrupted mass re-routes around us instead. (Wire-compat mode has
        no ACK ledger; there a mid-stream leave keeps the reference
        protocol's lossy semantics.) ``tol`` defaults just above the
        subnormal-dust floor (see :meth:`drain`)."""
        if self._engine is not None:
            self._engine.seal()  # emits the engine-tier seal event itself
        elif self._obs is not None:
            self._obs.event("seal", self.node.obs_id)
        self._sealed = True
        ok = self.drain(timeout=timeout, tol=tol)
        self.close()
        return ok

    # -- r12 cluster lifecycle (tentpole) ------------------------------------
    #
    # Consistent-cut protocol. The root pauses its own production, floods a
    # wire.SNAP marker down every child link, and each node on SNAP: pauses,
    # forwards the marker, waits for (a) every child's SNAP_ACK and (b) its
    # own in-flight ledgers to drain empty, then captures its shard (or
    # loads it — op "load" is the in-place restore) and acks up. Per-link
    # FIFO makes this a Chandy-Lamport-style cut with EMPTY channels: the
    # marker follows the sender's last pre-pause data, a child's SNAP_ACK
    # follows its last pre-capture data, and "ledger empty" means
    # everything we sent was applied — so at every capture instant both
    # ends of every link agree on the stream position and nothing is in
    # flight. No retransmission storm and no double-apply on restore, with
    # no seq surgery. Control traffic is outside the chaos classes (r06
    # rule), so a barrier completes deterministically even mid-chaos.

    @property
    def node_name(self) -> str:
        """Stable lifecycle name (LifecycleConfig.node_name, or the
        process-unique ``node-<obs_id>`` fallback)."""
        return (
            self.config.lifecycle.node_name or f"node-{self.node.obs_id}"
        )

    def snapshot_cluster(
        self,
        dirpath: str,
        snap_id: Optional[str] = None,
        timeout: Optional[float] = None,
    ) -> dict:
        """Root-initiated consistent-cut snapshot of the WHOLE tree into
        ``dirpath`` (one shard per node + MANIFEST.json with per-node
        sha256 digests). Blocks until the barrier completes; the tree is
        resumed before this returns — on success, failure, or timeout (a
        lifecycle op may fail, the cluster must never stay paused).
        Returns the result dict (``manifest``, ``duration_sec``, ...)."""
        if self._uplink is not None:
            raise RuntimeError(
                "snapshot_cluster is root-initiated: this node has an "
                "uplink (use ctl against the root, or call it there)"
            )
        return self._lc_request(
            {
                "op": "save",
                "dir": str(dirpath),
                "id": str(snap_id or f"snap-{time.monotonic_ns():x}"),
            },
            timeout,
        )

    def restore_cluster(
        self, dirpath: str, timeout: Optional[float] = None
    ) -> dict:
        """Root-initiated IN-PLACE restore of a live tree to the
        consistent cut under ``dirpath``: same barrier as
        :meth:`snapshot_cluster`, but at the quiesced instant every node
        LOADS its shard (replica + surviving links' residuals + carry +
        governor state) instead of writing one. Link wire seqs are never
        rewound — the drained-empty ledgers are what make the restored
        residuals pairwise consistent (st_engine_restore_ex). Subscriber
        links are re-seeded from the restored replica, so no FRESH mark
        can verify a read across the cut. Requires unchanged membership
        since the snapshot for full fidelity: residuals of links that no
        longer exist are dropped (their subtrees' own diff handshakes
        already repaired that mass — the load_shared contract)."""
        from ..utils import checkpoint as ckpt

        problems = ckpt.verify_manifest(dirpath)
        if problems:
            raise ValueError(
                f"snapshot at {dirpath} fails its manifest audit: "
                + "; ".join(problems)
            )
        if self._uplink is not None:
            raise RuntimeError("restore_cluster is root-initiated")
        return self._lc_request(
            {
                "op": "load",
                "dir": str(dirpath),
                "id": str(ckpt.load_manifest(dirpath).get("snap_id", "?")),
            },
            timeout,
        )

    def drain_node(self, target: str) -> None:
        """Planned migration: route a drain command (wire.CTL) down the
        tree to ``target``, which then runs the r06-proven graceful exit —
        seal ingress, drain everything it owes, close — and its children
        re-graft through the quarantine → carry → re-graft path with zero
        mass loss. Fire-and-forget: watch ``obs.top``'s drain row (or the
        membership events) for completion."""
        if self._uplink is not None:
            raise RuntimeError("drain_node is root-initiated")
        if self.config.transport.wire_compat:
            raise RuntimeError(
                "drain routing needs the native protocol's control plane"
            )
        if str(target) == self.node_name:
            raise ValueError(
                "cannot drain the root from itself — fail the root over "
                "first (master failover) or drain its children instead"
            )
        doc = {"op": "drain", "target": str(target), "from": self.node_name}
        if self._obs is not None:
            self._obs.event("ctl_cmd", self.node.obs_id, detail="drain")
        self._ctl_forward(doc, exclude=None)

    def _lc_request(self, req: dict, timeout: Optional[float]) -> dict:
        if self.config.transport.wire_compat:
            raise RuntimeError(
                "the lifecycle barrier needs the native protocol's typed "
                "control plane — the reference wire format cannot carry it "
                "(single-peer save_shared/load_shared still works)"
            )
        budget = (
            timeout
            if timeout is not None
            else self.config.lifecycle.snapshot_timeout_sec
        )
        # one barrier at a time: _lc_done/_lc_result are a single slot, so
        # concurrent API callers serialize here instead of a second
        # request's overlap-refusal waking the first with a spurious
        # failure while its barrier is still running. Results are also
        # MATCHED to requests by uid: a caller that timed out leaves its
        # barrier running, and its late result must never be handed to
        # the next caller as that caller's own verdict.
        import uuid as _uuid

        req["req"] = _uuid.uuid4().hex
        with self._lc_api_mu:
            req["deadline"] = time.monotonic() + budget
            req["budget_sec"] = budget
            self._lc_done.clear()
            self._lc_result = None
            self._lc_requests.append(req)
            self._wake.set()
            deadline = time.monotonic() + budget + 10.0
            while True:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise TimeoutError(
                        f"lifecycle {req['op']} barrier did not complete "
                        f"inside {budget}s (+grace)"
                    )
                if not self._lc_done.wait(min(remaining, 1.0)):
                    continue
                res = self._lc_result
                if res is not None and res.get("req") == req["req"]:
                    break
                # a previously-abandoned barrier's late verdict: discard
                # and keep waiting for OUR result
                self._lc_done.clear()
        if not res.get("ok"):
            raise RuntimeError(
                f"lifecycle {req['op']} failed: {res.get('error')}"
            )
        return res

    def _set_paused(self, paused: bool) -> None:
        """Quiesce (or resume) data production. Pausing is SYNCHRONOUS
        across one in-flight sender pass on BOTH tiers: the engine's
        st_engine_pause waits out its sender's pass boundary, and the
        python tier waits for two _send_loop pass increments — a pass
        already past its paused-check when the flag lands may still
        enqueue data produced from pre-pause state, and the consistent
        cut's SNAP marker must follow the last such message on every
        link, never overtake it."""
        if paused == self._paused:
            return
        self._paused = paused
        if self._engine is not None:
            self._engine.pause(paused)
        elif paused and self._send_thread.is_alive():
            g0 = self._send_pass
            deadline = time.monotonic() + 2.0
            while (
                self._send_pass < g0 + 2
                and time.monotonic() < deadline
                and not self._stop.is_set()
            ):
                self._wake.set()
                time.sleep(0.001)
        self._pause_deadline = (
            time.monotonic() + self.config.lifecycle.pause_timeout_sec
            if paused
            else 0.0
        )
        if self._obs is not None:
            self._obs.event(
                "lifecycle_pause" if paused else "lifecycle_resume",
                self.node.obs_id,
            )
        self._wake.set()

    def _lc_children(self, exclude: Optional[int] = None) -> list[int]:
        """Writer links the barrier/CTL flood covers: every attached codec
        link except the uplink, subscriber leaves (no shard, no drain —
        they re-seed from scratch), and ``exclude`` (the marker's source)."""
        up = self._uplink
        return [
            l
            for l in self.st.link_ids
            if l >= 0
            and l != up
            and l != exclude
            and l not in self._sub_links
        ]

    def _ctl_forward(self, doc: dict, exclude: Optional[int]) -> None:
        payload = wire.encode_lifecycle(wire.CTL, doc)
        for link in self._lc_children(exclude):
            try:
                self._send_blocking(link, payload)
            except Exception:
                log.exception("CTL forward failed on link %d", link)

    def _lc_begin(self, doc: dict, from_link: Optional[int]) -> None:
        """Enter the barrier (recv thread only). ``from_link`` is the
        uplink that delivered the SNAP marker; None = root-initiated."""
        if self._lc_op is not None:
            if doc.get("id") == self._lc_op["id"]:
                return  # duplicate marker (e.g. replayed): already in it
            msg = (
                f"{self.node_name}: lifecycle barrier overlap "
                f"({self._lc_op['id']} active, {doc.get('id')} refused)"
            )
            log.warning(msg)
            self._lc_errors += 1
            if from_link is None:
                self._lc_result = {
                    "ok": False, "error": msg, "req": doc.get("req"),
                }
                self._lc_done.set()
            else:
                # NACK so the parent's barrier completes with the error
                # recorded instead of hanging on this subtree
                self._send_blocking(
                    from_link,
                    wire.encode_lifecycle(
                        wire.SNAP_ACK,
                        {"id": doc.get("id"), "nodes": [], "errors": [msg]},
                    ),
                )
            return
        op = {
            "op": doc.get("op", "save"),
            "id": str(doc.get("id")),
            "dir": str(doc.get("dir", "")),
            "req": doc.get("req"),
            "from": from_link,
            "t0": time.monotonic(),
            "deadline": doc.get("deadline"),
            # the barrier's time budget: the root's remaining budget as
            # carried by the marker; a budget-less marker (shouldn't
            # happen from this build's roots) falls back to the LOCAL
            # pause timeout — the conservative never-stay-paused default
            "budget": float(
                doc.get(
                    "budget_sec",
                    self.config.lifecycle.snapshot_timeout_sec
                    if from_link is None
                    else self.config.lifecycle.pause_timeout_sec,
                )
            ),
            "waiting": set(self._lc_children(from_link)),
            "entries": [],
            "errors": [],
            "marked": False,  # markers flood from _lc_tick once the
            # paused data plane has fully flushed (ordering note there)
            "captured": False,
            "acked": False,  # SNAP_ACK delivered (retried until it is)
        }
        self._lc_op = op
        self._set_paused(True)
        # the pause safety deadline scales to the BARRIER's budget, not
        # the bare pause_timeout: a deep tree's barrier legitimately
        # outlives the default 30 s (slow drains), and a captured child
        # auto-resuming mid-barrier would silently tear the cut the root
        # then reports as ok. The marker carries the root's remaining
        # budget down (+5 s RESUME-propagation grace); the deadline still
        # bounds a dead-root wedge.
        self._pause_deadline = time.monotonic() + op["budget"] + 5.0
        if self._obs is not None:
            self._obs.event(
                "snap_begin", self.node.obs_id, arg=len(op["waiting"]),
                detail=op["op"],
            )

    def _lc_mark_children(self, op: dict) -> None:
        """Flood the SNAP marker down — only AFTER every data message this
        node will ever send pre-cut has been DELIVERED: _set_paused already
        synchronized the in-flight sender pass, the device-tier pipeline
        gauge must read empty (a paused pipeline only drains), and every
        unacked ledger must be empty. The ledger condition is what makes
        the cut sound under LOSS: a chaos-dropped frame's go-back-N
        retransmission would otherwise arrive AFTER the marker — applied
        past the receiver's capture while our shard records it delivered,
        i.e. mass in neither shard (fatal for the in-place restore, which
        has no diff-join to re-derive it). Paused production + active
        retransmission drain the ledgers in bounded time; a black-holed
        link tears down at ack_retry_limit and leaves the barrier through
        the LINK_DOWN error path."""
        if self._engine is None and self._pipe_frames > 0:
            return  # pipeline still draining; next tick re-checks
        if self.st.inflight_total() != 0:
            return  # undelivered pre-cut data; retransmission is on it
        op["marked"] = True
        now = time.monotonic()
        remaining = (
            op["deadline"] - now
            if op["from"] is None and op.get("deadline")
            else op["budget"] - (now - op["t0"])
        )
        fwd = wire.encode_lifecycle(
            wire.SNAP,
            {
                "op": op["op"], "id": op["id"], "dir": op["dir"],
                "parent": self.node_name,
                # the root's remaining budget rides the marker so every
                # node's pause deadline covers the WHOLE barrier
                "budget_sec": max(5.0, remaining),
            },
        )
        for link in list(op["waiting"]):
            if not self._send_blocking(link, fwd):
                op["waiting"].discard(link)
                op["errors"].append(
                    f"{self.node_name}: SNAP marker send failed on link "
                    f"{link}"
                )

    def _lc_quiesced(self) -> bool:
        """Paused AND nothing in flight: every unacked ledger empty (our
        sends were applied by their receivers) and every transport send
        queue drained (our markers/acks actually left)."""
        if self.st.inflight_total() != 0:
            return False
        for link in self.node.links:
            s = self.node.stats(link)
            if s is not None and s.send_queue != 0:
                return False
        return True

    def _lc_tick(self) -> None:
        """One barrier-driving pass (recv thread, every loop iteration)."""
        while self._lc_requests:
            self._lc_begin(self._lc_requests.popleft(), None)
        op = self._lc_op
        now = time.monotonic()
        if op is None:
            if (
                self._paused
                and self._pause_deadline
                and now > self._pause_deadline
            ):
                # never-leave-paused safety net (op state already gone)
                log.warning("lifecycle pause expired with no barrier — resuming")
                self._lc_errors += 1
                self._set_paused(False)
            self._ctl_poll(now)
            return
        if op["from"] is None:
            if op.get("deadline") and now > op["deadline"]:
                missing = sorted(op["waiting"])
                op["errors"].append(
                    f"{self.node_name}: barrier timeout "
                    f"(awaiting links {missing})" if missing else
                    f"{self.node_name}: barrier timeout (quiesce)"
                )
                self._lc_finish(ok=False)
                return
        elif now > self._pause_deadline:
            # RESUME never arrived (root/parent died mid-barrier): unpause
            # rather than stay frozen — the op is abandoned
            log.warning(
                "lifecycle barrier %s: no RESUME before the pause "
                "deadline — auto-resuming", op["id"],
            )
            self._lc_errors += 1
            self._lc_op = None
            self._set_paused(False)
            return
        if not op["marked"]:
            self._lc_mark_children(op)
        if op["captured"]:
            if op["from"] is not None and not op["acked"]:
                # the SNAP_ACK send failed (or over-cap encode fell back)
                # on an earlier tick: retry until delivered or the pause
                # deadline abandons the barrier — a latched-but-unacked
                # capture would otherwise wedge the parent into its
                # timeout with no error naming the cause
                self._lc_send_ack(op)
            return
        if (
            not op["marked"]
            or op["waiting"]
            or not self._lc_quiesced()
        ):
            return
        # subtree complete + locally quiesced: the cut instant for this node
        try:
            if op["op"] == "save":
                entry = self._write_shard(op["dir"], op["id"])
                op["entries"].append(entry)
                self._snap_total += 1
            else:
                self._load_shard_inplace(op["dir"])
                op["entries"].append(
                    {"node": self.node_name, "restored": True}
                )
                self._restore_total += 1
        except Exception as e:
            log.exception("lifecycle %s failed at %s", op["op"], self.node_name)
            op["errors"].append(f"{self.node_name}: {e!r}")
            self._lc_errors += 1
        op["captured"] = True
        if op["from"] is not None:
            self._lc_send_ack(op)
            # stay paused until the root's RESUME releases the barrier
        else:
            self._lc_finish(ok=not op["errors"])

    def _lc_send_ack(self, op: dict) -> None:
        doc = {
            "id": op["id"],
            "nodes": op["entries"],
            "errors": op["errors"],
        }
        try:
            payload = wire.encode_lifecycle(wire.SNAP_ACK, doc)
        except ValueError:
            # subtree manifest exceeded the wire cap (clusters past the
            # digest's own per-node bound): deliver the verdict with the
            # entries dropped rather than wedging the whole barrier — the
            # root fails it honestly, naming this node
            doc = {
                "id": op["id"],
                "nodes": [],
                "errors": op["errors"][:8]
                + [
                    f"{self.node_name}: subtree manifest exceeded the wire "
                    f"cap ({len(op['entries'])} shard entries dropped)"
                ],
            }
            payload = wire.encode_lifecycle(wire.SNAP_ACK, doc)
        if self._send_blocking(op["from"], payload):
            op["acked"] = True

    def _lc_finish(self, ok: bool) -> None:
        """Root only: write the manifest (save op), release the barrier
        down the tree, resume, and hand the verdict to the waiter. Runs on
        EVERY exit path — the cluster never stays paused."""
        op = self._lc_op
        assert op is not None and op["from"] is None
        dur = time.monotonic() - op["t0"]
        result: dict = {
            "ok": ok,
            "op": op["op"],
            "id": op["id"],
            "req": op.get("req"),
            "dir": op["dir"],
            "duration_sec": dur,
            "nodes": len(op["entries"]),
            "errors": op["errors"],
        }
        if op["errors"]:
            result["error"] = "; ".join(str(e) for e in op["errors"])
        if ok and op["op"] == "save":
            from ..utils import checkpoint as ckpt

            try:
                result["manifest"] = ckpt.write_manifest(
                    op["dir"], op["id"], op["entries"],
                    extra={"root": self.node_name, "duration_sec": dur},
                )
            except OSError as e:
                result["ok"] = False
                result["error"] = f"manifest write failed: {e}"
        self._snap_last_dur = dur
        resume = wire.encode_lifecycle(wire.RESUME, {"id": op["id"]})
        for link in self._lc_children():
            self._send_blocking(link, resume)
        self._lc_op = None
        self._set_paused(False)
        if self._obs is not None:
            self._obs.event(
                "snap_done", self.node.obs_id,
                arg=result["nodes"], detail=op["op"],
            )
        self._lc_result = result
        self._lc_done.set()

    def _write_shard(self, dirpath: str, snap_id: str) -> dict:
        """Capture this node's shard at the (quiesced) cut instant. The
        engine capture is ONE native lock acquisition (snapshot_ex), so
        sign2 residual planes, in-flight cascade frames and governor state
        cannot tear; the python tier's snapshot_all has the same contract
        under its state lock."""
        from ..utils import checkpoint as ckpt

        up = self._uplink
        if self._engine is not None:
            values, links, meta = self._engine.snapshot_ex()
        else:
            values, links = self.st.snapshot_all()
            values = np.asarray(values, np.float32)
            meta = {}
            with self._ack_mu:
                tx = dict(self._tx_seq)
            for lid in links:
                if lid < 0:
                    continue
                meta[lid] = {
                    "tx_seq": tx.get(lid, 0),
                    "rx_count": self._rx_count.get(lid, 0),
                    "prec": 1,
                    "sub": lid in self._sub_links,
                }
        entries = []
        for lid, resid in links.items():
            if lid < 0:
                entries.append(
                    {
                        "id": lid, "role": "carry",
                        "resid": np.asarray(resid, np.float32),
                    }
                )
                continue
            m = meta.get(lid, {})
            sub = bool(m.get("sub")) or lid in self._sub_links
            entries.append(
                {
                    "id": lid,
                    "role": "up" if lid == up else ("sub" if sub else "child"),
                    "tx_seq": m.get("tx_seq", 0),
                    "rx_count": m.get("rx_count", 0),
                    "prec": m.get("prec", 1),
                    # subscriber links persist meta only: a read-only leaf
                    # re-seeds from scratch on restore
                    "resid": None if sub else np.asarray(resid, np.float32),
                }
            )
        entry = ckpt.save_cluster_shard(
            dirpath,
            self.node_name,
            snap_id,
            self.st.spec.layout_digest(),
            values,
            entries,
            wire_version=self._wire_version,
        )
        if self._obs is not None:
            self._obs.event(
                "snap_shard", self.node.obs_id, arg=len(entries)
            )
        return entry

    def _load_shard_inplace(self, dirpath: str) -> None:
        """The in-place restore step (op "load"), at the quiesced barrier
        instant: replica + surviving writer links' residuals + carry +
        governor state from this node's shard, then a forced re-seed of
        every subscriber link from the restored replica — across the cut a
        subscriber's state is superseded and NO seq gap would ever expose
        it (the falsely-verified-read hazard the lifecycle test pins)."""
        import os as _os

        from ..utils import checkpoint as ckpt

        path = _os.path.join(dirpath, ckpt.shard_filename(self.node_name))
        shard = ckpt.load_cluster_shard(path)
        if shard["layout"] != self.st.spec.layout_digest():
            raise ValueError(
                f"shard {path} was written for a different table layout"
            )
        live = set(self.st.link_ids)
        links: dict[int, np.ndarray] = {}
        meta: dict[int, dict] = {}
        for lid, ent in shard["links"].items():
            if ent.get("role") == "carry":
                if ent.get("resid") is not None:
                    links[CARRY_LINK] = ent["resid"]
                continue
            if ent.get("role") == "sub" or ent.get("resid") is None:
                continue
            if lid in live:
                links[lid] = ent["resid"]
                meta[lid] = {"prec": ent.get("prec", 1)}
        if self._engine is not None:
            self._engine.restore_ex(shard["values"], links, meta)
        else:
            with self.st._lock:
                self.st.values = self.st._asarray(shard["values"])
                for lid, r in links.items():
                    if lid in self.st._links or lid == CARRY_LINK:
                        self.st._links[lid] = self.st._asarray(r)
        for lid, rng in list(self._sub_links.items()):
            self._attach_sub(lid, rng)
        self._wake.set()

    def _restore_at_startup(self, path: str) -> None:
        """Full-cluster restart restore (LifecycleConfig.restore_path),
        before the data plane starts. Values load into the replica; a
        NON-master node's checkpointed uplink residual (+ carry) becomes
        the re-graft carry, so the normal join handshake re-delivers
        exactly the owed up-flow (snapshot claims ``values - carry`` as
        tree-known; the diff seed covers the rest). The master drops its
        carry — its replica is now the authoritative seed and every
        child's diff join pulls the missing mass from it (the
        BECAME_MASTER discipline). Child-link residuals are discarded on
        BOTH: the children's own re-join diffs re-derive the down-flow
        (checkpoint.restore_carry_from_shard)."""
        from ..utils import checkpoint as ckpt

        shard = ckpt.load_cluster_shard(path)
        if shard["layout"] != self.st.spec.layout_digest():
            raise ValueError(
                f"restore shard {path} was written for a different table "
                f"layout"
            )
        values = shard["values"]
        carry = None if self.is_master else ckpt.restore_carry_from_shard(shard)
        if self._engine is not None:
            self._engine.restore_state(
                values, {} if carry is None else {CARRY_LINK: carry}
            )
        else:
            with self.st._lock:
                self.st.values = self.st._asarray(values)
                if carry is not None:
                    self.st._links[CARRY_LINK] = self.st._asarray(carry)
        self._restored_from = path
        self._restore_total += 1
        log.info(
            "restored %s from shard %s (snap %s)%s",
            self.node_name, path, shard["meta"].get("snap_id"),
            "" if carry is None else " with re-graft carry",
        )

    def _start_drain(self) -> None:
        """This node is the CTL drain target: run the graceful exit on a
        helper thread (leave() blocks and joins the recv thread — it must
        never run ON the recv thread)."""
        if self._draining:
            return
        self._draining = True
        self._drain_total += 1
        if self._obs is not None:
            self._obs.event("drain_begin", self.node.obs_id)
        grace = self.config.lifecycle.drain_grace_sec

        def _run():
            try:
                ok = self.leave(timeout=grace)
                log.info(
                    "drain of %s %s", self.node_name,
                    "complete" if ok else "timed out (closed anyway)",
                )
            except Exception:
                log.exception("drain of %s failed", self.node_name)

        threading.Thread(target=_run, daemon=True, name="st-drain").start()

    def _handle_ctl_msg(self, doc: dict, from_link: Optional[int]) -> None:
        op = doc.get("op")
        if op == "drain":
            if doc.get("target") == self.node_name:
                self._start_drain()
            else:
                self._ctl_forward(doc, exclude=from_link)
        else:
            log.warning("ignoring unknown CTL op %r", op)

    def _ctl_poll(self, now: float) -> None:
        """Root-side operator command channel: poll
        ``LifecycleConfig.ctl_dir`` for a cmd.json written by
        ``python -m shared_tensor_tpu.ctl`` and execute it on a worker
        thread (a snapshot blocks on the barrier this recv thread drives)."""
        lc = self.config.lifecycle
        if not lc.ctl_dir or self._uplink is not None:
            return
        if now - self._ctl_last_poll < 0.25:
            return
        self._ctl_last_poll = now
        import json as _json
        import os as _os

        cmd_path = _os.path.join(lc.ctl_dir, "cmd.json")
        try:
            with open(cmd_path) as f:
                cmd = _json.load(f)
            _os.unlink(cmd_path)  # claim
        except (OSError, ValueError):
            return  # absent, or mid-write; next poll gets it
        if self._obs is not None:
            self._obs.event(
                "ctl_cmd", self.node.obs_id, detail=str(cmd.get("op"))
            )
        threading.Thread(
            target=self._ctl_execute, args=(cmd,), daemon=True,
            name="st-ctl",
        ).start()

    def _ctl_execute(self, cmd: dict) -> None:
        import os as _os

        res: dict = {"req_id": cmd.get("req_id"), "op": cmd.get("op")}
        try:
            op = cmd.get("op")
            if op == "snapshot":
                r = self.snapshot_cluster(cmd["dir"], cmd.get("id"))
                res.update(
                    ok=True, id=r["id"], nodes=r["nodes"],
                    duration_sec=r["duration_sec"],
                    manifest=r.get("manifest"),
                )
            elif op == "restore":
                r = self.restore_cluster(cmd["dir"])
                res.update(
                    ok=True, id=r["id"], nodes=r["nodes"],
                    duration_sec=r["duration_sec"],
                )
            elif op == "drain":
                self.drain_node(cmd["target"])
                res.update(ok=True, target=cmd["target"], initiated=True)
            else:
                res.update(ok=False, error=f"unknown ctl op {op!r}")
        except Exception as e:
            res.update(ok=False, error=str(e))
        from ..utils.checkpoint import atomic_write_json

        lc = self.config.lifecycle
        path = _os.path.join(lc.ctl_dir, "result.json")
        try:
            atomic_write_json(path, res)
        except Exception as e:
            # the CLI is polling for SOME verdict: even a non-serializable
            # result value must not leave it timing out undiagnosed
            log.exception("ctl result write failed")
            try:
                atomic_write_json(
                    path,
                    {
                        "req_id": res.get("req_id"), "ok": False,
                        "error": f"result write failed: {e}",
                    },
                )
            except Exception:
                pass

    def close(self) -> None:
        """Leave the tree. Peers survive and re-graft (the reference prints an
        apology and exit(-1)s the entire process instead — quirk Q8).

        Idempotent and serialised: a second caller (``ctl drain`` runs
        leave() -> close() on a helper thread while the owner's ``finally``
        closes too) waits for the first teardown to finish and returns. Two
        threads inside the teardown at once tore the node down under a
        still-running engine and aborted the interpreter (ROADMAP D0)."""
        with self._close_lock:
            if self._closed:
                return
            self._closed = True
            self._stop.set()
            self._wake.set()
            for t in (self._send_thread, self._recv_thread):
                t.join(timeout=5.0)
            if self._engine is not None:
                # engine threads block inside the node's queues/condvars:
                # they must stop BEFORE the node is torn down
                self._engine.stop()
            if self._obs is not None:
                # final native-ring drain + sink/registry teardown, BEFORE
                # the node closes so the close-path events still merge in
                self._obs.close()
            self.node.close()
            if self._engine is not None:
                self._engine.destroy()

    # -- introspection -------------------------------------------------------

    @property
    def ready(self) -> bool:
        return self._ready.is_set()

    def _delivery_counts(self) -> tuple[int, int, int, int, int]:
        """(frames_out, frames_in, updates, msgs_out, msgs_in) — ONE
        engine-counter snapshot when native (separate reads would mix
        instants and could show e.g. msgs_in > frames_in mid-run)."""
        if self._engine is not None:
            c = self._engine._counters()
            return int(c[0]), int(c[1]), int(c[2]), int(c[3]), int(c[4])
        fo, fi = self.st.frames_out, self.st.frames_in
        up = self.st.updates
        if self.config.transport.wire_compat:
            # no ACK ledger in the reference protocol: one frame == one
            # message (metrics() taxonomy)
            return fo, fi, up, fo, fi
        with self._ack_mu:
            mo = sum(self._acked.values()) + sum(
                len(v) for v in self._unacked.values()
            )
            mi = sum(self._rx_count.values())
        return fo, fi, up, mo, mi

    def _obs_collect(self) -> dict:
        """Registry collector: the canonical-schema view of everything this
        peer can report that is not a live histogram — sampled once per
        snapshot/scrape (obs/schema.py is the name authority)."""
        import math

        out: dict = {}
        fo, fi, up, mo, mi = self._delivery_counts()
        out["st_frames_out_total"] = fo
        out["st_frames_in_total"] = fi
        out["st_updates_total"] = up
        out["st_msgs_out_total"] = mo
        out["st_msgs_in_total"] = mi
        out["st_inflight_msgs"] = self.st.inflight_total()
        # r07 buffer-pool planes — the zero-per-message-allocation
        # assertion: in steady state the acquire counters grow while the
        # alloc/miss counters stay flat (every buffer is a reuse).
        # st_tx_slot_* is the frame-slot ring (engine tx ring, or
        # wire.FramePool on the Python tier); st_transport_* is the C
        # transport's per-link tx/rx recycling.
        if self._engine is not None:
            p = self._engine.pool_stats()
            out["st_tx_slot_acquires_total"] = p["tx_slot_acquires"]
            out["st_tx_slot_alloc_events_total"] = p["tx_slot_alloc_events"]
            out["st_tx_slots_allocated"] = p["tx_slots_allocated"]
        elif self._tx_pool is not None:
            p = self._tx_pool.stats()
            out["st_tx_slot_acquires_total"] = p["tx_slot_acquires"]
            out["st_tx_slot_alloc_events_total"] = p["tx_slot_alloc_events"]
            out["st_tx_slots_allocated"] = p["tx_slots_free"]
        tp = self.node.pool_stats()
        out["st_transport_tx_acquires_total"] = tp["tx_acquires"]
        out["st_transport_tx_misses_total"] = tp["tx_misses"]
        out["st_transport_rx_acquires_total"] = tp["rx_acquires"]
        out["st_transport_rx_misses_total"] = tp["rx_misses"]
        out["st_transport_zc_msgs_total"] = tp["zc_msgs"]
        # r10 writer-side serving gauges/counters. The python-tier counts
        # are authoritative only on the python tier (the engine's C sender
        # owns them otherwise and obs_stats() below overrides).
        out["st_sub_links"] = len(self._sub_links)
        out["st_sub_msgs_out_total"] = self._sub_msgs_out
        out["st_sub_fresh_out_total"] = self._sub_fresh_out
        # r12 lifecycle telemetry (obs.top's lifecycle rows; schema.py).
        # st_wire_version rides the per-node digest breakdown so
        # ``ctl versions`` can audit a rolling upgrade from the root.
        op = self._lc_op
        out["st_wire_version"] = self._wire_version
        out["st_lifecycle_paused"] = 1 if self._paused else 0
        out["st_snapshot_in_progress"] = (
            1 if op is not None and op.get("op") == "save" else 0
        )
        out["st_snapshot_shards_acked"] = self._snap_acks
        out["st_snapshot_total"] = self._snap_total
        out["st_snapshot_last_duration_seconds"] = self._snap_last_dur
        out["st_restore_total"] = self._restore_total
        out["st_drain_in_progress"] = 1 if self._draining else 0
        out["st_drain_total"] = self._drain_total
        out["st_lifecycle_errors_total"] = self._lc_errors
        if self._engine is not None:
            out.update(self._engine.obs_stats())
        out["st_corrupt_scales_zeroed_total"] = wire.corrupt_scales_zeroed()
        from ..obs import events as _events

        out["st_obs_events_dropped_total"] = _events.native_dropped()
        # r09 convergence telemetry. st_residual_norm: the L2 norm over
        # EVERY error-feedback residual (carry slot included — that is
        # owed mass too), derived from the per-link RMS both tiers already
        # serve: norm^2 = sum(rms_l^2 * n). 0 = quiesced, nothing owed.
        # The python tier's link_ids lists the carry pseudo-slot itself;
        # the engine keeps its carry outside the link map, so query it
        # explicitly (st_engine_residual_rms answers -1 with the carry).
        ss = 0.0
        n = self.st.spec.total_n
        links = list(self.st.link_ids)
        if self._engine is not None:
            links.append(CARRY_LINK)
        for link in links:
            rms = self.st.residual_rms(link)
            ss += rms * rms * n
        out["st_residual_norm"] = math.sqrt(ss)
        # per-link staleness/hops of the latest traced apply: the engine
        # tier serves them over the st_engine_link_obs ABI; the python
        # tier records them at _note_trace time
        if self._engine is not None:
            for link in self.st.link_ids:
                if link < 0:
                    continue
                lo = self._engine.link_obs(link)
                if lo is not None and lo[1] > 0:
                    out[_schema.link_key("st_staleness_seconds", link)] = lo[0]
                    out[_schema.link_key("st_update_hops_last", link)] = lo[1]
        else:
            # r18: live aging — the stored value is the origin GENERATION
            # stamp; its age is computed NOW, so a stalled link's gauge
            # grows between applies (the SLO's staleness signal)
            now_ns = self._now_ns()
            for link, (gen, hop) in list(self._staleness.items()):
                out[_schema.link_key("st_staleness_seconds", link)] = max(
                    0.0, (now_ns - gen) / 1e9
                )
                out[_schema.link_key("st_update_hops_last", link)] = hop
            out["st_traced_msgs_in_total"] = self._traced_in
        # r18 origin attribution + clock plane: the origin node of each
        # link's freshest traced apply (python tier; the engine tier's
        # arrives via the native-ring tap), and this node's estimated
        # offset to the tree root — the health analyzer joins the two to
        # widen staleness to offset-corrected +/- uncertainty.
        for link, origin in list(self._stale_origin.items()):
            out[_schema.link_key("st_staleness_origin", link)] = origin
        if self._clock.known:
            out["st_clock_offset_seconds"] = self._clock.offset_seconds
            out["st_clock_uncertainty_seconds"] = (
                self._clock.uncertainty_seconds
            )
        out["st_clock_probes_total"] = self._clock.probes
        if self._health is not None:
            out.update(self._health.metrics())
        for link in self.node.links:
            s = self.node.stats(link)
            if s is not None:
                out[_schema.link_key("st_link_bytes_out_total", link)] = (
                    s.bytes_out
                )
                out[_schema.link_key("st_link_bytes_in_total", link)] = (
                    s.bytes_in
                )
                out[_schema.link_key("st_link_wire_msgs_out_total", link)] = (
                    s.frames_out
                )
                out[_schema.link_key("st_link_wire_msgs_in_total", link)] = (
                    s.frames_in
                )
                out[_schema.link_key("st_link_residual_rms", link)] = (
                    self.st.residual_rms(link)
                )
                out[_schema.link_key("st_link_send_queue", link)] = s.send_queue
                out[_schema.link_key("st_link_recv_queue", link)] = s.recv_queue
            # r11 stripe telemetry (per logical link): negotiated and
            # surviving socket counts + stripe lifecycle totals
            st = self.node.stripe_stats(link)
            if st is not None and st["stripes"] > 1:
                out[_schema.link_key("st_stripe_count", link)] = st["stripes"]
                out[_schema.link_key("st_stripe_live", link)] = st["live"]
                out["st_stripe_deaths_total"] = (
                    out.get("st_stripe_deaths_total", 0) + st["deaths"]
                )
                out["st_stripe_reroutes_total"] = (
                    out.get("st_stripe_reroutes_total", 0) + st["reroutes"]
                )
            # r14 shm-lane telemetry (per logical link): lane state plus
            # the lane's own message/byte traffic (also folded into the
            # link wire counters above — these isolate the shm share)
            sh = self.node.shm_stats(link)
            if sh is not None and sh["state"] > 0:
                out[_schema.link_key("st_shm_active", link)] = sh["state"]
                out["st_shm_msgs_out_total"] = (
                    out.get("st_shm_msgs_out_total", 0) + sh["msgs_out"]
                )
                out["st_shm_msgs_in_total"] = (
                    out.get("st_shm_msgs_in_total", 0) + sh["msgs_in"]
                )
                out["st_shm_bytes_out_total"] = (
                    out.get("st_shm_bytes_out_total", 0) + sh["bytes_out"]
                )
                out["st_shm_bytes_in_total"] = (
                    out.get("st_shm_bytes_in_total", 0) + sh["bytes_in"]
                )
        # r11 per-link wire precision (engine tier; 1-bit everywhere else)
        if self._engine is not None:
            for link in self.st.link_ids:
                if link < 0:
                    continue
                prec = self._engine.link_precision(link)
                if prec > 0:
                    out[_schema.link_key("st_link_precision", link)] = prec
        return out

    def metrics(
        self, canonical: bool = True, cluster: bool = False
    ) -> dict:
        """Observability the reference entirely lacks (SURVEY.md §5.5).

        Returns the flat canonical-schema view (obs/schema.py is the name
        authority): delivery counters, buffer-pool planes, per-link
        gauges, engine aggregates — all under ``st_*`` names.
        ``cluster=True`` (r09) returns the merged WHOLE-TREE digest from
        this node's vantage — own registry + every subtree digest
        (obs/aggregate.py); at the root that is the cluster.

        The r08 legacy NESTED shape (``frames_out`` / ``delivery.*`` /
        ``links[i].*`` keys) was kept "for one release" as a deprecated
        alias view and is REMOVED as of r13 — ``canonical=False`` raises,
        and tools/lint_metrics.py forbids the alias keys from returning.
        The canonical twins carry byte-equal values: the removal renamed
        keys, never accounting.

        Counter taxonomy (ONE definition per number, reconcilable across
        layers — round-3 verdict Weak #6):

        - ``st_frames_out_total`` / ``st_frames_in_total`` — CODEC frames:
          non-idle quantized frames handed toward the wire / applied from
          it. A burst message carries many; idle (all-zero-scale) frames
          count nowhere. Invariant: a quiesced single-writer pair has
          ``sender frames_out == receiver frames_in``.
        - ``st_msgs_out_total`` / ``st_msgs_in_total`` — wire DATA/BURST
          messages sent / received (what the ACK ledger tracks; an
          undecodable data message still counts on the receive side).
        - ``st_inflight_msgs`` — sent-but-unacked messages; 0 after a
          successful :meth:`drain`. Acked messages = msgs_out - inflight.
          Wire-compat exception: the reference protocol has no ACK
          (delivery degrades to ack-on-enqueue), so there one frame == one
          message — msgs == frames and inflight is always 0.
        - ``st_link_wire_msgs_out_total{link=}`` / ``..in..`` —
          transport-level messages on the socket: data AND control
          (ACK/SYNC/CHUNK/...), excluding keepalives; >= the data-message
          counts above by exactly the control traffic.
          ``st_link_bytes_*`` include framing and keepalives. Wire-compat
          caveat: a compat keepalive IS a real zero-scale frame on the
          wire, indistinguishable at the transport layer — so the
          RECEIVE-side wire count includes idle-period keepalives there
          (the send side still excludes them).
        """
        if cluster:
            return self.cluster_metrics()
        if not canonical:
            raise ValueError(
                "the legacy nested peer.metrics() shape was removed (r13);"
                " consume the canonical st_* schema (obs/schema.py)"
            )
        # the registry snapshot merges the collector (this peer's sampled
        # counters) with the LIVE instruments (histograms, python-tier
        # delivery counters); with obs disabled the collector view alone
        # still serves the schema
        if self._obs is not None:
            return self._obs.registry.snapshot()
        return self._obs_collect()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # -- send side -----------------------------------------------------------

    def _send_loop(self) -> None:
        if self._engine is not None:
            return  # the native engine's own sender thread owns this path
        compat = self.config.transport.wire_compat
        interval = self.config.sync_interval_sec
        # Pipelined frame production (round-2 verdict Weak #2): up to
        # ``send_pipeline_depth`` dispatched-but-unfetched frames per link,
        # each with its device->host copy started asynchronously at dispatch
        # time. Quantizes chain on device, their transfers overlap each other
        # and the host's encode+socket work, so on a high-latency device link
        # the frame rate is bandwidth-bound, not round-trip-bound.
        # Error-feedback ordering is safe: the residual update happens at
        # dispatch time under SharedTensor's lock.
        #
        # Delivery accounting: a sent frame stays in SharedTensor's in-flight
        # ledger until the RECEIVER acknowledges it (wire.ACK, handled in
        # _on_message) — enqueue into the native send queue is NOT delivery
        # (a link can die with queued frames, and their error feedback would
        # be silently lost; measured as the regraft divergence flake). In
        # wire-compat mode the reference protocol has no ACK, so delivery
        # degrades to ack-on-enqueue (the C peer loses everything on death
        # anyway, quirk Q8).
        # r07 double-buffered encode/drain: node.send() copies the pooled
        # slot into the C transport's (recycled) tx buffer and returns the
        # moment it is QUEUED — the C sender thread drains the socket while
        # this loop encodes the next batch into a fresh slot. The pool
        # makes that overlap allocation-free: encode k+1 and the socket
        # write of k proceed concurrently with zero per-message heap
        # traffic on either side (wire.FramePool here, the transport's
        # BufPool below).
        # numpy host tier: quantize is synchronous host work — pipelining
        # just hoards the SharedTensor lock; depth only pays on device tiers
        # where dispatch/transfer are async.
        depth = 1 if self.st.host_tier else max(1, int(self.config.send_pipeline_depth))
        pipe: dict[int, deque] = {}
        hot: set[int] = set()  # links whose last finished frame carried data
        while not self._stop.is_set():
            self._send_pass += 1  # pass boundary (_set_paused's sync wait)
            self._pipe_frames = sum(len(q) for q in pipe.values())
            sent_any = False
            links = [l for l in self.st.link_ids if l >= 0]  # skip CARRY_LINK
            for stale in [l for l in pipe if l not in links]:
                del pipe[stale]  # LINK_DOWN already rolled their ledger back
                hot.discard(stale)
            for link in links:
                if link in self._sub_links:
                    # r10 subscriber link: unledgered send path (no window,
                    # no unacked entries, no retransmission) + FRESH beats.
                    # Paused (r12 quiesce): no production, but an already-
                    # DRAINED link keeps its FRESH beat so a current
                    # subscriber can still verify its bound across the
                    # barrier (an undrained one gets no mark — a read
                    # across the cut must refuse, never falsely verify).
                    if self._paused:
                        self._sub_fresh_beat(link)
                        continue
                    if self._send_sub(link):
                        sent_any = True
                    continue
                if self._paused and not pipe.get(link):
                    # r12 lifecycle quiesce: no NEW production. Frames
                    # already dispatched into the device pipeline still
                    # finish and send below (their error feedback is
                    # applied; the barrier waits for their ACKs), the
                    # pipeline just stops topping up.
                    continue
                if not compat and self._window_full(link):
                    # go-back-N send window: a link whose unacked ledger is
                    # full (stalled peer, black hole in progress) produces
                    # no new frames — bounds both the retained-payload
                    # memory and the retransmittable tail; residual mass
                    # keeps accumulating and quantizes once ACKs reopen
                    # the window (or teardown rolls it into the carry)
                    continue
                if self._burst > 1:
                    # Host-tier burst path: K residual halvings quantized in
                    # one synchronous call, ONE message, ONE ledger entry,
                    # ONE receiver ACK (Config.frame_burst rationale).
                    out = self.st.begin_frame_burst(link, self._burst)
                    if out is None:
                        continue  # link dropped concurrently
                    seq, burst = out
                    if not burst:
                        self.st.ack_frame(link, seq)  # idle: no-op burst
                        hot.discard(link)
                        continue
                    hot.add(link)
                    payload = self._register_data(
                        link,
                        seq,
                        lambda buf, s, t: wire.encode_burst_into(
                            burst, self.st.spec, s, buf, trace=t
                        ),
                    )
                    # crash point: frames ledgered + error feedback applied,
                    # message NOT yet on the wire — death here must roll the
                    # whole burst into the re-graft carry
                    self._fault_point("mid-burst")
                    if self._send_blocking(link, payload, data=True):
                        sent_any = True
                    else:
                        self.st.nack_frame(link)
                    continue
                # Device tier: K-frame bursts when enabled — ONE dispatch +
                # ONE device->host fetch per message (self._burst_device;
                # the device link pays its round trip per FETCH, so K
                # frames per fetch multiply delivered residual per round
                # trip exactly as BURST does on host).
                dev_burst = (
                    not compat
                    and not self.st.host_tier
                    and self._burst_device > 1
                )
                q = pipe.setdefault(link, deque())
                # top up: a cold (idle) link risks one speculative frame per
                # wake tick; a hot link keeps the full pipeline busy —
                # and a paused (r12 quiesce) one only drains, never refills
                target = (
                    0 if self._paused else depth if link in hot else 1
                )
                while len(q) < target:
                    df = (
                        self.st.begin_frame_burst_device(
                            link, self._burst_device
                        )
                        if dev_burst
                        else self.st.begin_frame(link)
                    )
                    if df is None:
                        break  # link dropped concurrently
                    for arr in df[1]:
                        try:
                            arr.copy_to_host_async()
                        except AttributeError:
                            pass  # non-jax array (already host-side)
                    q.append(df)
                if not q:
                    continue

                def _finish(d):
                    return (
                        self.st.finish_frame_burst(d)
                        if dev_burst
                        else self.st.finish_frame(d)
                    )

                seq, df = q.popleft()
                frame = _finish(df)
                while frame is None:
                    # Idle frame (a no-op: scale 0 left the residual
                    # untouched): ack it and drain the remaining speculative
                    # frames — they must be FINISHED, not dropped (an add()
                    # may have raced the dispatches, making a later one
                    # non-idle, and its error feedback is already applied;
                    # dropping it would lose that delta forever).
                    self.st.ack_frame(link, seq)
                    hot.discard(link)
                    if not q:
                        break
                    seq, df = q.popleft()
                    frame = _finish(df)
                if frame is None:
                    continue
                hot.add(link)
                # registered (with its wire seq) BEFORE sending: the
                # receiver's ACK must never race ahead of the ledger entry
                # it acknowledges
                if compat:
                    payload = wire.encode_compat_frame(frame, self.st.spec)
                elif dev_burst:
                    payload = self._register_data(
                        link,
                        seq,
                        lambda buf, s, t: wire.encode_burst_into(
                            frame, self.st.spec, s, buf, trace=t
                        ),
                    )
                else:
                    payload = self._register_data(
                        link,
                        seq,
                        lambda buf, s, t: wire.encode_frame_into(
                            frame, s, buf, trace=t
                        ),
                    )
                self._fault_point("mid-burst")  # ledgered, not yet sent
                if self._send_blocking(link, payload, data=True):
                    if compat:
                        self.st.ack_frame(link, seq)  # no ACK in the protocol
                    sent_any = True
                else:
                    # link died with this frame (and possibly speculative
                    # successors) undelivered: roll their error feedback back
                    # so drop_link/carry sees the full owed residual
                    pipe.pop(link, None)
                    hot.discard(link)
                    self.st.nack_frame(link)
            self._check_retransmit(links)
            if self._stop.is_set():
                return
            if interval > 0:
                time.sleep(interval)
            elif not sent_any:
                # idle: wait for a local add() or an incoming frame to create
                # new residual mass (event-driven wake, fixing quirk Q2)
                self._wake.wait(0.05)
                self._wake.clear()

    def _send_sub(self, link: int) -> bool:
        """One sender pass for a read-only subscriber link (python tier;
        the native engine runs the same logic in C — stengine.cpp's
        subscriber branch). Unledgered: the message is considered delivered
        on enqueue (``ack_frame`` immediately — the compat-tier discipline),
        no unacked entry is kept and no ACK will come; a message the wire
        swallows surfaces as a seq gap at the subscriber, whose resync
        handshake re-seeds the link. Ranged subscriptions ship one
        wire.RDATA per frame (only the subscribed words); full-table ones
        ship ordinary DATA/BURST. Idle links get a periodic wire.FRESH
        drain mark so the subscriber can keep verifying its staleness
        bound while nothing is being written."""
        rng = self._sub_links.get(link)
        if rng is not None:
            # drop out-of-range residual BEFORE scale selection (the range
            # discipline — core.mask_link_residual docstring), but only
            # when the replica has actually moved since the last mask: the
            # mask is a full-table copy under the state lock, and paying
            # it every idle send-loop pass would contend with add() for
            # nothing (st.state_version() is two counter reads)
            ver = self.st.state_version()
            if ver != self._sub_mask_ver.get(link):
                wlo, wcnt = rng
                self.st.mask_link_residual(link, wlo * 32, (wlo + wcnt) * 32)
                self._sub_mask_ver[link] = ver
        # FRESH stamp candidate, captured BEFORE the drained-residual
        # determination below: an add() racing in after the begin_* call
        # found the residual empty must not be covered by the mark (its
        # mass is not in what we sent) — a stamp taken at send time would
        # falsely verify freshness over it. Any t at or before the
        # determination is safe: everything added before the determination
        # was either quantized+enqueued already (FIFO delivers it before
        # the FRESH) or left mass that made the determination non-empty.
        # The C tier gets the same guarantee by stamping under e->mu.
        fresh_t = self._now_ns()
        if self.st.host_tier:
            # serving links trade batch efficiency for pipeline LATENCY:
            # the subscriber's staleness floor is queue depth x per-message
            # apply time, so cap the burst well under the wire budget
            # (stengine.cpp kSubBurstCap — same bound on the C tier)
            out = self.st.begin_frame_burst(link, min(self._burst, 32))
            if out is None:
                return False
            seq, frames = out
        else:
            out = self.st.begin_frame(link)
            if out is None:
                return False
            seq, df = out
            f = self.st.finish_frame(df)
            frames = [f] if f is not None else []
        if not frames:
            self.st.ack_frame(link, seq)  # idle: no-op
            self._sub_fresh_mark(link, fresh_t)
            return False
        trace = None
        if self._trace_wire:
            trace = self._trace_stamp
            if trace is None:
                trace = (self.node.obs_id, self._now_ns(), 0)
        nmsg = len(frames) if rng else 1
        with self._ack_mu:
            base = self._tx_seq.get(link, 0)
            self._tx_seq[link] = base + nmsg
        ok = True
        if rng:
            wlo, wcnt = rng
            for i, f in enumerate(frames):
                payload = wire.encode_rdata(
                    f, wlo, wcnt, base + i + 1, trace=trace
                )
                if not self._send_blocking(link, payload, data=True):
                    ok = False
                    break
                self._sub_msgs_out += 1
        else:
            if len(frames) == 1:
                payload = wire.encode_frame(frames[0], base + 1, trace=trace)
            else:
                payload = wire.encode_burst(
                    frames, self.st.spec, base + 1, trace=trace
                )
            ok = self._send_blocking(link, payload, data=True)
            if ok:
                self._sub_msgs_out += 1
        if ok:
            self.st.ack_frame(link, seq)  # delivered-on-enqueue (unledgered)
        else:
            self.st.nack_frame(link)
        return ok

    def _sub_fresh_mark(self, link: int, fresh_t: int) -> None:
        """Send ONE FRESH drain mark, interval-throttled — the shared tail
        of both freshness paths (the running sender's idle branch and the
        paused-quiesce beat), so the mark's contract (carries the link's
        last tx_seq, lossy zero-timeout send, bookkeeping) lives in one
        place. ``fresh_t`` must have been stamped BEFORE the caller's
        drained-residual determination (the _send_sub ordering note)."""
        now = time.monotonic()
        if now - self._sub_fresh.get(link, 0.0) < (
            self.config.serve.fresh_interval_sec
        ):
            return
        with self._ack_mu:
            last_seq = self._tx_seq.get(link, 0)
        try:
            if self.node.send(
                link, wire.encode_fresh(fresh_t, last_seq), timeout=0.0
            ):
                self._sub_fresh[link] = now
                self._sub_fresh_out += 1
        except BrokenPipeError:
            pass  # LINK_DOWN will clean the link up

    def _sub_fresh_beat(self, link: int) -> None:
        """FRESH beat for a PAUSED sender (r12 quiesce): only a fully
        drained residual may be marked fresh — a paused link still owing
        mass gets no mark, so a subscriber read across the cut refuses
        (StalenessError) instead of falsely verifying. Stamp captured
        BEFORE the drained determination, same discipline as _send_sub."""
        fresh_t = self._now_ns()
        if self.st.residual_rms(link) > 0.0:
            return
        self._sub_fresh_mark(link, fresh_t)

    def _register_data(self, link: int, ledger_seq: int, encode_into):
        """Allocate the link's next wire seq, encode the outgoing DATA/BURST
        message with it INTO a pooled slot (r07/r09: ``encode_into(buf,
        seq, trace)`` writes the wire bytes — v2-framed when ``trace`` is
        set — in place and returns the length), and append
        (ledger_seq, wire_seq, payload, slot) to the unacked retransmission
        ledger — the slot's filled prefix IS the payload, kept verbatim so
        a delivery timeout can resend it byte-identical (go-back-N; wire.py
        tx_seq docstring), and it returns to the pool when the entry pops.
        The encode itself (multi-MB numpy serialization for big bursts)
        runs OUTSIDE _ack_mu so it never stalls the recv thread's ACK pops;
        this thread is the link's only seq allocator and appender, and the
        peer cannot ACK a seq before the send that follows the append, so
        the two lock windows cannot misorder the ledger.

        Slot reuse is single-writer-safe: only this (send) thread acquires
        slots, so a slot released by the recv thread's ACK pop cannot be
        overwritten while any in-flight payload view of it is still being
        sent — the next acquire happens on this thread, after that send."""
        obs = self._obs
        with self._ack_mu:
            txs = self._tx_seq.get(link, 0) + 1
            self._tx_seq[link] = txs
        # r09 trace context: the pending stamp (latest local add or traced
        # apply); a peer that has neither yet stamps itself at hop 0
        trace = None
        if self._trace_wire:
            trace = self._trace_stamp
            if trace is None:
                trace = (self.node.obs_id, self._now_ns(), 0)
        slot = self._tx_pool.acquire()
        t0 = time.monotonic()
        n = encode_into(slot, txs, trace)
        if obs is not None:
            obs.encode.observe(time.monotonic() - t0)
        payload = slot[:n]
        with self._ack_mu:
            if link not in self._tx_seq:
                # LINK_DOWN raced between the two lock windows and purged
                # this link's ledger state; appending now would recreate
                # the dict entry for a dead link (ids are never reused)
                # and pin the payload until close(). The slot goes back to
                # the pool at once — safe to send the view first, because
                # only this thread can re-acquire it (docstring above).
                self._tx_pool.release(slot)
                return payload
            q = self._unacked.setdefault(link, [])
            now = time.monotonic()
            if not q:
                self._ack_progress[link] = now
            # 5th field: ledger-append time, consumed by the ACK-pop RTT
            # histogram (st_ack_rtt_seconds; includes retransmission
            # rounds by construction — same definition as the engine tier)
            q.append((ledger_seq, txs, payload, slot, now))
        return payload

    def _window_full(self, link: int) -> bool:
        with self._ack_mu:
            return len(self._unacked.get(link, ())) >= SEND_WINDOW

    def _check_retransmit(self, links) -> None:
        """Go-back-N delivery timer (TransportConfig.ack_timeout_sec): when
        a link's oldest unacked message has waited past the timeout, resend
        the HEAD of the unacked tail byte-identical (RETX_PREFIX messages —
        same wire seqs, so the receiver's dedup makes a spurious retransmit
        harmless, and in-order acceptance means only the head can restore
        progress anyway). After ack_retry_limit rounds with zero ACK
        progress the link is a black hole (accepts writes, acknowledges
        nothing): tear it down so LINK_DOWN -> rollback -> carry ->
        re-graft recovers every undelivered frame on a fresh link instead
        of retrying forever."""
        tcfg = self.config.transport
        # Sweep ledger state whose link is gone (runs even with the timer
        # disabled): _register_data's first lock window can recreate
        # _tx_seq for a link whose LINK_DOWN purge already ran, pinning the
        # payload forever — link ids are never reused, so anything not in
        # the live set is garbage. Only this thread appends, so a link
        # attached after `links` was snapshotted cannot have entries yet.
        purged = []
        with self._ack_mu:
            live = set(links)
            for stale in [l for l in self._unacked if l not in live]:
                purged.extend(self._unacked.pop(stale, ()))
                self._tx_seq.pop(stale, None)
                self._acked.pop(stale, None)
                self._ack_progress.pop(stale, None)
                self._retx_rounds.pop(stale, None)
        self._release_slots(purged)
        if tcfg.ack_timeout_sec <= 0 or tcfg.wire_compat:
            return
        now = time.monotonic()
        for link in links:
            with self._ack_mu:
                q = self._unacked.get(link)
                # per-round exponential backoff (capped 8x): the timer
                # measures time since ledger append, so on a
                # bandwidth-capped link a big burst can legitimately wait
                # out several timeouts while still queued locally — a flat
                # timer would retransmit (and eventually tear down) a
                # healthy saturated link; backoff keeps spurious rounds
                # from compounding while a true black hole still hits the
                # retry limit in bounded time
                wait = tcfg.ack_timeout_sec * min(
                    1 << self._retx_rounds.get(link, 0), 8
                )
                if not q or now - self._ack_progress.get(link, now) < wait:
                    continue
                rounds = self._retx_rounds.get(link, 0) + 1
                self._retx_rounds[link] = rounds
                self._ack_progress[link] = now
                # payload views over ledger-held slots: safe to send after
                # the lock drops even if an ACK pops them mid-send — a
                # released slot can only be REUSED by this same (send)
                # thread, after these sends (see _register_data)
                tail = [e[2] for e in q[:RETX_PREFIX]]
            if rounds > max(1, tcfg.ack_retry_limit):
                log.warning(
                    "link %d: no ACK progress after %d retransmission "
                    "rounds — tearing down for re-graft",
                    link, rounds - 1,
                )
                if self._obs is not None:
                    # the black-hole verdict is exactly what a postmortem
                    # should explain: dump the merged timeline around it
                    self._obs.event(
                        "blackhole_teardown", self.node.obs_id, link,
                        rounds - 1,
                    )
                    self._obs.hub.dump("goback_teardown")
                self.node.drop_link(link)
                continue
            log.info(
                "link %d: retransmitting %d unacked message(s), round %d",
                link, len(tail), rounds,
            )
            if self._obs is not None:
                self._obs.retransmits.inc(len(tail))
                self._obs.event(
                    "retransmit", self.node.obs_id, link, len(tail)
                )
            for payload in tail:
                if not self._send_blocking(link, payload, data=True):
                    break

    def _release_slots(self, entries) -> None:
        """Return popped ledger entries' pool slots (r07 slot lifecycle:
        acked/purged -> free). Entries are (ledger_seq, wire_seq, payload,
        slot, sent_at) tuples; legacy bytes payloads carry slot=None."""
        if self._tx_pool is None:
            return
        for entry in entries:
            slot = entry[3]
            if slot is not None:
                self._tx_pool.release(slot)

    def _fault_point(self, name: str) -> None:
        """Named protocol point for the fault plan's kill schedule."""
        if self._faults is not None:
            self._faults.point(name)

    def _send_blocking(
        self, link: int, payload: bytes, data: bool = False
    ) -> bool:
        """Deliver one frame, riding out backpressure. On a dead link the
        frame is dropped — its content is still in our replica, and the
        re-graft handshake re-derives exactly the missing delta.

        ``data=True`` marks DATA/BURST payloads: the fault plan (when one
        is installed) may drop, delay, duplicate, truncate, bit-corrupt,
        stall or sever them here — the Python tier's wire boundary.
        Handshake and ACK traffic never goes through the chaos."""
        # ONE load of the plan: the chaos soak detaches it mid-run
        # (p._faults = None) from another thread, and a re-load between
        # the None-check and the call would AttributeError — killing this
        # daemon send thread silently, the exact wedge class r06 hardened
        # the recv thread against
        plan = self._faults
        if plan is not None and data:
            payloads, delay, sever = plan.on_send(link, payload)
            if delay > 0:
                time.sleep(delay)
            ok = True
            for p in payloads:
                ok = self._send_raw(link, p)
                if not ok:
                    break
            if sever:
                self.node.drop_link(link)
                return False
            # a dropped/stalled frame reports success: the sender must
            # believe it delivered (that is the fault) — its ledger entry
            # stays unacked, which is exactly what rollback recovers
            return ok
        return self._send_raw(link, payload)

    def _send_raw(self, link: int, payload: bytes) -> bool:
        quarantine = self.config.transport.quarantine_send_failures
        fails = 0
        while not self._stop.is_set():
            try:
                if self.node.send(link, payload, timeout=0.1):
                    return True
            except BrokenPipeError:
                return False
            fails += 1
            if quarantine > 0 and fails >= quarantine:
                # Per-link quarantine: ~quarantine/10 seconds of a full
                # send queue with zero drained bytes means the peer has
                # stopped consuming but kept its socket open. Retrying hot
                # pins this thread (and the frames) on a dead-in-practice
                # link until peer_timeout_sec; tearing it down routes
                # through LINK_DOWN -> rollback -> carry -> re-graft, the
                # path that loses nothing.
                log.warning(
                    "quarantining link %d after %d consecutive send "
                    "failures (~%.0fs stalled): tearing down for re-graft",
                    link, fails, fails * 0.1,
                )
                if self._obs is not None:
                    self._obs.event(
                        "quarantine", self.node.obs_id, link, fails
                    )
                self.node.drop_link(link)
                return False
        return False

    # -- receive side ---------------------------------------------------------

    def _recv_loop(self) -> None:
        """Guard shell around the real loop: an UNHANDLED exception here
        used to kill the daemon thread silently and wedge the peer (the
        r05/r06 failure class). Now it dumps a flight-recorder postmortem
        (merged native+Python timeline + registry snapshots) and restarts
        the loop — bounded retries so a hot crash loop still surfaces."""
        failures = 0
        while not self._stop.is_set():
            try:
                self._recv_loop_inner()
                return  # clean exit: stop was set
            except Exception:
                failures += 1
                log.exception(
                    "recv thread hit an unhandled exception (restart %d/3)",
                    failures,
                )
                if self._obs is not None:
                    self._obs.hub.poll_native()
                    self._obs.hub.dump("recv_thread_exception")
                if failures >= 3:
                    raise
                time.sleep(0.1)

    def _recv_loop_inner(self) -> None:
        compat = self.config.transport.wire_compat
        while not self._stop.is_set():
            if self._obs is not None:
                # drain the native event ring into the flight recorder on
                # the peer's own thread (never a background thread racing
                # node teardown); rate-limited inside poll_native
                self._obs.hub.poll_native(self._obs.drain_interval)
            if (
                self._digest_interval > 0
                and self._obs is not None
                and _obs.obs_enabled()
            ):
                # r09 in-band aggregation: piggyback this subtree's merged
                # metrics digest up the tree (or, at the root, publish the
                # whole-tree view) once per interval — control-plane
                # traffic on the peer's own housekeeping thread. Gated on
                # obs like everything else: ST_OBS=0 / ObsConfig.enabled
                # =False means NO periodic snapshot/JSON/wire work (the
                # explicit metrics(cluster=True) call still serves), and
                # the RUNTIME flag (obs.set_enabled) pauses the beat too —
                # that is what lets obs_overhead.py's health arm A/B the
                # full digest+health+clock housekeeping cost.
                now = time.monotonic()
                if now - self._digest_last >= self._digest_interval and (
                    self._uplink is not None
                    or self.config.obs.cluster_json_path
                    or self._health is not None
                ):
                    # a root with no JSON/health sink has nobody to
                    # publish TO — its cluster view is built on demand
                    # (metrics(cluster=True)); don't pay the snapshot per
                    # beat just to discard it
                    self._digest_last = now
                    try:
                        self._publish_digest()
                    except Exception as e:
                        log.debug("digest publish failed: %s", e)
                # r18 clock plane beat rides the same housekeeping pass
                self._clock_beat(now)
            busy = self._handle_events()
            try:
                # r12 lifecycle: drive any active barrier / operator
                # command channel. Must never kill the recv loop — a
                # failed lifecycle op resolves through its own error path.
                self._lc_tick()
            except Exception:
                log.exception("lifecycle tick failed (recv thread continues)")
            if (
                compat
                and self._engine is not None
                and not self._ready.is_set()
                and self._uplink is not None
            ):
                # Engine-mode compat readiness: the engine consumes the
                # uplink's frames, so _decode_compat (the python tier's
                # readiness hook) never runs. The transport's per-link
                # frames_in counts EVERY received frame including zero-scale
                # keepalives — the same "parent's stream is flowing, even
                # idle" bar (quirk Q4's fix) the python tier uses.
                s = self.node.stats(self._uplink)
                if s is not None and s.frames_in > 0:
                    self._ready.set()
            if self._engine is not None:
                # control-plane messages the engine deferred (it owns only
                # DATA/BURST/ACK on attached links)
                while True:
                    c = self._engine.poll_ctrl()
                    if c is None:
                        break
                    busy = True
                    try:
                        self._on_message(c[0], c[1])
                    except Exception as e:
                        log.warning("dropping bad ctrl message on link %d: %s", c[0], e)
            for link in list(self.node.links):
                if link in self._engine_links:
                    continue  # the engine's receiver thread consumes these
                # Consecutive DATA/BURST frames batch into ONE device apply
                # (core.receive_frames): without this, per-frame dispatch on
                # a busy device falls behind a fast sender and the RX queue
                # backs up by hundreds of frames. Control messages flush the
                # batch first so relative order is preserved. ``msgs`` counts
                # wire MESSAGES (what the sender's ledger tracks and ACKs
                # acknowledge); a burst message carries many frames. Trace
                # notes are buffered and recorded AFTER the flush applies
                # (same accounting instant as the native receiver) —
                # telemetry must not claim a hop whose batch then failed.
                batch: list = []
                traced: list = []
                msgs = 0
                # host tier only: its applies are synchronous numpy/C work,
                # so recycling after the flush cannot race anything. A
                # device tier's jitted apply may consume the arrays
                # asynchronously (H2D transfer) — it keeps fresh copies.
                scratch = self._rx_scratch.get(link)
                if scratch is None and not compat and self.st.host_tier:
                    scratch = self._rx_scratch.setdefault(
                        link, wire.DecodeScratch(self.st.spec)
                    )
                for _ in range(256):  # bounded so other links aren't starved
                    try:
                        payload = self.node.recv(link, timeout=0.0)
                    except BrokenPipeError:
                        break
                    if payload is None:
                        break
                    busy = True
                    try:
                        if compat:
                            frame = self._decode_compat(link, payload)
                            if frame is not None:
                                batch.append(frame)
                            continue
                        if payload[0] in (wire.DATA, wire.BURST):
                            if self._sealed:
                                # leaving: discard unACKed — the sender's
                                # ledger re-delivers after our departure
                                continue
                            # Go-back-N acceptance (wire.py tx_seq): only
                            # the next in-order, decodable message is
                            # applied and counted. A duplicate (seq <= rx:
                            # injected, or a retransmit racing our ACK) and
                            # anything after a gap (seq > rx+1: a message
                            # vanished at the wire) are discarded unapplied
                            # — the sender retransmits the hole
                            # byte-identical, so nothing is lost, nothing
                            # applies twice, and the cumulative ACK is
                            # always exactly the last accepted seq. An
                            # undecodable message (truncated/garbled) is
                            # likewise discarded WITHOUT consuming its seq;
                            # its retransmission arrives whole.
                            # expected seq masked to u32: the wire field
                            # wraps at 2^32 while rx_count counts on
                            # (matching the native engine's compare)
                            seq = wire.data_seq(payload, self.st.spec)
                            want = (
                                self._rx_count.get(link, 0) + msgs + 1
                            ) & 0xFFFFFFFF
                            if seq != want:
                                log.debug(
                                    "link %d: discarding out-of-order "
                                    "data message (seq %d, expected %d)",
                                    link, seq, want,
                                )
                                if self._obs is not None:
                                    # dedup instrument is None on engine
                                    # peers; this path is still reachable
                                    # there pre-attach (handshake-window
                                    # DATA), so guard it
                                    if self._obs.dedup is not None:
                                        self._obs.dedup.inc()
                                    self._obs.event(
                                        "dedup_discard", self.node.obs_id,
                                        link, seq,
                                    )
                                continue
                            if payload[0] == wire.DATA:
                                batch.append(
                                    wire.decode_frame(
                                        payload, self.st.spec, scratch
                                    )
                                )
                            else:
                                batch.extend(
                                    wire.decode_burst(
                                        payload, self.st.spec, scratch
                                    )
                                )
                            msgs += 1
                            traced.append(payload)
                            continue
                    except Exception as e:  # a bad frame must not kill the node
                        log.warning("dropping bad frame on link %d: %s", link, e)
                        continue
                    # control message: flush queued frames first (order), and
                    # never let a flush failure swallow the control message —
                    # a dropped WELCOME/DONE would hang the join handshake
                    self._flush_frames(link, batch, msgs, scratch)
                    for p in traced:
                        self._note_trace(link, p)
                    batch, traced, msgs = [], [], 0
                    try:
                        self._on_message(link, payload)
                    except Exception as e:
                        log.warning("dropping bad message on link %d: %s", link, e)
                    if link in self._engine_links:
                        # the handshake just attached this link to the native
                        # engine: stop consuming NOW — the next message is
                        # the engine's (and its rx accounting took over at
                        # the attach-time count)
                        break
                self._flush_frames(link, batch, msgs, scratch)
                for p in traced:
                    self._note_trace(link, p)
                self._flush_acks(link)  # retry any backpressure-dropped ACK
            if not busy:
                time.sleep(0.002)

    def _flush_frames(
        self,
        link: int,
        batch: list,
        msgs: int | None = None,
        scratch: Optional[wire.DecodeScratch] = None,
    ) -> None:
        n_ack = len(batch) if msgs is None else msgs
        if batch:
            t0 = time.monotonic()
            try:
                self.st.receive_frames(link, batch)
            except Exception:
                # Fall back to per-frame apply so one bad frame costs only
                # itself, not up to 255 good ones (received deltas are never
                # resent — the sender's error feedback already cleared them,
                # so a discarded good frame would silently diverge the
                # replicas).
                for f in batch:
                    try:
                        self.st.receive_frame(link, f)
                    except Exception as e:
                        log.warning("dropping bad frame on link %d: %s", link, e)
            if self._obs is not None:
                self._obs.apply.observe(time.monotonic() - t0)
            if scratch is not None:
                # frames applied (receive_frames is synchronous on every
                # tier): their pooled decode arrays are reusable now
                scratch.recycle()
            self._wake.set()  # flood refills other links' residuals
        # crash point: mass applied + flooded, ACK not yet sent — the
        # two-generals window; the sender re-delivers (at-least-once)
        if n_ack:
            self._fault_point("between-apply-and-ack")
        # ACK counts ACCEPTED wire MESSAGES (one ledger entry each), not
        # frames: a burst message carries many frames but rolls back / acks
        # whole. With the tx_seq discipline (recv loop) the cumulative count
        # is exactly the last in-order seq applied — undecodable or
        # out-of-order messages were never counted and will be
        # retransmitted by their sender.
        if n_ack:
            self._ack_received(link, n_ack)

    def _now_ns(self) -> int:
        """Monotonic ns for cross-node-comparable stamps (trace
        generations, clock probes, digest times), plus the simulated skew
        when a test/bench configured one — so every stamp another node
        compares against behaves like a genuinely skewed host clock."""
        return time.monotonic_ns() + self._skew_ns

    def _health_event(self, name: str, arg: int, detail: str) -> None:
        """Analyzer event sink -> the flight recorder timeline."""
        obs = self._obs
        if obs is not None:
            obs.event(name, self.node.obs_id, 0, arg, detail=detail)

    def _clock_beat(self, now: float) -> None:
        """r18 clock plane beat (housekeeping thread): probe the uplink
        with a four-stamp offset sample every clock_sync_interval_sec.
        The root never probes — it IS the reference. Lossy like the
        digest beat: a bounced send just waits for the next interval."""
        if (
            self._clock_interval <= 0
            or self.is_master
            or now - self._clock_last < self._clock_interval
        ):
            return
        up = self._uplink
        if up is None:
            return
        self._clock_last = now
        try:
            self.node.send(
                up, wire.encode_clock(self._clock.probe_payload()), timeout=0.05
            )
        except BrokenPipeError:
            pass  # uplink died; re-graft re-targets the next probe

    def _note_trace(self, link: int, payload: bytes) -> None:
        """r09 trace bookkeeping for one ACCEPTED data message (python
        tier; the engine's receiver does the same in C): advance the
        pending stamp one hop, record the link's staleness/hop gauges, and
        put a trace_apply record on the timeline. Telemetry gates on obs
        exactly like the native twin (st_obs_is_enabled in stengine.cpp's
        receiver) — with obs off only the stamp advance remains, the part
        PROPAGATION needs."""
        obs = self._obs
        if obs is None and not self._trace_wire:
            return
        tr = wire.data_trace(payload, self.st.spec)
        if tr is None:
            return
        origin, gen, hops = tr
        hop = min(hops + 1, 255)
        if self._trace_wire:
            self._trace_stamp = (origin, gen, hop)
        if obs is None:
            return
        # r18: store the origin GENERATION stamp, not a frozen age — the
        # collector computes the live age at snapshot time, so a stalled
        # link's staleness GROWS (what the SLO burn-rate alert watches)
        # instead of freezing at its last-apply value. The origin node id
        # feeds the health analyzer's cross-host offset correction.
        self._staleness[link] = (gen, hop)
        self._stale_origin[link] = origin
        self._traced_in += 1
        if obs.hops is not None:
            obs.hops.observe(hop)
        obs.event(
            "trace_apply", self.node.obs_id, link, gen,
            extra=((origin << 8) | hop),
        )

    # -- r09 in-band cluster digest -----------------------------------------

    def _build_digest(self) -> dict:
        """This subtree's merged metrics digest: our own registry snapshot
        folded with each child link's latest digest (obs/aggregate.py owns
        the merge semantics; subtree disjointness makes counter sums
        exact). Bounded before it ever hits the wire."""
        from ..obs import aggregate

        doc = aggregate.from_snapshot(
            self.node.obs_id,
            self.metrics(canonical=True),
            self._now_ns(),
        )
        # r12: the lifecycle node name rides the per-node breakdown so the
        # operator surface (ctl drain/versions) can address nodes by name
        ent = doc["nodes"].get(str(int(self.node.obs_id)))
        if ent is not None:
            ent["name"] = self.node_name
        for child in list(self._child_digests.values()):
            aggregate.merge(doc, child)
        aggregate.bounded(doc)
        if self._obs is not None:
            self._obs.cluster_nodes.set(aggregate.cluster_nodes(doc))
        return doc

    def _publish_digest(self) -> dict:
        """One digest beat: send the subtree digest to the uplink, or —
        at the root — write the whole-tree view to
        ObsConfig.cluster_json_path for ``obs.top``. Lossy by design
        (backpressure skips a beat; the next one carries fresher
        totals)."""
        doc = self._build_digest()
        up = self._uplink
        if up is not None:
            try:
                # small blocking budget, NOT 0: a saturated data plane (the
                # normal state of a training run — the engine keeps the
                # 8-deep transport queue full) would bounce every
                # zero-timeout enqueue and the tree view would silently go
                # stale exactly when it matters; 50 ms is one queue-drain
                # on any healthy link, paid on the housekeeping thread. A
                # beat that still bounces is dropped — the next one
                # carries fresher totals anyway.
                if (
                    self.node.send(up, wire.encode_digest(doc), timeout=0.05)
                    and self._obs is not None
                ):
                    self._obs.digest_out.inc()
            except BrokenPipeError:
                pass  # uplink died; LINK_DOWN will re-route the next beat
        else:
            if self._health is not None:
                # r18: the root's health analyzer samples every digest
                # beat — time-series ingest, heat scoring, SLO burn rates,
                # health.json (the analyzer writes it itself)
                try:
                    self._health.beat(doc, self._now_ns())
                except Exception as e:
                    log.debug("health beat failed: %s", e)
            if self.config.obs.cluster_json_path:
                import json as _json
                import os as _os

                path = self.config.obs.cluster_json_path
                tmp = f"{path}.tmp.{_os.getpid()}"
                try:
                    with open(tmp, "w") as f:
                        _json.dump(doc, f)
                        f.write("\n")
                    _os.replace(tmp, path)  # atomic: never a torn read
                except OSError as e:
                    log.debug("cluster digest write failed: %s", e)
        return doc

    def push_digest(self) -> dict:
        """Force one digest beat NOW (the periodic timer keeps running).
        Tests and quiesce-time accounting use this to propagate exact
        totals bottom-up instead of waiting out the interval."""
        self._digest_last = time.monotonic()
        return self._publish_digest()

    def cluster_metrics(self) -> dict:
        """The live whole-tree view from this node's vantage: its own
        registry + every digest its subtree has reported. At the tree ROOT
        this is the cluster — ``metrics(cluster=True)`` is the documented
        spelling."""
        return self._build_digest()

    def cluster_prometheus_text(self) -> str:
        """Prometheus text exposition of the cluster digest (merged
        counters/histograms; per-node gauges labeled ``{node=}``)."""
        from ..obs import aggregate

        return aggregate.prometheus_text(self._build_digest())

    def _ack_received(self, link: int, n: int) -> None:
        """Tell the sender its frames arrived (drives its in-flight ledger;
        see _send_loop). Cumulative, and RETRIED: an ACK dropped to send-queue
        backpressure is only healed by a later one if more DATA arrives — the
        final ACK of a burst would otherwise be lost forever, leaving the
        sender's ledger undrained (drain() spinning, rollback re-delivering
        delivered frames on link death)."""
        if self.config.transport.wire_compat or n <= 0:
            return
        count = self._rx_count.get(link, 0) + n
        self._rx_count[link] = count
        self._flush_acks(link)

    def _flush_acks(self, link: int) -> None:
        count = self._rx_count.get(link, 0)
        if count <= self._ack_sent.get(link, 0):
            return
        try:
            if self.node.send(link, wire.encode_ack(count), timeout=0.0):
                self._ack_sent[link] = count
        except BrokenPipeError:
            self._ack_sent[link] = count  # link dead; nothing left to ack

    #: EventKind -> timeline event name (matches the native codes 1..4, so
    #: every native membership event pairs with a later "py"-tier twin —
    #: the handled-at timestamp the cross-tier ordering test leans on)
    _EVENT_NAMES = {
        EventKind.LINK_UP: "link_up",
        EventKind.LINK_DOWN: "link_down",
        EventKind.BECAME_MASTER: "became_master",
        EventKind.REJOIN_FAILED: "isolated",
    }

    def _handle_events(self) -> bool:
        evs = self.node.poll_events(timeout=0.0)
        for ev in evs:
            if self._obs is not None:
                self._obs.event(
                    self._EVENT_NAMES[ev.kind], self.node.obs_id,
                    ev.link_id, int(ev.is_uplink),
                )
            if ev.kind == EventKind.LINK_UP:
                try:
                    self._on_link_up(ev)
                except DuplicateLink:
                    # A duplicate link id (e.g. a LINK_UP replayed across a
                    # transport hiccup) must be a logged no-op: this runs on
                    # the daemon recv thread, and an escaped raise would
                    # silently kill it and wedge the peer — the link is
                    # already attached, which is the state the event asks
                    # for anyway.
                    log.warning(
                        "duplicate LINK_UP for link %d ignored", ev.link_id
                    )
                except Exception:
                    # Any OTHER attach-path error must surface loudly (it is
                    # NOT a replay and may mean the link never attached) —
                    # but never by killing the daemon recv thread: a dead
                    # recv loop wedges the whole peer, the exact
                    # exit(-1)-class failure this framework exists to
                    # delete. The link CANNOT be left up either: a
                    # half-attached link still ACKs every message by count
                    # while the apply path drops its frames (unknown link),
                    # so the sender would clear error feedback for mass
                    # that never landed — silent permanent divergence. Tear
                    # it down instead: LINK_DOWN -> rollback -> carry ->
                    # re-graft re-delivers everything on a fresh link.
                    log.exception(
                        "LINK_UP handling failed for link %d — tearing the "
                        "link down for re-graft (recv thread continues)",
                        ev.link_id,
                    )
                    try:
                        self.node.drop_link(ev.link_id)
                    except Exception:
                        log.exception(
                            "teardown of half-attached link %d failed",
                            ev.link_id,
                        )
            else:
                try:
                    self._on_membership_event(ev)
                except Exception:
                    # same thread-survival rule as LINK_UP above
                    log.exception(
                        "membership event %s for link %d failed "
                        "(recv thread continues)", ev.kind, ev.link_id
                    )
        return bool(evs)

    def _on_link_up(self, ev) -> None:
        if ev.is_uplink:
            self._uplink = ev.link_id
            # a re-grafted uplink supersedes any earlier isolation
            # verdict (REJOIN_FAILED is a status, not a sentence —
            # the native layer keeps retrying and may heal)
            self._error = None
            if self.config.transport.wire_compat:
                # reference protocol has no handshake: start
                # streaming at once — into the carried residual
                # when re-grafting (our undelivered mass), else
                # zero. A re-grafting leaf resets its replica NOW
                # to EXACTLY the carry (fresh-joiner semantics: a
                # true fresh joiner with pending adds holds them in
                # values AND residual; the parent's re-seed then
                # refills tree state additively on top). Resetting
                # to zero instead would desync this node by the
                # carry forever: the carry floods to every OTHER
                # peer, and split horizon never returns it here —
                # see the LINK_DOWN comment.
                if self._compat_reset_on_regraft:
                    self._compat_reset_on_regraft = False
                    if self._engine is not None:
                        self._engine.compat_regraft(ev.link_id)
                    else:
                        self.st.regraft_reset_to_carry(
                            CARRY_LINK, ev.link_id
                        )
                elif self._engine is not None:
                    # interior re-graft (or first join): residual =
                    # carry + anything added since the consume —
                    # attach-by-diff recomputes against live values,
                    # so the two-step consume/attach loses nothing
                    carry, snap = self._engine.take_carry_and_snapshot()
                    if carry is not None:
                        self._engine.new_link_diff(
                            ev.link_id, np.asarray(snap - carry, "<f4")
                        )
                    else:
                        self._engine.new_link(ev.link_id, seed=False)
                else:
                    carry, _ = self.st.take_link_and_snapshot(
                        CARRY_LINK
                    )
                    self.st.new_link(
                        ev.link_id, seed=False, residual=carry
                    )
                if self._engine is not None:
                    self._engine_links.add(ev.link_id)
            else:
                self._start_join(ev.link_id)
        else:
            if self.config.transport.wire_compat:
                # reference join: seed the child with the full replica
                # through the codec stream (src/sharedtensor.c:379-381)
                if self._engine is not None:
                    self._engine.new_link(ev.link_id, seed=True)
                    self._engine_links.add(ev.link_id)
                else:
                    self.st.new_link(ev.link_id, seed=True)
            else:
                # native: wait for the child's SYNC snapshot before
                # opening the codec link
                self._pending[ev.link_id] = bytearray()
    def _on_membership_event(self, ev) -> None:
        if ev.kind == EventKind.LINK_DOWN:
            # r12: a child dying mid-barrier must not hang the cut — its
            # subtree's shards are simply absent (recorded as an error;
            # the root's verdict then fails honestly instead of stalling)
            op = self._lc_op
            if op is not None and ev.link_id in op["waiting"]:
                op["waiting"].discard(ev.link_id)
                op["errors"].append(
                    f"{self.node_name}: child link {ev.link_id} died "
                    f"mid-barrier"
                )
            self._pending.pop(ev.link_id, None)
            self._engine_links.discard(ev.link_id)
            self._rx_scratch.pop(ev.link_id, None)
            self._staleness.pop(ev.link_id, None)
            self._stale_origin.pop(ev.link_id, None)
            self._child_digests.pop(ev.link_id, None)
            # a dead subscriber link carries NO residual forward: a
            # read-only leaf owes the tree nothing, and a re-joining
            # subscriber re-seeds from scratch anyway
            self._sub_links.pop(ev.link_id, None)
            self._sub_fresh.pop(ev.link_id, None)
            self._sub_mask_ver.pop(ev.link_id, None)
            self._pending_sub.pop(ev.link_id, None)
            with self._ack_mu:
                purged = self._unacked.pop(ev.link_id, ())
                self._tx_seq.pop(ev.link_id, None)
                self._acked.pop(ev.link_id, None)
                self._rx_count.pop(ev.link_id, None)
                self._ack_sent.pop(ev.link_id, None)
                self._ack_progress.pop(ev.link_id, None)
                self._retx_rounds.pop(ev.link_id, None)
            self._release_slots(purged)
            if ev.is_uplink:
                # Keep undelivered upward updates for the re-grafted
                # uplink — in a LIVE carry slot that continues to absorb
                # add()/flood mass while we are orphaned (see
                # CARRY_LINK). If the parent died mid-handshake the
                # codec link never existed; everything we owe the tree
                # is then replica - sent_snapshot, computed LAZILY at
                # re-join time so orphan-period adds are included.
                if self._engine is not None:
                    stashed = self._engine.stash_carry(ev.link_id)
                else:
                    # one lock: a concurrent add() must find either the
                    # dying link or the carry slot, never neither
                    stashed = self.st.stash_carry(ev.link_id, CARRY_LINK)
                if not stashed and self._sent_snapshot is not None:
                    self._mid_handshake_base = self._sent_snapshot
                self._sent_snapshot = None
                self._uplink = None
                if self.config.transport.wire_compat:
                    # The reference protocol cannot express a stateful
                    # re-graft: the new parent will re-seed us with its
                    # FULL replica (no diff handshake exists), so
                    # retained state would double. A LEAF therefore
                    # zeroes its replica — but only AT the re-graft
                    # (LINK_UP below), never here: rejoin may instead
                    # end in BECAME_MASTER, where our retained state IS
                    # the authoritative seed and zeroing it would serve
                    # an empty tree. With children the reset would
                    # double THEM (their state stays while our
                    # seed-refill floods down), so an interior node
                    # keeps state and accepts the documented
                    # double-count — still strictly better than the
                    # reference, which kills the whole tree (quirk Q8).
                    # (the carry pseudo-slot is not a real link)
                    real = [l for l in self.st.link_ids if l >= 0]
                    if not real:
                        self._compat_reset_on_regraft = True
                    else:
                        log.warning(
                            "wire-compat interior node lost its uplink:"
                            " re-seeded state may double (the reference"
                            " protocol has no diff handshake)"
                        )
            else:
                self.st.drop_link(ev.link_id)
        elif ev.kind == EventKind.BECAME_MASTER:
            # our parent died and rejoin found nobody: we claimed the
            # rendezvous and are the new root (native master failover);
            # whatever state we hold is now the authoritative seed —
            # including in wire-compat, where a pending re-graft reset
            # must be cancelled (zeroing the new root would serve an
            # empty tree). The carry is DROPPED: its mass is already in
            # our (now-authoritative) replica, a root never re-joins
            # upward, and a live-but-unconsumable carry would cost an
            # extra O(total) pass on every add/apply forever.
            if self._engine is not None:
                self._engine.drop_carry()
            else:
                self.st.take_link_and_snapshot(CARRY_LINK)
            self._mid_handshake_base = None
            self._compat_reset_on_regraft = False
            self._uplink = None
            self.is_master = True
            self._error = None
            self._ready.set()
        elif ev.kind == EventKind.REJOIN_FAILED:
            # Status, not a sentence: the native layer keeps cycling
            # join-then-claim-rendezvous forever; under detection skew a
            # sibling may claim the rendezvous seconds after this fires,
            # and the next LINK_UP/BECAME_MASTER clears the error.
            self._error = ConnectionError(
                "uplink lost and rejoin failed; node is isolated "
                "(still retrying in the background)"
            )
            self._ready.set()  # unblock wait_ready, which re-raises

    def _attach_diff(self, link: int, snap) -> None:
        """Open the codec link with residual = replica - snap. In engine mode
        the attach hands the link's data plane to the native engine, seeded
        with the cumulative message count Python acked during the handshake
        (so the ACK stream stays monotonic across the handoff)."""
        if self._engine is not None:
            self._engine.new_link_diff(
                link, np.asarray(snap, "<f4"), rx_init=self._rx_count.get(link, 0)
            )
            self._engine_links.add(link)
        else:
            self.st.new_link_diff(link, snap)
        self._arm_sign2(link)

    def _shm_ring_bytes(self) -> int:
        """Ring bytes per direction for this table: TWICE the max traced
        sign2 burst (the largest wire message the engine can emit), so
        the lane always pipelines >= 2 messages — floored at 1 MiB and
        capped by TransportConfig.shm_ring_bytes. Sizing to the table
        matters both ways on one memory system: a ring much smaller than
        a burst runs the lane in lockstep, while one much larger than
        needed cycles through DRAM instead of staying cache-resident
        (measured at 1 Mi: a 16 MiB ring beats a 64 MiB one by ~8%)."""
        want = 2 * (
            wire.HDR_V3
            + wire.burst_frames_cap(self.st.spec)
            * wire.frame_payload2_bytes(self.st.spec)
            + 64
        )
        # the user's cap is the OUTER bound (a memory-tight box setting
        # 128 KiB must get 128 KiB rings, not the floor): floor first,
        # cap last
        return min(
            self.config.transport.shm_ring_bytes, max(1 << 20, want)
        )

    def _arm_sign2(self, link: int) -> None:
        """r11: arm the adaptive-precision governor for this link iff BOTH
        ends advertised sign2 (ours is config/env-gated via self._sign2)."""
        if (
            self._engine is not None
            and self._sign2
            and self._peer_sign2.pop(link, False)
        ):
            self._engine.link_allow_sign2(link)
        # r14: an r14 peer decodes the aligned v3 framing — emission to it
        # may drop the repack copy from ITS receive path (same consume-at-
        # attach discipline as the sign2 flag above)
        if self._engine is not None and self._peer_r14.pop(link, False):
            self._engine.link_wire_v3(link)

    def _attach_sub(self, link: int, rng: Optional[tuple[int, int]]) -> None:
        """Attach — or RE-seed, the resync path — a read-only subscriber
        link (r10 serving tier). Order matters throughout:

        - a resync DETACHES first (discarding the old residual — the
          snapshot about to ship supersedes it) so the sender produces
          nothing in the window;
        - the wire seq restarts at 1 so the subscriber's post-seed gap
          detector has a deterministic base;
        - ``_sub_links`` is set BEFORE the codec link opens, so the send
          loop can never take the ledgered path for it (an unacked entry
          on a never-ACKing link would black-hole it);
        - WELCOME + snapshot CHUNKs + DONE + FRESH are enqueued BEFORE the
          attach (per-link FIFO ⇒ the subscriber finishes seeding before
          any codec DATA arrives — the same rationale as the writer join
          path).

        On the engine tier, attach and subscriber mode are ONE atomic
        native call (st_engine_attach_sub) for the same no-ledgered-window
        reason."""
        self._peer_sign2.pop(link, None)  # subscriber links stay 1-bit
        self._peer_shm.pop(link, None)  # ...and keep TCP (no shm offer)
        self._peer_r14.pop(link, None)  # ...and v2 framing
        resync = link in self._sub_links
        if resync:
            if self._engine is not None:
                self._engine.drop_link(link)
            else:
                self.st.drop_link(link)
        with self._ack_mu:
            purged = self._unacked.pop(link, ())
            self._tx_seq.pop(link, None)
            self._acked.pop(link, None)
            self._ack_progress.pop(link, None)
            self._retx_rounds.pop(link, None)
        self._release_slots(purged)
        wlo, wcnt = rng if rng is not None else (0, 0)
        self._sub_links[link] = rng
        self._sub_fresh[link] = 0.0
        # The seed rides the CONTROL plane: WELCOME, then our replica
        # snapshot (the subscribed pages only) as CHUNKs + DONE + a FRESH
        # mark stamped at snapshot time, and only THEN the codec link
        # opens (residual = whatever landed between snapshot and attach).
        # Rationale: subscriber links are unledgered, so a codec-stream
        # seed is only as reliable as every one of its messages — under
        # sustained loss a multi-message drain essentially never completes
        # gap-free, and the subscriber would resync forever (measured in
        # the r10 chaos arm). Control traffic is outside the chaos classes
        # by the r06 rule (chaos exercises recovery, never wedges a
        # handshake), so a re-seed completes DETERMINISTICALLY and the
        # codec stream carries only steady-state deltas.
        t_snap = self._now_ns()
        vals = np.asarray(self.st.snapshot_flat(), np.float32)
        self._send_blocking(link, bytes([wire.WELCOME]))
        sl = vals[wlo * 32 : (wlo + wcnt) * 32] if rng is not None else vals
        for chunk in wire.encode_snapshot_chunks(sl):
            self._send_blocking(link, chunk)
        # last_seq 0: the post-seed stream hasn't started (seqs restart at
        # 1 below), and the subscriber has applied exactly 0 of it
        self._send_blocking(link, wire.encode_fresh(t_snap, 0))
        if self._engine is not None:
            self._engine.new_link_sub(
                link,
                vals,
                rx_init=self._rx_count.get(link, 0),
                word_lo=wlo,
                word_cnt=wcnt,
                fresh_interval_sec=self.config.serve.fresh_interval_sec,
            )
            self._engine_links.add(link)
        else:
            # residual = values_now - vals: exactly the adds/floods that
            # raced the snapshot transfer (usually zero); _send_sub
            # range-masks it per pass
            self.st.new_link_diff(link, vals)
        if self._obs is not None:
            self._obs.event(
                "sub_resync" if resync else "sub_attach",
                self.node.obs_id, link, wcnt,
            )
        log.info(
            "link %d attached read-only%s%s", link,
            f" (words [{wlo}, {wlo + wcnt}))" if rng else " (full table)",
            " — resync re-seed" if resync else "",
        )

    def _attach_zero(self, link: int) -> None:
        if self._engine is not None:
            self._engine.new_link(
                link, seed=False, rx_init=self._rx_count.get(link, 0)
            )
            self._engine_links.add(link)
        else:
            self.st.new_link(link, seed=False)
        self._arm_sign2(link)

    # native-mode join handshake, child side
    def _start_join(self, uplink: int) -> None:
        # Consume the carry ATOMICALLY with the replica snapshot (one lock
        # in the state layer): an add() racing between the two would appear
        # in the snapshot but not the carry — presented to the parent as
        # tree-known state and erased tree-wide by its diff seed.
        if self._engine is not None:
            carry, snap = self._engine.take_carry_and_snapshot()
        else:
            carry, snap = self.st.take_link_and_snapshot(CARRY_LINK)
        if carry is None and self._mid_handshake_base is not None:
            # parent died before the handshake finished: everything we owe
            # is values - base, including orphan-period adds (lazy compute)
            carry = snap - self._mid_handshake_base
        self._mid_handshake_base = None
        if carry is not None:
            # exclude updates we still owe the tree, else the parent's diff
            # seed would subtract them from us while our carried residual
            # re-delivers them upward — a permanent divergence of exactly
            # the carried amount
            snap = snap - carry
            # the carry rides the NEW uplink: seeded at WELCOME as
            # values_now - sent_snapshot, which is exactly carry + whatever
            # lands during the handshake (the live slot keeps absorbing)
        self._sent_snapshot = snap
        from ..compat import SYNC_FLAG_SHM, SYNC_FLAG_SIGN2

        # r14: advertise the same-host shm lane (flag + our host identity
        # in the tolerant SYNC tail); a pre-r14 or cross-host parent just
        # ignores it and the link stays on TCP
        sflags = SYNC_FLAG_SIGN2 if self._sign2 else 0
        if self._shm_ok:
            sflags |= SYNC_FLAG_SHM
        self._send_blocking(
            uplink,
            wire.encode_sync(
                self.st.spec,
                self._wire_version,
                flags=sflags,
                shm_host=self._shm_host,
            ),
        )
        # crash point: SYNC sent, snapshot not — the parent holds a pending
        # handshake buffer for a child that just died mid-walk
        self._fault_point("mid-join-walk")
        for chunk in wire.encode_snapshot_chunks(np.asarray(snap, dtype="<f4")):
            if not self._send_blocking(uplink, chunk):
                return  # uplink died mid-handshake; LINK_DOWN re-derives carry
        # WELCOME (handled in _on_message) opens the codec link

    def _on_message(self, link: int, payload: bytes) -> None:
        kind = payload[0]
        if kind == wire.DATA:
            # same go-back-N acceptance as the recv-loop data path (this
            # branch serves stray DATA routed through the control plane);
            # expected seq masked to the wire field's u32 wrap
            if wire.data_seq(payload, self.st.spec) != (
                self._rx_count.get(link, 0) + 1
            ) & 0xFFFFFFFF:
                return  # dup/gap: discard unapplied, await retransmission
            self.st.receive_frame(link, wire.decode_frame(payload, self.st.spec))
            self._ack_received(link, 1)
            self._wake.set()  # flood refills other links' residuals
        elif kind == wire.ACK:
            # cumulative ACK = last in-order wire seq the peer accepted;
            # every unacked entry at or below it is delivered — its pool
            # slot returns to the ring (slot lifecycle: acked -> free)
            count = wire.decode_ack(payload)
            popped = []
            with self._ack_mu:
                self._acked[link] = count
                q = self._unacked.get(link, [])
                while q and q[0][1] <= count:
                    popped.append(q.pop(0))
                if popped:
                    # delivery progressed: reset the go-back-N timer
                    self._ack_progress[link] = time.monotonic()
                    self._retx_rounds.pop(link, None)
            self._release_slots(popped)
            if self._obs is not None and popped:
                now = time.monotonic()
                for entry in popped:
                    # entry[4] = ledger-append time (see _register_data)
                    self._obs.ack_rtt.observe(now - entry[4])
            for entry in popped:
                self.st.ack_frame(link, entry[0])
        elif kind == wire.SYNC:
            k, n, digest = wire.decode_sync(payload)
            ver = wire.sync_wire_version(payload)
            if ver != self._wire_version:
                # framing skew is fine (decoders accept both) but worth a
                # line: a tree stuck on v1 emission has no trace telemetry
                log.info(
                    "link %d joins with wire framing v%d (ours: v%d) — "
                    "interop ok; trace coverage follows the emitter",
                    link, ver, self._wire_version,
                )
            mine = self.st.spec
            if digest != mine.layout_digest():
                log.warning(
                    "rejecting link %d: table layout differs "
                    "(theirs: %d leaves / %d elems; ours: %d / %d)",
                    link, k, n, mine.num_leaves, mine.total_n,
                )
                self._send_blocking(
                    link,
                    wire.encode_reject(
                        f"table layout mismatch: yours ({k} leaves, {n} elems)"
                        f" is not byte-compatible with ours"
                        f" ({mine.num_leaves}, {mine.total_n})"
                    ),
                )
                self.node.drop_link_flushed(link)
                self._pending.pop(link, None)
                self._pending_sub.pop(link, None)
            else:
                from ..compat import (
                    SYNC_FLAG_READ_ONLY,
                    SYNC_FLAG_SHM,
                    SYNC_FLAG_SIGN2,
                )

                # r11: remember the joiner's sign2 decode capability for
                # the attach that follows DONE
                self._peer_sign2[link] = bool(
                    wire.sync_flags(payload) & SYNC_FLAG_SIGN2
                )
                # r14: same-host shm candidacy — the joiner advertised the
                # lane AND its host identity matches ours (consumed at
                # WELCOME time, when we serve the segment). The flag alone
                # (host match or not) marks the peer r14 — it decodes the
                # aligned v3 framing. Gated on OUR _shm_ok too: ST_SHM=0
                # must pin this node to pre-r14 behavior END TO END (v2
                # emission included — the documented A/B escape hatch).
                self._peer_r14[link] = bool(
                    self._shm_ok
                    and wire.sync_flags(payload) & SYNC_FLAG_SHM
                )
                self._peer_shm[link] = bool(
                    self._shm_ok
                    and wire.sync_shm_host(payload) == self._shm_host
                )
                if wire.sync_flags(payload) & SYNC_FLAG_READ_ONLY:
                    # r10 read-only subscriber handshake — possibly a
                    # RESYNC on a live link (seq gap repair): a RANGE
                    # message may follow before DONE
                    self._pending_sub[link] = None
                    log.info(
                        "link %d joins read-only (subscriber handshake)",
                        link,
                    )
                self._pending[link] = bytearray(self.st.spec.total * 4)
        elif kind == wire.RANGE:
            wlo, wcnt = wire.decode_range(payload)
            words = self.st.spec.total // 32
            if link not in self._pending_sub:
                log.warning(
                    "ignoring RANGE on link %d outside a subscriber "
                    "handshake", link,
                )
            elif not (0 <= wlo and 0 < wcnt and wlo + wcnt <= words):
                self._send_blocking(
                    link,
                    wire.encode_reject(
                        f"range [{wlo}, {wlo + wcnt}) outside the "
                        f"{words}-word table"
                    ),
                )
                self.node.drop_link_flushed(link)
                self._pending.pop(link, None)
                self._pending_sub.pop(link, None)
            else:
                self._pending_sub[link] = (wlo, wcnt)
        elif kind == wire.CHUNK:
            buf = self._pending.get(link)
            if buf is not None:
                wire.decode_chunk_into(payload, buf)
        elif kind == wire.DONE:
            buf = self._pending.pop(link, None)
            if buf is not None and link in self._pending_sub:
                # r10 subscriber attach / resync re-seed (the subscriber's
                # handshake carries no snapshot upload — the parent pushes
                # ITS snapshot down the control plane instead)
                self._attach_sub(link, self._pending_sub.pop(link))
                self._wake.set()
            elif buf is not None:
                # tier-native: numpy on the host tier (no backend init)
                snap = self.st._asarray(np.frombuffer(bytes(buf), "<f4"))
                # WELCOME is enqueued BEFORE the codec link opens: per-link
                # FIFO then guarantees the child sees WELCOME before any
                # DATA. In the reverse order the sender (native engine:
                # microseconds after attach) can put DATA on the wire first;
                # the child applies it pre-WELCOME AND counts it again in
                # its attach diff (residual = values_now - sent_snapshot) —
                # echoing the mass back upward, a permanent +M divergence.
                # An add() landing between the two calls is safe: it's in
                # `values` by attach time, so the diff seed carries it.
                # The WELCOME carries OUR capability flags (r11 trailing
                # byte — pre-r11 children dispatch on the kind byte alone
                # and ignore it) and, r14, the same-host shm segment
                # offer: the segment is SERVED (created + mapped, rx ring
                # armed) before the WELCOME ships, so the name the child
                # reads is guaranteed to exist when it joins. A failed
                # serve (no /dev/shm space, compat mode) degrades to a
                # plain WELCOME — the link keeps TCP.
                from ..compat import SYNC_FLAG_SHM, SYNC_FLAG_SIGN2

                wflags = SYNC_FLAG_SIGN2 if self._sign2 else 0
                shm_offer = None
                # the flag marks US as r14 (the child may then emit the
                # aligned v3 framing toward us) even when no segment
                # offer follows (cross-host r14 tree, serve failure)
                if self._shm_ok:
                    wflags |= SYNC_FLAG_SHM
                if self._peer_shm.pop(link, False):
                    served = self.node.shm_serve(
                        link, self._shm_ring_bytes()
                    )
                    if served is not None:
                        shm_offer = (self._shm_host, served[1], served[0])
                self._send_blocking(
                    link, wire.encode_welcome(wflags, shm_offer)
                )
                self._attach_diff(link, snap)
                self._wake.set()
        elif kind == wire.WELCOME:
            from ..compat import SYNC_FLAG_SIGN2

            # r11: the parent's capability flags ride the WELCOME tail (a
            # pre-r11 parent's bare WELCOME reads back as 0 — the uplink
            # then stays 1-bit)
            self._peer_sign2[link] = bool(
                wire.welcome_flags(payload) & SYNC_FLAG_SIGN2
            )
            # r14: the parent's flag marks it r14 (v3-framing decoder);
            # gated on OUR _shm_ok so ST_SHM=0 pins v2 emission too (the
            # documented pre-r14 escape hatch is end-to-end)
            from ..compat import SYNC_FLAG_SHM

            self._peer_r14[link] = bool(
                self._shm_ok
                and wire.welcome_flags(payload) & SYNC_FLAG_SHM
            )
            # ...and a same-host parent offered its shm segment — join it
            # (map + token-validate); ANY failure keeps the uplink on TCP
            # with a shm_fallback timeline event recording why
            offer = wire.welcome_shm(payload)
            if offer is not None and self._shm_ok:
                o_host, o_token, o_name = offer
                if o_host == self._shm_host:
                    if not self.node.shm_join(link, o_name, o_token):
                        log.info(
                            "shm attach on uplink %d failed — keeping TCP "
                            "(see the shm_fallback timeline event)", link,
                        )
            snap = self._sent_snapshot
            self._sent_snapshot = None
            if snap is not None:
                # everything we hold that the snapshot didn't claim — the
                # carried residual plus adds/floods during the handshake —
                # is owed upward
                self._attach_diff(link, snap)
            else:  # duplicate WELCOME; be tolerant
                self._attach_zero(link)
            self._ready.set()
            self._wake.set()
        elif kind == wire.DIGEST:
            # r09 in-band aggregation: a subtree's bounded metrics digest.
            # Latest-wins per link; merged lazily at the next build. Engine
            # links route here too (the C receiver defers every non-data
            # kind to poll_ctrl).
            self._child_digests[link] = wire.decode_digest(payload)
            if self._obs is not None:
                self._obs.digest_in.inc()
        elif kind == wire.CLOCK:
            # r18 clock plane: a child's four-stamp offset probe (answer
            # synchronously down the SAME link — the turnaround time is
            # inside the child's measured RTT either way), or our own
            # uplink's reply (fold into the estimator). Chaos-exempt
            # control traffic, the r06 rule.
            doc = wire.decode_clock(payload)
            if doc.get("op") == "probe":
                try:
                    self.node.send(
                        link,
                        wire.encode_clock(self._clock.reply_payload(doc)),
                        timeout=0.05,
                    )
                except BrokenPipeError:
                    pass  # prober died; nothing to answer
            elif doc.get("op") == "reply" and link == self._uplink:
                self._clock.on_reply(doc)
        elif kind == wire.SNAP:
            # r12 lifecycle barrier marker from our parent: per-link FIFO
            # means every pre-pause data message on this link was applied
            # before this handler runs — the consistent-cut property
            self._lc_begin(wire.decode_lifecycle(payload), link)
        elif kind == wire.SNAP_ACK:
            doc = wire.decode_lifecycle(payload)
            op = self._lc_op
            if op is None or str(doc.get("id")) != op["id"]:
                log.warning(
                    "stray SNAP_ACK on link %d (id %s)", link, doc.get("id")
                )
                return
            op["waiting"].discard(link)
            op["entries"].extend(doc.get("nodes", []))
            op["errors"].extend(doc.get("errors", []))
            self._snap_acks += max(1, len(doc.get("nodes", [])))
        elif kind == wire.RESUME:
            doc = wire.decode_lifecycle(payload)
            op = self._lc_op
            if op is not None and str(doc.get("id")) != op["id"]:
                # a RESUME for a barrier we never joined (we NACKed its
                # SNAP, so our subtree never saw it either): releasing on
                # it would unpause this node mid-cut of the barrier we ARE
                # in. Our own barrier's RESUME — or the pause deadline —
                # releases us.
                log.warning(
                    "ignoring RESUME for foreign barrier %s (active: %s)",
                    doc.get("id"), op["id"],
                )
                return
            # release the subtree FIRST: children must never stay paused
            # because of our own state
            for child in self._lc_children(exclude=link):
                self._send_blocking(child, payload)
            self._lc_op = None
            self._set_paused(False)
        elif kind == wire.CTL:
            self._handle_ctl_msg(wire.decode_lifecycle(payload), link)
        elif kind == wire.REJECT:
            self._error = SpecMismatch(wire.decode_reject(payload))
            self._ready.set()  # unblock wait_ready, which re-raises
        else:
            raise ValueError(f"unknown message kind {kind}")

    def _decode_compat(self, link: int, payload: bytes):
        """Decode one reference-wire frame; returns a TableFrame to batch, or
        None for idle keepalives (which still count for readiness)."""
        frame = wire.decode_compat_frame(payload, self.st.spec)
        if link == self._uplink and not self._ready.is_set():
            # Readiness = the parent's stream is flowing. Counting zero-scale
            # keepalives too fixes the reference's all-zero-tensor hang
            # (quirk Q4): an idle parent still proves liveness within 1s.
            self._ready.set()
        return frame  # None = reference idle keepalive (quirk Q2)


def create_or_fetch(
    host: str,
    port: int,
    template: Any,
    config: Config | None = None,
    timeout: float = 30.0,
) -> SharedTensorPeer:
    """The reference's entry point (``sharedtensor.createOrFetch``,
    src/sharedtensor.c:347): create the shared tensor at ``host:port`` if
    nobody owns it yet (becoming master, seeded from ``template``), else join
    the existing tree (``template`` supplies only the table layout).

    Blocks until the node is ready — master immediately, joiner after the
    state-transfer handshake.
    """
    peer = SharedTensorPeer(host, port, template, config)
    try:
        peer.wait_ready(timeout)
    except BaseException:
        peer.close()
        raise
    return peer
