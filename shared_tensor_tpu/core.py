"""SharedTensor: the process-local replica + per-link codec state.

This is the TPU-native equivalent of the reference's ``SharedTensor`` struct
(reference src/sharedtensor.c:30-39: full replica ``values[]`` plus one
residual buffer per tree link) and its update semantics (``addFromInternal``
:334-344; flood-on-receive :124-127). Differences by design:

- State is a pytree ("table") of tensors with per-leaf codec scales, not one
  flat buffer — the reference README's "table sync" TODO (README.md:41) is
  first-class here.
- Links are dynamic: the reference hard-codes exactly 3 (up/left/right) and
  pre-accumulates updates into *unconnected* slots so a late joiner can be
  seeded (SURVEY.md §5.4). Here a new link's residual is explicitly seeded
  with the current replica — the same state-transfer-through-the-codec
  mechanism, made explicit — so any number of links works and a dropped peer
  can re-graft anywhere (fixes reference quirk Q8 / README.md:33).
- All array updates are functional JAX ops guarded by one mutex; the
  reference's unsynchronized concurrent ``float +=`` races (quirk Q7, lost
  updates) are gone by construction while the *approximate* semantics stay in
  the codec.

The object is deliberately transport-agnostic: the peer engine (comm/) calls
``make_frame``/``receive_frame``; tests drive it in-process.
"""

from __future__ import annotations

import glob
import importlib.util
import os
import pkgutil
import threading
from typing import Any, Optional

import jax
import jax.numpy as jnp

from .config import CodecConfig
import numpy as np

from .ops.table import (
    TableFrame,
    TableSpec,
    accumulate_table,
    apply_table_batch,
    apply_table_many,
    flatten,
    make_spec,
    quantize_table,
    quantize_table_burst,
    unflatten,
)


def _accelerator_plausible() -> bool:
    """Cheap no-backend-init probe: could this process see an accelerator?
    Checks device nodes (TPU /dev/accel*, /dev/vfio; GPU /dev/nvidia*) and
    installed jax plugin packages. False means the host is CPU-only and the
    host tier can activate WITHOUT initializing the XLA CPU client (whose
    thread pool contends with the C codec loops — measured 2.7x, see
    SharedTensor.__init__)."""
    for pat in (
        "/dev/accel*",      # TPU
        "/dev/nvidia*",     # NVIDIA GPU
        "/dev/kfd",         # AMD ROCm compute
        "/dev/dri/renderD*",  # GPU render nodes (ROCm without kfd exposure)
        "/dev/vfio/*",      # passthrough devices
    ):
        if glob.glob(pat):
            return True
    try:
        spec = importlib.util.find_spec("jax_plugins")
        if spec is not None and spec.submodule_search_locations:
            if any(pkgutil.iter_modules(list(spec.submodule_search_locations))):
                return True
        # PJRT plugins may register ONLY via the entry-point group (no
        # jax_plugins namespace package, no matching /dev node — e.g.
        # jax-metal): missing them would silently demote an accelerator
        # host to the numpy tier.
        import importlib.metadata as _md

        if any(True for _ in _md.entry_points(group="jax_plugins")):
            return True
    except Exception:
        return True  # can't tell: be conservative, ask the real backend
    return False


def host_tier_active() -> bool:
    """Will a SharedTensor built now run the host (numpy/C) codec tier?
    The same decision SharedTensor.__init__ makes, callable without
    constructing one. Initializes no jax backend when JAX_PLATFORMS is set
    or the host is detectably CPU-only (_accelerator_plausible); only a
    host with accelerator hardware/plugins present falls through to
    jax.default_backend() — and such a host is about to initialize that
    backend for the device tier anyway."""
    mode = os.environ.get("ST_HOST_CODEC", "auto")
    if mode != "auto":
        return mode == "numpy"
    plat = jax.config.jax_platforms
    if plat:
        return str(plat).split(",")[0] == "cpu"
    if not _accelerator_plausible():
        return True
    return jax.default_backend() == "cpu"


class DuplicateLink(ValueError):
    """A link id that is already attached. Its own type so the recv-thread
    event loop can treat a replayed LINK_UP as a logged no-op WITHOUT
    swallowing unrelated ValueErrors from the attach path (a masked real
    error there silently desyncs the peer — ADVICE r04 item 2 follow-up)."""


class SnapshotPublisher:
    """Lock-free double-buffered snapshot publication (r10 serving tier).

    The snapshot paths the reference's ``copyToTensor`` maps to all copy
    under the data-plane lock — ``EngineTensor.read()`` holds the engine
    mutex for a full-table memcpy, so a serving loop polling it would
    stall the quantize/apply threads that share that mutex exactly when
    traffic is heaviest. The serve tier reads from THIS instead: the
    writer side (the subscriber's apply thread) builds a fresh snapshot
    and :meth:`publish`\\ es it as one reference swap; readers
    :meth:`acquire` the current (array, meta) tuple with zero locks — a
    single attribute read, atomic under the GIL — so a read can never
    block an apply (or an ``add()`` upstream) by more than the one
    buffer swap the writer itself performs.

    The published array is owned by the publisher's consumers: the writer
    must hand over a COPY (or an array it will no longer mutate) — that
    copy is the "double buffer"."""

    __slots__ = ("_cur",)

    def __init__(self):
        self._cur: tuple = (None, 0, 0)  # (array, freshness_ns, version)

    def publish(self, array, freshness_ns: int, version: int) -> None:
        self._cur = (array, int(freshness_ns), int(version))

    def touch(self, freshness_ns: int) -> None:
        """Refresh the freshness mark WITHOUT a new array (idle FRESH
        beats: the state didn't change, only its verified age did)."""
        arr, old, ver = self._cur
        if freshness_ns > old:
            self._cur = (arr, int(freshness_ns), ver)

    def acquire(self) -> tuple:
        """(array, freshness_ns, version) — the latest published snapshot,
        read lock-free. array is None until the first publish."""
        return self._cur


class SharedTensor:
    """Replica + per-link residuals for one shared table of tensors.

    Reference API mapping (src/sharedtensor.c:455-465):
      ``copyToTensor`` -> :meth:`read` (snapshot), ``addFromTensor`` ->
      :meth:`add`, link fan-out -> :meth:`new_link`/:meth:`receive_frame`.
    """

    def __init__(
        self,
        template: Any,
        codec: CodecConfig | None = None,
        seed_values: bool = False,
    ):
        self.spec: TableSpec = make_spec(template)
        self.codec = codec or CodecConfig()
        self._lock = threading.Lock()
        # Host-codec tier selection: on an accelerator backend the codec runs
        # as device (Pallas/XLA) ops; on a CPU backend the numpy tier
        # (ops/codec_np.py) is the production path — XLA-CPU's pack/unpack
        # lowering is an order of magnitude off numpy's C loops, enough to
        # stall links via TCP backpressure at 16Mi elements (measured).
        # ST_HOST_CODEC=numpy|xla overrides (parity tests pin either).
        # CPU backend specifically — on any accelerator (TPU or GPU) the
        # codec must stay a device computation; only a host-only backend
        # should fall back to host loops. host_tier_active prefers the
        # configured platform string over jax.default_backend(): the latter
        # INITIALIZES the backend, and a live XLA CPU client's thread pool
        # contends with the host tier's C loops (measured on a 1-vCPU host:
        # 2.7x slower frames). A host-tier node must never start a backend.
        self._np = host_tier_active()
        if seed_values:
            if self._np:
                from .ops.codec_np import flatten_np

                self.values = flatten_np(template, self.spec)
            else:
                self.values = flatten(template, self.spec)
        else:
            self.values = (
                np.zeros(self.spec.total, np.float32)
                if self._np
                else jnp.zeros(self.spec.total, jnp.float32)
            )
        self._links: dict[int, jnp.ndarray] = {}
        # Per-link ledger of dispatched-but-unacknowledged frame deltas,
        # keyed by frame sequence number (insertion-ordered): each entry is
        # old_residual - new_residual, i.e. exactly what that frame delivers.
        # Quantizing applies error feedback immediately, but delivery is not
        # certain until the RECEIVER acknowledges (wire.ACK): the frame can
        # die in the sender pipeline, the native send queue, or the socket.
        # If the link dies first, every unacknowledged delta is rolled back
        # into the residual (drop_link/nack_frame), so a re-grafted uplink
        # re-owes it. Each ledger entry is the FRAME itself (device-side,
        # ~n/8 bytes): a frame's delta is exactly scale*(1-2*bit), so
        # re-APPLYING the frame to the residual undoes its error feedback
        # bit-for-bit — 32x less memory than materializing the delta, which
        # matters at pipeline depth 8+ on multi-Mi tables.
        #
        # Delivery contract this buys (stated precisely because the flood
        # makes it subtle): FIRST-HOP delivery is guaranteed — an update is
        # never lost between this node and a live neighbor. Mass that was
        # acknowledged by an INTERIOR node which then crashes before flooding
        # it onward can still be lost tree-wide (a per-hop ack cannot witness
        # end-to-end flood completion, and the codec's gradual residual drain
        # admits no exact frame->content mapping to ack transitively).
        # Therefore: state that has finished propagating is never lost; a
        # graceful leave (peer.drain() then close()) loses nothing; a CRASH
        # of an interior node may drop the in-transit mass sitting in its RX
        # queue/residuals at that instant, after which the tree still repairs
        # to agreement via the re-graft diff handshake. The reference kills
        # the entire tree on any death (quirk Q8), so every arm of this
        # contract is strictly stronger.
        self._inflight: dict[int, dict[int, tuple[TableFrame, ...]]] = {}
        self._frame_seq = 0
        # observability (SURVEY.md §5.5: the reference has none).
        # ONE meaning per counter (peer.metrics() documents the full
        # taxonomy): frames_out = non-idle codec frames handed toward the
        # wire — counted at fetch on the pipelined device path
        # (finish_frame) and at quantize on the burst path
        # (begin_frame_burst); same set of frames, timing differs by at
        # most the pipeline depth. frames_in = codec frames applied from
        # the wire. Idle (all-zero-scale) frames count in neither.
        self.frames_out = 0
        self.frames_in = 0
        self.updates = 0

    @property
    def host_tier(self) -> bool:
        """True when the codec runs as synchronous host (numpy/C) work rather
        than async device dispatch — callers tune pipelining accordingly."""
        return self._np

    # -- links -------------------------------------------------------------

    def _asarray(self, x) -> Any:
        """Array in this tier's native type (numpy on CPU, jax on device)."""
        return (
            np.asarray(x, np.float32)
            if self._np
            else jnp.asarray(x, jnp.float32)
        )

    def _zeros(self) -> Any:
        return (
            np.zeros(self.spec.total, np.float32)
            if self._np
            else jnp.zeros(self.spec.total, jnp.float32)
        )

    def new_link(
        self,
        link_id: int,
        seed: bool = True,
        residual: Optional[jnp.ndarray] = None,
    ) -> None:
        """Open a link. ``seed=True`` preloads the residual with the full
        current replica, so the peer on the other end receives complete
        state-to-date through normal codec frames — the reference's join /
        state-transfer mechanism (src/sharedtensor.c:379-381 master seeding;
        §5.4), generalized to any link at any time (rejoin support).

        ``residual`` overrides the seed with an explicit starting residual:
        the peer engine uses this to carry a dead uplink's undelivered
        residual onto the re-grafted uplink, so a node's pending updates
        survive its parent's death instead of being lost."""
        with self._lock:
            if link_id in self._links:
                raise DuplicateLink(f"link {link_id} already exists")
            if residual is not None:
                if residual.shape != (self.spec.total,):
                    raise ValueError(
                        f"residual shape {residual.shape} != ({self.spec.total},)"
                    )
                self._links[link_id] = self._asarray(residual)
            elif seed:
                self._links[link_id] = self.values
            else:
                self._links[link_id] = self._zeros()

    def new_link_diff(self, link_id: int, peer_snapshot: jnp.ndarray) -> None:
        """Open a downstream link toward a peer whose replica currently equals
        ``peer_snapshot``, seeding the residual with (our replica − theirs) —
        the delta that, once streamed, converges them to our state. A fresh
        joiner's snapshot is all-zero, making this exactly the reference's
        seed-with-full-replica join (src/sharedtensor.c:379-381); a re-grafted
        peer with live state receives only what it is missing (the reference
        cannot re-graft at all, quirk Q8)."""
        with self._lock:
            if link_id in self._links:
                raise DuplicateLink(f"link {link_id} already exists")
            snap = self._asarray(peer_snapshot)
            if snap.shape != (self.spec.total,):
                raise ValueError(
                    f"snapshot shape {snap.shape} != ({self.spec.total},)"
                )
            self._links[link_id] = self.values - snap

    def stash_carry(self, link_id: int, carry_id: int) -> bool:
        """Move a dead link's residual (unacked frames rolled back) into the
        live carry pseudo-slot ``carry_id``, merging with any existing carry
        — ONE lock acquisition. A multi-step pop/merge/create would leave a
        window where a concurrent add() finds neither the dead link nor the
        carry slot, and that orphan mass would later be erased tree-wide by
        the re-graft diff (the loss the live slot exists to prevent).
        Returns False if ``link_id`` is unknown (mid-handshake death)."""
        with self._lock:
            resid = self._links.pop(link_id, None)
            if resid is None:
                return False
            inflight = self._inflight.pop(link_id, {})
            resid = self._unapply(resid, inflight)
            prev = self._links.pop(carry_id, None)
            if prev is not None:
                resid = resid + prev
            self._links[carry_id] = resid
            return True

    def take_link_and_snapshot(
        self, link_id: int
    ) -> tuple[Optional[jnp.ndarray], jnp.ndarray]:
        """drop_link + replica snapshot under ONE lock acquisition. The
        peer's re-graft uses this on its carry pseudo-link: an add() landing
        between a separate drop and snapshot would appear in the snapshot
        but not the carry — presenting orphan-period mass as tree-known
        state, which the parent's diff seed then erases tree-wide."""
        with self._lock:
            resid = self._links.pop(link_id, None)
            inflight = self._inflight.pop(link_id, {})
            if resid is not None:
                resid = self._unapply(resid, inflight)
            return resid, self.values

    def drop_link(self, link_id: int) -> Optional[jnp.ndarray]:
        """Close a link (peer died or left); returns its undelivered residual
        (or None if unknown) INCLUDING any unacknowledged in-flight frame
        deltas — those frames were quantized but never delivered, so their
        error feedback is rolled back into what the replacement link owes.
        The peer engine re-seeds a re-grafted uplink with this so pending
        updates survive a parent's death. The reference instead kills the
        whole process on any link failure (quirk Q8)."""
        with self._lock:
            resid = self._links.pop(link_id, None)
            inflight = self._inflight.pop(link_id, {})
            if resid is not None:
                resid = self._unapply(resid, inflight)
            return resid

    def _unapply(self, resid: jnp.ndarray, frames: dict) -> jnp.ndarray:
        """Roll back unacknowledged frames: a frame's delta is exactly
        scale*(1-2*bit), so re-applying it to the residual restores the
        pre-quantize state bit-for-bit (see the ledger comment above).
        Ledger entries are tuples of frames (a burst rolls back whole)."""
        if self._np:
            from .ops.codec_np import apply_table_many_np

            for entry in frames.values():
                for f in entry:
                    resid = apply_table_many_np(
                        (resid,), np.asarray(f.scales), np.asarray(f.words),
                        self.spec,
                    )[0]
            return resid
        for entry in frames.values():
            for f in entry:
                resid = apply_table_many((resid,), f, self.spec)[0]
        return resid

    @property
    def link_ids(self) -> tuple[int, ...]:
        with self._lock:
            return tuple(self._links)

    def inflight_total(self) -> int:
        """Number of dispatched MESSAGES (ledger entries — a burst counts
        once, however many frames it carries) not yet acknowledged by their
        receivers, across all links (0 = everything sent has landed)."""
        with self._lock:
            return sum(len(q) for q in self._inflight.values())

    def snapshot_all(self) -> tuple[jnp.ndarray, dict[int, jnp.ndarray]]:
        """Consistent point-in-time view of (replica, {link: residual}) under
        ONE lock acquisition — the checkpoint primitive. Separate
        snapshot_flat + per-link reads would let a concurrent frame land
        between them, tearing the error-feedback invariant on restore."""
        with self._lock:
            return self.values, dict(self._links)

    # -- user API ----------------------------------------------------------

    def read(self) -> Any:
        """Snapshot of the replica as the caller's pytree structure
        (reference l_copyToTensor, src/sharedtensor.c:435-446)."""
        if self._np:
            from .ops.codec_np import unflatten_np

            return unflatten_np(self.values, self.spec)
        return unflatten(self.values, self.spec)

    def reset_values(self) -> None:
        """Zero the replica (keep links/residuals). The wire-compat re-graft
        path uses this: the reference protocol has no diff handshake, so a
        re-grafted uplink re-seeds us with the parent's FULL replica —
        fresh-joiner semantics (zeroed state, undelivered residual carried
        onto the new uplink) are the only exact ones expressible in-protocol
        (see peer._handle_events)."""
        with self._lock:
            self.values = self._zeros()

    def regraft_reset_to_carry(self, carry_id: int, new_link_id: int) -> None:
        """The wire-compat leaf re-graft, as ONE atomic step: consume the
        carry pseudo-slot, set the replica to EXACTLY the carry, and open
        the new uplink with the carry as its residual.

        Fresh-joiner semantics under the reference protocol mean the parent
        re-seeds us with its full replica additively — so our replica must
        start at precisely the mass the tree does NOT yet know (the carry),
        the way a true fresh joiner with pending adds holds them in values
        AND residual (add(): both sides). Resetting to zero instead loses
        the carry from this node forever: it streams up and floods to every
        OTHER peer (split horizon never returns it), ending with the tree
        at state+carry and this node at state. Atomicity for the same
        reason as stash_carry: a concurrent add() must land either in
        (carry -> values+residual) or in (values+new residual), never
        partially."""
        with self._lock:
            if new_link_id in self._links:
                raise DuplicateLink(f"link {new_link_id} already exists")
            carry = self._links.pop(carry_id, None)
            if carry is None:
                self.values = self._zeros()
                self._links[new_link_id] = self._zeros()
            else:
                # arrays are functional (replaced, never mutated) on both
                # tiers, so values and the residual may share storage
                self.values = carry
                self._links[new_link_id] = carry

    def snapshot_flat(self) -> jnp.ndarray:
        """Atomic snapshot of the padded flat replica (handshake / checkpoint
        use). Values arrays are replaced, never mutated, so the reference's
        torn-read hazard (§5.2) cannot occur."""
        with self._lock:
            return self.values

    def add(self, delta: Any) -> None:
        """Merge an additive update: replica and every link residual receive
        it (reference addFromInternal, src/sharedtensor.c:334-344)."""
        if self._np:
            from .ops.codec_np import flatten_np

            update = flatten_np(delta, self.spec)
        else:
            update = flatten(delta, self.spec)
        with self._lock:
            ids = tuple(self._links)
            arrays = (self.values, *(self._links[i] for i in ids))
            if self._np:
                from .ops.codec_np import accumulate_table_np

                out = accumulate_table_np(arrays, np.asarray(update), self.spec)
            else:
                out = accumulate_table(arrays, update, self.spec)
            self.values = out[0]
            for i, r in zip(ids, out[1:]):
                self._links[i] = r
            self.updates += 1

    def mask_link_residual(self, link_id: int, elo: int, ehi: int) -> None:
        """Zero a link's residual OUTSIDE [elo, ehi) — the r10 range-
        subscription discipline: adds/floods refill the full residual, but
        a ranged subscriber link's receiver will never get the out-of-range
        mass, so the sender drops it before scale selection instead of
        letting it decay through frames of useless traffic (the native
        engine does the same in its subscriber branch). Functional replace,
        never an in-place mutation — snapshots may share storage."""
        with self._lock:
            r = self._links.get(link_id)
            if r is None:
                return
            if self._np:
                m = np.array(r, np.float32, copy=True)
                m[:elo] = 0.0
                m[ehi:] = 0.0
            else:
                m = jnp.asarray(r).at[:elo].set(0.0).at[ehi:].set(0.0)
            self._links[link_id] = m

    # -- sync engine hooks -------------------------------------------------

    def begin_frame(self, link_id: int) -> Optional[tuple[int, TableFrame]]:
        """Dispatch one sender step for a link: quantize the residual into a
        frame (device arrays, NOT yet fetched) and apply error feedback.
        Returns (seq, frame), or None if the link was dropped concurrently
        (peer death race). ``seq`` identifies the frame in the in-flight
        ledger; the caller must eventually :meth:`ack_frame` it (delivered or
        provably no-op) or let nack/drop roll it back.

        Split from :meth:`finish_frame` so the peer engine can double-buffer:
        dispatch frame t+1's quantize before fetching/sending frame t, so the
        device computes while the host does the transfer + socket write
        (round-2 verdict Weak #2: the serialized path left the device idle
        during every send)."""
        with self._lock:
            resid = self._links.get(link_id)
            if resid is None:
                return None
            if self._np:
                from .ops.codec_np import quantize_table_np

                scales, words, new_resid = quantize_table_np(
                    resid,
                    self.spec,
                    self.codec.scale_policy,
                    self.codec.per_leaf_scale,
                )
                frame = TableFrame(scales, words)
            else:
                frame, new_resid = quantize_table(
                    resid,
                    self.spec,
                    self.codec.scale_policy,
                    self.codec.per_leaf_scale,
                )
            # Storing unconditionally is safe: at scale 0 the new residual is
            # identical to the old one.
            self._links[link_id] = new_resid
            self._frame_seq += 1
            seq = self._frame_seq
            # the frame IS its own delivery record; re-applied on nack/drop
            self._inflight.setdefault(link_id, {})[seq] = (frame,)
        return seq, frame

    def begin_frame_burst(
        self, link_id: int, k: int
    ) -> Optional[tuple[int, list[TableFrame]]]:
        """Quantize up to ``k`` successive frames of a link's residual in one
        call — each frame halves what the previous one left (the same
        sequence the streaming path would produce one message at a time),
        stopping early when the residual quantizes to all-zero scales. The
        burst is ONE in-flight ledger entry / ONE wire message / ONE
        receiver ACK. Host (numpy) tier only: the loop is synchronous host
        work. Returns (seq, frames) with 0..k frames (0 = link idle)."""
        from .ops.codec_np import quantize_table_np

        with self._lock:
            resid = self._links.get(link_id)
            if resid is None:
                return None
            frames: list[TableFrame] = []
            for _ in range(k):
                scales, words, new_resid = quantize_table_np(
                    resid,
                    self.spec,
                    self.codec.scale_policy,
                    self.codec.per_leaf_scale,
                )
                if not scales.any():
                    break  # idle: nothing left the codec can express
                frames.append(TableFrame(scales, words))
                resid = new_resid
            self._links[link_id] = resid
            self._frame_seq += 1
            seq = self._frame_seq
            if frames:
                self._inflight.setdefault(link_id, {})[seq] = tuple(frames)
            self.frames_out += len(frames)
        return seq, frames

    def begin_frame_burst_device(
        self, link_id: int, k: int
    ) -> Optional[tuple[int, TableFrame]]:
        """Device-tier burst: K successive halvings quantized in ONE jitted
        dispatch (ops/table.quantize_table_burst), fetched later with ONE
        device->host sync (:meth:`finish_frame_burst`). One ledger entry /
        wire message / receiver ACK, like the host burst. Returns
        (seq, stacked TableFrame with leading K axis) — device arrays, not
        yet fetched."""
        with self._lock:
            resid = self._links.get(link_id)
            if resid is None:
                return None
            frames, new_resid = quantize_table_burst(
                resid,
                self.spec,
                k,
                self.codec.scale_policy,
                self.codec.per_leaf_scale,
            )
            self._links[link_id] = new_resid
            self._frame_seq += 1
            seq = self._frame_seq
            # ledger rollback re-applies per frame; zero-scale tail frames
            # are exact no-ops so storing all K is correct
            self._inflight.setdefault(link_id, {})[seq] = tuple(
                TableFrame(frames.scales[i], frames.words[i]) for i in range(k)
            )
        return seq, frames

    def finish_frame_burst(
        self, frames: TableFrame
    ) -> Optional[list[TableFrame]]:
        """Fetch a dispatched burst with one blocking sync and trim the
        all-zero-scale tail (once a frame quantizes to zero scales, every
        later scan step is a no-op — zeros appear only as a suffix).
        Returns None for a fully idle burst (suppressed, like
        finish_frame)."""
        scales, words = jax.device_get((frames.scales, frames.words))
        k_eff = 0
        for i in range(scales.shape[0]):
            if not scales[i].any():
                break
            k_eff = i + 1
        if k_eff == 0:
            return None
        self.frames_out += k_eff
        return [TableFrame(scales[i], words[i]) for i in range(k_eff)]

    def ack_frame(self, link_id: int, seq: int) -> None:
        """Frame ``seq`` is accounted for — the receiver acknowledged it, or
        it was an idle no-op (zero delta) that never hit the wire: forget its
        in-flight delta."""
        with self._lock:
            q = self._inflight.get(link_id)
            if q is not None:
                q.pop(seq, None)

    def nack_frame(self, link_id: int) -> None:
        """Delivery failed but the link still exists: roll every outstanding
        frame's error feedback back into the residual (the deltas were never
        received, so the link's peer is still owed them)."""
        with self._lock:
            q = self._inflight.pop(link_id, None)
            resid = self._links.get(link_id)
            if resid is None or not q:
                return
            self._links[link_id] = self._unapply(resid, q)

    def finish_frame(self, frame: TableFrame) -> Optional[TableFrame]:
        """Fetch a dispatched frame to host memory. Returns None for an idle
        frame (every leaf at scale 0) when the codec suppresses them (fixing
        reference quirk Q2 — it transmits 1 zero-scale frame/s/link forever).

        One device->host transfer serves both the idle check and the wire
        encoding (the frame is bytes-bound anyway). Doing the idle check as
        its own jnp.any() would cost a second blocking sync per frame."""
        scales, words = jax.device_get((frame.scales, frame.words))
        if self.codec.suppress_zero_frames and not scales.any():
            return None
        self.frames_out += 1
        return TableFrame(scales, words)

    def make_frame(self, link_id: int) -> Optional[TableFrame]:
        """begin_frame + finish_frame in one call, acknowledged immediately —
        the caller takes delivery responsibility (tests, simple callers)."""
        out = self.begin_frame(link_id)
        if out is None:
            return None
        seq, frame = out
        fetched = self.finish_frame(frame)
        self.ack_frame(link_id, seq)
        return fetched

    def receive_frame(self, link_id: int, frame: TableFrame) -> None:
        """Apply an incoming frame to the replica and to every *other* link's
        residual (split-horizon flood with per-hop re-quantization, reference
        sync_in src/sharedtensor.c:124-127). ``link_id`` may be unknown
        (already-dropped peer): the frame still applies to the replica.

        Corruption-zeroed (all-zero-scale) frames apply as no-ops and count
        NOWHERE — the same taxonomy rule the engine tier enforces
        (stengine.cpp apply_batch): a quiesced pair must satisfy
        sender.frames_out == receiver.frames_in on every tier, or the
        divergence reads as a phantom discrepancy exactly when an operator
        is debugging a corrupt link."""
        if not np.asarray(frame.scales).any():
            return
        with self._lock:
            others = tuple(i for i in self._links if i != link_id)
            arrays = (self.values, *(self._links[i] for i in others))
            if self._np:
                from .ops.codec_np import apply_table_many_np

                out = apply_table_many_np(
                    arrays,
                    np.asarray(frame.scales),
                    np.asarray(frame.words),
                    self.spec,
                )
            else:
                out = apply_table_many(arrays, frame, self.spec)
            self.values = out[0]
            for i, r in zip(others, out[1:]):
                self._links[i] = r
            self.frames_in += 1

    def receive_frames(self, link_id: int, frames: list[TableFrame]) -> None:
        """Batched :meth:`receive_frame`: apply K queued frames from one link
        in a single device dispatch (their summed delta — codec deltas are
        pure adds and commute). K is padded with zero-scale no-op frames to
        the next power of two so jit specializes on O(log K) shapes. This is
        the receive path's defense against dispatch-overhead backlog: a
        sender can emit frames faster than a busy device can absorb
        one-dispatch-per-frame (see ops/table.py apply_table_batch)."""
        if not frames:
            return
        if len(frames) == 1:
            return self.receive_frame(link_id, frames[0])
        # all-zero-scale frames apply as no-ops and count nowhere (the
        # engine tier's taxonomy rule — see receive_frame)
        applied = sum(1 for f in frames if np.asarray(f.scales).any())
        if applied == 0:
            return
        if self._np:
            scales = np.stack([np.asarray(f.scales) for f in frames])
            words = np.stack([np.asarray(f.words) for f in frames])
            from .ops.codec_np import apply_table_batch_np

            with self._lock:
                others = tuple(i for i in self._links if i != link_id)
                arrays = (self.values, *(self._links[i] for i in others))
                out = apply_table_batch_np(arrays, scales, words, self.spec)
                self.values = out[0]
                for i, r in zip(others, out[1:]):
                    self._links[i] = r
                self.frames_in += applied
            return
        k = 1
        while k < len(frames):
            k *= 2
        scales = np.zeros((k, self.spec.num_leaves), np.float32)
        words = np.zeros((k, self.spec.total // 32), np.uint32)
        for i, f in enumerate(frames):
            scales[i] = np.asarray(f.scales)
            words[i] = np.asarray(f.words)
        stacked = TableFrame(jnp.asarray(scales), jnp.asarray(words))
        with self._lock:
            others = tuple(i for i in self._links if i != link_id)
            arrays = (self.values, *(self._links[i] for i in others))
            out = apply_table_batch(arrays, stacked, self.spec)
            self.values = out[0]
            for i, r in zip(others, out[1:]):
                self._links[i] = r
            self.frames_in += applied

    # -- introspection -----------------------------------------------------

    def state_version(self) -> int:
        """Monotone change counter for the replica: bumps on every local
        add and every applied foreign frame. Cheap (two counter reads) —
        the peer's ranged-subscriber send path uses it to skip the
        full-table residual mask on passes where nothing moved
        (peer._send_sub)."""
        return self.updates + self.frames_in

    def residual_rms(self, link_id: int) -> float:
        with self._lock:
            r = self._links.get(link_id)
        if r is None:
            return 0.0
        if self._np:
            # numpy on the host tier: drain()/metrics() call this, and a
            # jnp reduction here would initialize the XLA CPU backend —
            # undoing the tier's no-backend invariant for the process's
            # whole lifetime (2.7x frame-rate contention, see __init__).
            r = np.asarray(r, np.float64)
            return float(np.sqrt(np.dot(r, r) / self.spec.total_n))
        return float(jnp.sqrt(jnp.sum(r * r) / self.spec.total_n))

    def __repr__(self) -> str:  # pragma: no cover
        return (
            f"SharedTensor(leaves={self.spec.num_leaves}, n={self.spec.total_n}, "
            f"links={list(self._links)}, out={self.frames_out}, in={self.frames_in})"
        )
