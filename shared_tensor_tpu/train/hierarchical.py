"""Hierarchical tier: pods synced over ICI internally, bridged over the
DCN/TCP peer tree externally.

This composes the framework's two communication tiers into the reference's
actual multi-machine scenario (reference README.md:26: peers on mutually
reachable hosts, one port per tensor), at pod granularity: each *pod* (a
device mesh running PodTrainer's fused compressed sync) acts as ONE peer in
the TCP tree (comm/peer.py — the reference's self-organizing binary-tree
overlay, src/sharedtensor.c:192-332). Updates thus flow

  device peer --ICI all-gather (1-bit frames)--> pod replica mean
  pod --TCP tree codec frames (1-bit, error feedback)--> other pods

with the codec's error-feedback at BOTH levels and no synchronization
between them — a pod never blocks on the WAN; cross-pod deltas arrive
whenever the tree delivers them (the reference's async contract,
README.md:24, held end-to-end).

Bridge semantics (all additive, order-free):

- push: the pod's net training progress since the last push — the change of
  the pod-mean replica — is `add()`ed into the tree exactly like a worker's
  local update (reference addFromTensor, src/sharedtensor.c:334-344).
- pull: whatever the tree delivered since the last pull (other pods'
  deltas; measured against what we already pushed) is applied to every
  device replica's values, residuals untouched — split horizon at the pod
  boundary.

The peer tier's table is row-major and the pod's device table holds its
leaves of whole 8 x 128 tiles in tile order (``ops/table.py``): the bridge's
bookkeeping is kept in the peer's order and converted where it meets the
pod (its mean on the way out, the pulled delta on the way in).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from ..comm.peer import SharedTensorPeer
from ..ops.table import reorder, unflatten
from ..parallel.ici import apply_external
from .async_sgd import PodTrainer


class HierarchicalTrainer:
    """Wraps a PodTrainer and a SharedTensorPeer into one training-loop
    peer. ``sync_every`` pod steps between tree exchanges (the analog of
    the reference's natural TCP backpressure pacing: residual mass simply
    accumulates between frames).

    Contract: at construction the pod replicas must equal the peer-tier
    replica (the bridge tracks *deltas* on both sides from that common
    point; a mismatched start is permanently baked into this pod's model).
    Use :meth:`create` — it seeds a master pod from the template and a
    joiner pod from the state the tree streamed over — rather than wiring
    the pieces manually."""

    @classmethod
    def create(
        cls,
        mesh,
        host: str,
        port: int,
        template: Any,
        loss_fn,
        sync_every: int = 1,
        peer_config=None,
        timeout: float = 30.0,
        pod_sync_every: int = 1,
        **pod_kwargs,
    ) -> "HierarchicalTrainer":
        """create_or_fetch at pod granularity: become the master pod (seeded
        from ``template``) or join the tree and start the pod from the
        replica state the tree transferred (the reference's
        state-transfer-through-codec join, src/sharedtensor.c:379-391).

        Two pacing knobs, one per tier: ``sync_every`` = pod steps between
        TREE exchanges (this class's own pacing); ``pod_sync_every`` = pod
        steps between INTRA-POD ICI exchanges (threaded to
        ``PodTrainer.sync_every`` — it cannot ride ``pod_kwargs`` because
        the name collides with this function's parameter)."""
        pod_kwargs.setdefault("sync_every", pod_sync_every)
        from ..comm.peer import create_or_fetch

        peer = create_or_fetch(host, port, template, peer_config, timeout)
        try:
            # ONE snapshot seeds both the pod and the bridge bookkeeping.
            # Codec frames keep streaming into peer.st after create_or_fetch
            # returns (a joiner returns at WELCOME, mid state-transfer); any
            # frame applied between "seed the pod" and "record what the pod
            # has seen" would be counted as seen but never applied — a
            # permanent silent divergence (ADVICE.md round-1 high finding).
            snap = peer.st.snapshot_flat()
            pod = PodTrainer(
                mesh, unflatten(snap, peer.st.spec), loss_fn, **pod_kwargs
            )
            return cls(pod, peer, sync_every, _peer_seen=snap)
        except BaseException:
            peer.close()
            raise

    def __init__(
        self,
        pod: PodTrainer,
        peer: SharedTensorPeer,
        sync_every: int = 1,
        _peer_seen: jnp.ndarray | None = None,
    ):
        # the same tree, shapes and padding; the leaves' orders may differ
        if peer.st.spec.row_major().layout_digest() != pod.spec.row_major().layout_digest():
            raise ValueError("pod table layout != peer table layout")
        self.pod = pod
        self.peer = peer
        self.sync_every = max(1, int(sync_every))
        # What the pod has already incorporated of the peer-tier replica,
        # and what the peer tier already has of the pod's progress, both in
        # the peer's order.
        # ``_peer_seen`` must be the exact snapshot the pod was seeded from
        # (create() passes it); deriving it from the pod itself keeps the
        # invariant for manual wiring too — a fresh peer.st.snapshot_flat()
        # here would silently absorb frames applied since the pod seed.
        self._pod_pushed = self._pod_mean()
        self._peer_seen = _peer_seen if _peer_seen is not None else self._pod_pushed
        self.exchanges = 0

    def _pod_mean(self) -> jnp.ndarray:
        """The pod replicas' mean, in the peer's order."""
        mean = jnp.mean(self.pod.state.values, axis=0)
        return reorder(mean, self.pod.spec, self.peer.st.spec)

    def step(self, batch: Any, lr: float = 1e-2):
        losses, scales = self.pod.step(batch, lr)
        if self.pod.steps % self.sync_every == 0:
            self.exchange()
        return losses, scales

    def exchange(self) -> None:
        """One push+pull against the tree. Non-blocking beyond the device
        reductions: `add` enqueues into link residuals; frames stream in the
        peer engine's background threads."""
        # pull: tree progress since last seen (excludes our own pushes,
        # which are already in _peer_seen via the push bookkeeping below)
        snap = self.peer.st.snapshot_flat()
        incoming = snap - self._peer_seen
        # push: pod training progress since last push. MUST go through the
        # peer object's add (not st.add): it wakes the send loop — a direct
        # st.add leaves frames waiting for the next keepalive tick.
        mean = self._pod_mean()
        outgoing = mean - self._pod_pushed
        self.peer.add(unflatten(outgoing, self.peer.st.spec))
        # The peer replica now includes our push; remember both.
        self._peer_seen = snap + outgoing
        self._pod_pushed = mean + incoming  # after applying incoming below
        apply = jax.device_get(incoming)  # host hop: peer tier is host-side
        self.pod.state = apply_external(
            self.pod.state, reorder(jnp.asarray(apply), self.peer.st.spec, self.pod.spec)
        )
        self.exchanges += 1

    def read(self, peer: int = 0) -> Any:
        return self.pod.read(peer)

    def close(self) -> None:
        self.peer.close()
