"""Async data-parallel SGD over the pod tier: the reference's training story,
fused into one XLA program per step.

The reference's workload is N workers each looping {copyToTensor; compute a
local update; addFromTensor} while peer updates stream in asynchronously
(reference README.md:13-19, example.lua:14-26). On a pod, each device along
the ``peer`` mesh axis is one such worker; a training step is

  1. every peer computes grads of its own replica on its own batch
     (``jax.vmap`` over the peer axis — GSPMD keeps each peer's compute on
     its own device, zero cross-device traffic);
  2. ``add_updates``: the scaled update lands in the peer's replica (visible
     immediately, like ``addFromTensor``) and its outgoing residual;
  3. the fused compressed sync step (parallel/ici.py): 1-bit quantize +
     all-gather over ICI + split-horizon apply.

One ``jax.jit`` covers all three, so XLA overlaps the codec/collective with
backward-pass compute where the schedule allows. Compute never blocks on
host round-trips — the async-semantics contract (reference README.md:24)
holds step-to-step: a peer's update is visible locally at once and reaches
others compressed, with bounded +/-scale overshoot.

``sync_every > 1`` trades freshness for bandwidth exactly like the
reference's natural backpressure pacing (its TCP link simply falls behind and
residuals accumulate, reference src/sharedtensor.c:176-177): local steps
accumulate into the residual and one compressed frame carries their sum.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..config import CodecConfig, MeshConfig, ScalePolicy
from ..ops.table import TableSpec, flatten, make_spec, unflatten
from ..parallel.ici import (
    PeerSyncState,
    add_updates,
    add_updates_raw,
    build_sync_phases,
    build_sync_step,
    init_state,
    read_peer,
)
from ..utils import profiling


def build_train_step(
    mesh: Mesh,
    spec: TableSpec,
    loss_fn: Callable[[Any, Any], jnp.ndarray],
    policy: ScalePolicy = ScalePolicy.POW2_RMS,
    per_leaf: bool = True,
    compressed: bool = True,
    sync: bool = True,
    config: MeshConfig | None = None,
    optimizer=None,
    overlap: bool = False,
):
    """Compile ``(state, opt_state, batch, lr) -> (state', opt_state',
    per-peer loss, scales)``.

    ``loss_fn(params, batch_item) -> scalar`` sees the caller's parameter
    pytree; ``batch`` carries a leading peer axis on every leaf. It may
    return ``(scalar, aux)`` instead, ``aux`` a pytree of arrays (counters,
    parts of the loss): the step then returns a fifth element, ``aux`` with
    a leading peer axis, computed in the same program. ``lr`` is a
    traced scalar so schedules don't retrigger compilation. ``sync=False``
    builds the no-communication arm (pure local SGD — the isolation baseline
    for convergence comparisons).

    ``optimizer`` is any optax GradientTransformation, applied per peer to
    the FLAT gradient vector (each peer keeps its own momentum/Adam state;
    ``lr`` is then ignored — the transform owns the step size). The transform
    must be elementwise (momentum/adam/rmsprop/...), since it sees the padded
    flat buffer, not the parameter tree. Its additive updates flow through
    the same path as plain SGD deltas: visible locally at once, compressed
    toward the group.

    ``overlap=True`` (compressed sync only) reorders the fused program so
    the ICI all-gather has no data dependency on this step's compute: the
    CURRENT residual is quantized + gathered first, grads run in the middle,
    and the gathered frames + local update land at the end — XLA's latency-
    hiding scheduler then runs the collective under the backward pass
    instead of serializing after it (the reference's "compute never waits
    for sync", README.md:24; SURVEY.md §7.4 hard part 1). The local update
    is delivered one step later; eventual consistency is unchanged.
    ``apply_gathered(values, *send(residual)[1:])`` composed immediately is
    bit-for-bit the non-overlap sync (tests pin this).

    Host span ``st:build_train_step`` (around ``st:build_sync_step``); the
    program is built, ``st:build.*`` of program ``_step``, inside the
    ``st:train.step`` that first calls it."""
    with profiling.pod_tier().span("build_train_step"):
        cfg = config or MeshConfig()
        if overlap and (not sync or not compressed):
            raise ValueError("overlap=True requires sync=True and compressed=True")
        sync_raw = (
            build_sync_step(
                mesh,
                spec,
                policy=policy,
                per_leaf=per_leaf,
                compressed=compressed,
                config=cfg,
                jit_compile=False,
            )
            if sync and not overlap
            else None
        )
        phases = (
            build_sync_phases(
                mesh, spec, policy=policy, per_leaf=per_leaf, config=cfg
            )
            if sync and overlap
            else None
        )
        k = spec.num_leaves if per_leaf else 1

        def loss_and_aux(params, batch_item):
            out = loss_fn(params, batch_item)
            return out if isinstance(out, tuple) else (out, None)

        grad_fn = jax.value_and_grad(loss_and_aux, has_aux=True)

        def per_peer(values_row: jnp.ndarray, batch_item):
            with jax.named_scope("st.grads"):
                params = unflatten(values_row, spec)
                (loss, aux), grads = grad_fn(params, batch_item)
                return loss, flatten(grads, spec), aux

        def with_aux(state, opt_state, losses, scales, aux):
            out = (state, opt_state, losses, scales)
            return out if aux is None else (*out, aux)

        def update_of(g, opt_state, values, lr):
            with jax.named_scope("st.update"):
                if optimizer is None:
                    return -lr * g, opt_state
                return jax.vmap(optimizer.update)(g, opt_state, values)

        def _step(state: PeerSyncState, opt_state, batch, lr):
            if phases is not None:
                # OVERLAP mode: quantize + all-gather the residual as it stands —
                # no data dependency on this step's grads, so XLA's latency-
                # hiding scheduler runs the collective under the backward pass.
                # The local update below rides the NEXT step's frame (async
                # semantics unchanged: a frame carries whatever residual mass
                # exists at frame time, exactly like the reference's streams).
                send, apply_gathered = phases
                r2, words_all, scales_all = send(state.residual)
                losses, g, aux = jax.vmap(per_peer)(state.values, batch)
                updates, opt_state = update_of(g, opt_state, state.values, lr)
                v2 = apply_gathered(state.values, words_all, scales_all)
                state = add_updates_raw(PeerSyncState(v2, r2), updates)
                return with_aux(state, opt_state, losses, scales_all, aux)
            losses, g, aux = jax.vmap(per_peer)(state.values, batch)
            updates, opt_state = update_of(g, opt_state, state.values, lr)
            state = add_updates_raw(state, updates)
            if sync_raw is not None:
                state, scales = sync_raw(state)
            else:
                scales = jnp.zeros((state.values.shape[0], k), jnp.float32)
            return with_aux(state, opt_state, losses, scales, aux)

        return jax.jit(_step, donate_argnums=(0,) if optimizer is None else (0, 1))


@dataclasses.dataclass
class PodTrainer:
    """Convenience wrapper owning the sharded state + compiled step.

    ``create_or_fetch`` for the pod tier: construct with a parameter template
    and every peer starts from that seed, replicas kept eventually-consistent
    by the compressed sync (the in-pod analog of comm/peer.py's
    ``create_or_fetch`` — SURVEY.md §2.2 row 1)."""

    mesh: Mesh
    template: Any
    loss_fn: Callable[[Any, Any], jnp.ndarray]
    codec: CodecConfig = dataclasses.field(default_factory=CodecConfig)
    mesh_config: MeshConfig = dataclasses.field(default_factory=MeshConfig)
    compressed: bool = True
    sync: bool = True
    optimizer: Any = None  # optax GradientTransformation (see build_train_step)
    overlap: bool = False  # collective under the backward pass (see build_train_step)
    #: Pod steps per sync exchange. With k > 1, k-1 steps run the no-sync
    #: program (updates accumulate in the residual — the module docstring's
    #: freshness-for-bandwidth trade, the analog of the reference's natural
    #: TCP backpressure pacing) and every k-th step syncs the accumulated sum
    #: as ONE frame.
    sync_every: int = 1

    def __post_init__(self):
        """Host spans: ``st:trainer_init`` around ``st:make_spec``,
        ``st:init_state`` (and its children, which wait for their arrays:
        see :func:`~shared_tensor_tpu.parallel.ici.init_state`),
        ``st:opt_init`` (dispatches only) and one ``st:build_train_step`` a
        program (builds nothing yet: the first ``st:train.step`` does)."""
        pod = profiling.pod_tier()
        with pod.span("trainer_init"):
            with pod.span("make_spec"):
                # the device table: each leaf of whole 8 x 128 tiles in its
                # tile order, so the step's view of it is a bitcast
                self.spec: TableSpec = make_spec(self.template, tile_order=True)
            self.state: PeerSyncState = init_state(
                self.mesh, self.spec, self.template, self.mesh_config
            )
            self.n_peer: int = self.mesh.shape[self.mesh_config.peer_axis]
            with pod.span("opt_init"):
                self.opt_state = (
                    None
                    if self.optimizer is None
                    else jax.vmap(self.optimizer.init)(self.state.values)
                )
            self.sync_every = max(1, int(self.sync_every))
            kw = dict(
                policy=self.codec.scale_policy,
                per_leaf=self.codec.per_leaf_scale,
                compressed=self.compressed,
                config=self.mesh_config,
                optimizer=self.optimizer,
            )
            self._step = build_train_step(
                self.mesh, self.spec, self.loss_fn,
                sync=self.sync, overlap=self.overlap, **kw,
            )
            # the off-beat program for sync_every > 1: identical step, no
            # exchange — updates pile into the residual until the sync beat
            self._step_local = (
                build_train_step(self.mesh, self.spec, self.loss_fn, sync=False, **kw)
                if self.sync and self.sync_every > 1
                else None
            )
        self.steps = 0
        #: the newest step's ``aux`` (leading peer axis, on the device) where
        #: ``loss_fn`` returns ``(loss, aux)``; None before the first step
        self.aux: Any = None
        pod.watch(self)

    def shard_batch(self, batch: Any) -> Any:
        """Pin a [n_peer, ...] batch pytree to the peer axis so each peer's
        slice lives on its own devices before the step runs."""
        ax = self.mesh_config.peer_axis

        def put(x):
            sh = NamedSharding(self.mesh, P(ax, *([None] * (x.ndim - 1))))
            return jax.device_put(x, sh)

        with profiling.pod_tier().span("shard_batch"):
            return jax.tree.map(put, batch)

    def step(self, batch: Any, lr: float = 1e-2):
        """One fused train step (+sync on every ``sync_every``-th call).
        Returns (per-peer losses f32[n_peer], per-peer-leaf scales); state
        advances in place. With an optax ``optimizer``, ``lr`` is ignored
        (the transform owns the step size)."""
        pod = profiling.pod_tier()
        pod.step_now = self.steps  # a compilation in here is this step's
        fn = self._step
        if self._step_local is not None and (self.steps + 1) % self.sync_every:
            fn = self._step_local
        synced = self.sync and fn is self._step
        # the step log: the span is the host's time inside this call, and from
        # its end to the next one's start is what the caller did
        with pod.span(
            "train.step", step_num=self.steps, program="sync" if synced else "local"
        ):
            self.state, self.opt_state, losses, scales, *aux = fn(
                self.state, self.opt_state, batch, jnp.float32(lr)
            )
        if aux:
            self.aux = aux[0]
        self.steps += 1
        pod.count_step(synced)
        return losses, scales

    def lower(self, batch: Any, lr: float = 1e-2):
        """The fused step :meth:`step` runs on a sync beat, lowered for this
        state and ``batch`` and not run: ``.compile().as_text()`` is how
        chip_smoke.py shows the codec kernels are in the program
        (``tpu_custom_call``)."""
        return self._step.lower(
            self.state, self.opt_state, batch, jnp.float32(lr)
        )

    def read(self, peer: int = 0) -> Any:
        """Peer ``peer``'s current replica as the template pytree (reference
        copyToTensor, src/sharedtensor.c:435-446)."""
        return read_peer(self.state, self.spec, peer)

    def add(self, updates: jax.Array) -> None:
        """Out-of-band additive update, [n_peer, spec.total] flat (reference
        addFromTensor outside the training loop)."""
        with profiling.pod_tier().span("add"):
            self.state = add_updates(self.state, updates)

    def replica_spread(self) -> float:
        """Max abs deviation of any replica from the peer mean — the
        eventual-consistency observable (0 when fully converged/synced)."""
        v = self.state.values
        return float(jnp.max(jnp.abs(v - jnp.mean(v, axis=0, keepdims=True))))
