"""The pod tier: async compressed peer sync over ICI collectives.

This is the BASELINE.json north star — "the TCP tree-topology peer sync behind
addFromTensor/copyToTensor is replaced by ICI reduce-scatter + all-gather over
the pod mesh, preserving the async eventually-consistent update semantics".

Topology re-design (TPU-first, not a port): the reference connects peers in a
binary tree because TCP links are point-to-point and flooding with per-hop
re-quantization is how a tree broadcasts (reference src/sharedtensor.c:124-127;
SURVEY.md §2.3). A TPU pod's ICI is an all-to-all fabric with hardware
collectives, so the tree disappears: every device on the ``peer`` mesh axis is
a peer holding its own replica, and one sync step is

  1. quantize the local residual (1-bit sign + per-leaf pow2-RMS scale, error
     feedback — the exact reference codec, ops/table.py semantics);
  2. ``all_gather`` the *packed sign words + scales* over the peer axis —
     1 bit/element on the wire, 32x less ICI traffic than an fp32 ``psum``;
  3. apply the sum of every *other* peer's reconstructed delta to the local
     replica (split horizon, reference sync_in src/sharedtensor.c:119-129).

Because the graph is fully connected, the reference's flood-and-requantize
(each hop re-quantizes, degrading the signal down the tree) is unnecessary:
every peer receives every other peer's frame first-hand, at one quantization.
Semantics preserved: updates merge additively, replicas are eventually
consistent with bounded +/-scale overshoot, and compute never has to wait — a
step syncs whatever residual mass exists and converged peers idle at scale 0.

The ``shard`` mesh axis additionally shards the flat table buffer, so the
replica is a pod-resident sharded jax.Array: per-leaf scale reductions psum
over the shard axis and the peer all-gather moves only local shards. Tables
beyond one device's HBM (the reference crashes at ~60 Mi elements, quirk Q6)
sync at ICI speed.

The exact arm (``compressed=False``) delivers every peer's pending residual
exactly via fp32 ``psum`` — the "exact allreduce" comparison required by
BASELINE config 4.

Everything here is functional and jitted; one fused step does codec + exchange
+ apply with no host round-trips.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..config import MeshConfig, ScalePolicy
from ..ops.codec import SAT
from ..ops.packing import BITS_PER_WORD, LANES
from ..ops.table import (
    LeafRows,
    TableSpec,
    apply_rows,
    flatten,
    leaf_scales,
    live_lanes,
    quantize_rows,
    resolve_impl,
    unflatten,
)
from ..utils.profiling import pod_tier
from .mesh import rows_per_shard


class PeerSyncState(NamedTuple):
    """Per-peer replicas + residuals, sharded over the (peer, shard) mesh.

    ``values[p]`` is peer p's full replica of the flat padded table (the
    reference's ``values[]``, src/sharedtensor.c:34); ``residual[p]`` is its
    one outgoing residual toward the group (the reference's per-link
    ``delta[]``, one per tree link — fully connected needs only one)."""

    values: jax.Array  # f32[n_peer, spec.total]
    residual: jax.Array  # f32[n_peer, spec.total]


def state_sharding(mesh: Mesh, config: MeshConfig | None = None) -> NamedSharding:
    cfg = config or MeshConfig()
    return NamedSharding(mesh, P(cfg.peer_axis, cfg.shard_axis))


#: :func:`flatten` / :func:`unflatten` as one program each, for the pod's
#: table outside a trace: op by op, every tiled leaf's reshape and transpose
#: would build a program of its own
_flatten = jax.jit(flatten, static_argnums=1)
_unflatten = jax.jit(unflatten, static_argnums=1)


def init_state(
    mesh: Mesh,
    spec: TableSpec,
    template=None,
    config: MeshConfig | None = None,
) -> PeerSyncState:
    """All peers start from the same seed (``template``, or zeros). The
    reference instead has one master seed its state and stream it to joiners
    (src/sharedtensor.c:379-381); in-pod peers are born simultaneously so the
    seed is just replicated — the streaming join path lives in the DCN tier
    (comm/peer.py).

    Host spans: ``st:init_state`` around ``st:init_state.seed`` (the
    template flattened in one program and put under the shard axis),
    ``st:init_state.broadcast`` (one program: the seed to every peer) and
    ``st:init_state.residual`` (one program: zeros). Each child ends by
    waiting for its array, which the next one or the caller's first step
    would wait for anyway, so a child's seconds are its own work, programs
    built and run, and not its successor's. Without a template the values
    are zeros too, dispatched in ``st:init_state`` itself (the program is
    built there) and waited for with the residual."""
    sh = state_sharding(mesh, config)
    peer_ax, shard_ax = sh.spec
    shape = (mesh.shape[peer_ax], spec.total)
    rows_per_shard(spec.total, mesh.shape[shard_ax])  # validate divisibility
    pod = pod_tier()
    with pod.span("init_state"):
        # Both arrays are built under their sharding, so each device only
        # ever holds its own (1, total / n_shard) block: broadcasting on the
        # default device first would stage the whole (n_peer, total) state
        # there.
        zeros = jax.jit(lambda: jnp.zeros(shape, jnp.float32), out_shardings=sh)
        if template is None:
            values = zeros()
        else:
            with pod.span("init_state.seed"):
                seed = jax.block_until_ready(jax.device_put(
                    _flatten(template, spec), NamedSharding(mesh, P(shard_ax))
                ))
            with pod.span("init_state.broadcast"):
                values = jax.block_until_ready(jax.jit(
                    lambda f: jnp.broadcast_to(f, shape), out_shardings=sh
                )(seed))
            del seed
        # last: seeding holds the template, its flat copy and the broadcast
        # at once, five tables with the residual beside them (16.25 GB of a
        # v5e's 16.91 for a 3.24 GB table; my chip run, PR 35)
        with pod.span("init_state.residual"):
            residual = jax.block_until_ready(zeros())
    return PeerSyncState(values, residual)


def read_peer(state: PeerSyncState, spec: TableSpec, peer: int):
    """Peer ``peer``'s current replica as the caller's pytree (reference
    copyToTensor)."""
    with pod_tier().span("read_peer"):
        return _unflatten(state.values[peer], spec)


def add_updates_raw(state: PeerSyncState, updates: jax.Array) -> PeerSyncState:
    """Each peer merges its own additive update (``updates[p]`` for peer p):
    replica and residual both receive it, so it is visible locally at once and
    queued for the group (reference addFromInternal, src/sharedtensor.c:
    334-344). Sanitized like ops.table.accumulate_table (quirk Q9 fix).

    Un-jitted so callers (train/async_sgd.py) can fuse it into a larger
    step; use :func:`add_updates` standalone."""
    with jax.named_scope("st.add_updates"):
        u = jnp.nan_to_num(
            updates.astype(jnp.float32), nan=0.0, posinf=3.0e38, neginf=-3.0e38
        )
        return PeerSyncState(
            jnp.clip(state.values + u, -3.0e38, 3.0e38),
            jnp.clip(state.residual + u, -3.0e38, 3.0e38),
        )


add_updates = jax.jit(add_updates_raw, donate_argnums=(0,))


@partial(jax.jit, donate_argnums=(0,))
def apply_external(state: PeerSyncState, delta: jax.Array) -> PeerSyncState:
    """Apply a delta that arrived from OUTSIDE the pod (the DCN/TCP peer
    tier) to every pod peer's replica — values only, residuals untouched.

    This is split-horizon at the pod boundary (reference sync_in never
    re-floods a frame back toward the link it came from,
    src/sharedtensor.c:124-127): every pod peer receives the external delta
    directly here, so queueing it into intra-pod residuals would deliver it
    twice. ``delta`` is flat [spec.total], broadcast over peers."""
    d = jnp.nan_to_num(
        delta.astype(jnp.float32), nan=0.0, posinf=3.0e38, neginf=-3.0e38
    )
    return PeerSyncState(
        jnp.clip(state.values + d[None, :], -3.0e38, 3.0e38), state.residual
    )


# --- the fused sync step ----------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _StepCtx:
    """Static layout shared by the sync-step builders: mesh axes and the
    table's leaves as each shard's rows see them (``ops.table.LeafRows``, a
    window a shard), which the scale reductions, the XLA passes and the
    kernels' scalar tables all come from. No per-row index vector exists."""

    peer_ax: str
    shard_ax: str
    n_peer: int
    leaves: LeafRows
    over: LeafRows  # what a scale is taken over: the leaves, or the table whole

    @property
    def rows_local(self) -> int:
        return self.leaves.rows

    def window(self):
        """This shard's window of ``leaves``. Call inside shard_map only
        (uses axis_index)."""
        if self.leaves.n_windows == 1:
            return None
        return jax.lax.axis_index(self.shard_ax)

    def live(self) -> jnp.ndarray:
        """bool[rows_local, 128]: this shard's live lanes, for the XLA
        passes. Call inside shard_map only."""
        return live_lanes(self.leaves.rowcount(self.window()))


def _make_ctx(
    mesh: Mesh, spec: TableSpec, per_leaf: bool, cfg: MeshConfig
) -> _StepCtx:
    rows_per_shard(spec.total, mesh.shape[cfg.shard_axis])  # validate divisibility
    leaves = LeafRows.of(spec, mesh.shape[cfg.shard_axis])
    return _StepCtx(
        peer_ax=cfg.peer_axis,
        shard_ax=cfg.shard_axis,
        n_peer=mesh.shape[cfg.peer_axis],
        leaves=leaves,
        # per_leaf=False: one global scale over the whole table (the
        # reference's exact behavior, src/sharedtensor.c:153-159)
        over=leaves if per_leaf else leaves.whole(),
    )


def _leaf_scales(
    ctx: _StepCtx, rows: jnp.ndarray, live: jnp.ndarray, policy: ScalePolicy
) -> jnp.ndarray:
    """Per-leaf scales from this shard's rows (ops.table.leaf_scales over
    this shard's leaf ranges), reduced over the shard axis: this is where the
    sharded replica pays one small collective — k floats — per frame."""
    window = ctx.window()
    return leaf_scales(
        rows,
        live,
        partial(ctx.over.reduce, window=window),
        partial(ctx.over.expand, window=window),
        jnp.asarray(np.asarray(ctx.over.ns, dtype=np.float32)),
        policy,
        ctx.shard_ax,
    )


def _codec_send(ctx: _StepCtx, policy: ScalePolicy, impl: str, residual):
    """Sender half of the pod sync, per shard block: per-leaf scales
    (cross-shard reduction) + sign-quantize/pack/error-feedback
    (ops.table.quantize_rows, on the resolved tier ``impl``, handed the
    scales per leaf as they are) + all-gather of the packed frames over the
    peer axis — the wire is 1 bit/element + k scales per peer over ICI. One
    source of truth for both the fused step (build_sync_step) and the
    overlap phases (build_sync_phases).

    Returns (new_residual [flat], words_all [n_peer, words_rows, 128] (each
    peer's packed words as ops.table.quantize_rows returns them: the flat
    word vector 128 words a row, dense in HBM; gathered in that shape, which
    ops.table.apply_rows takes as it arrives), scales_all [n_peer, k],
    scales_local [k])."""
    with jax.named_scope("st.codec_send"):
        r = residual.reshape(ctx.rows_local, LANES)
        scales = _leaf_scales(ctx, r, ctx.live(), policy)
        words, r2 = quantize_rows(scales, ctx.leaves, ctx.window(), residual, impl)
        with jax.named_scope("st.allgather"):
            words_all = jax.lax.all_gather(words, ctx.peer_ax)  # (n_peer, words_rows, 128)
            scales_all = jax.lax.all_gather(scales, ctx.peer_ax)  # (n_peer, k)
    return r2, words_all, scales_all, scales


def _codec_apply(ctx: _StepCtx, impl: str, values, words_all, scales_all):
    """Receiver half, per shard block: apply the sum of every OTHER peer's
    frame (split horizon = zero out OUR row of the per-frame scales; a
    zero-scale frame contributes exactly nothing) to the local replica, in
    one pass (ops.table.apply_rows, on the resolved tier ``impl``). Shared by
    build_sync_step and build_sync_phases."""
    with jax.named_scope("st.codec_apply"):
        me = jax.lax.axis_index(ctx.peer_ax)
        s_all = jnp.where((jnp.arange(ctx.n_peer) == me)[:, None], 0.0, scales_all)
        (v2,) = apply_rows(
            s_all, ctx.leaves, ctx.window(), words_all, (values,), impl
        )
        return v2


def build_sync_step(
    mesh: Mesh,
    spec: TableSpec,
    policy: ScalePolicy = ScalePolicy.POW2_RMS,
    per_leaf: bool = True,
    compressed: bool = True,
    config: MeshConfig | None = None,
    jit_compile: bool = True,
    impl: str = "auto",
):
    """Compile one fused pod sync step: ``state -> (state', scales)``.

    ``scales`` is f32[n_peer, num_leaves] — the per-frame step sizes each peer
    transmitted (0 rows = idle peers), the core observability quantity the
    reference lacks entirely (SURVEY.md §5.5).

    ``compressed=False`` builds the exact-allreduce arm instead (BASELINE
    config 4's comparison): every pending residual is delivered in full fp32
    precision and residuals drop to exactly zero.

    ``impl`` is the row codec's tier (ops.table.resolve_impl: "auto" is the
    Pallas kernels exactly where they compile, a TPU; "pallas"/"xla" pin one
    for parity tests).

    Host span ``st:build_sync_step``: the layout's tables and, the first
    time in a process, the import of Pallas; the program itself is built
    (``st:build.*``) when it is first called or lowered.
    """
    with pod_tier().span("build_sync_step"):
        cfg = config or MeshConfig()
        ctx = _make_ctx(mesh, spec, per_leaf, cfg)
        peer_ax, shard_ax = ctx.peer_ax, ctx.shard_ax
        impl = resolve_impl(impl)

        def _compressed_body(values, residual):
            """Compose the shared codec halves (same blocks as
            build_sync_phases — the compose-parity test pins the equivalence)."""
            r2, words_all, scales_all, scales = _codec_send(ctx, policy, impl, residual)
            v2 = _codec_apply(ctx, impl, values, words_all, scales_all)
            return v2, r2, scales

        def _exact(values, residual):
            r = residual.reshape(ctx.rows_local, LANES)
            live = ctx.live()
            # report the would-have-been scales so both arms expose the same
            # observability surface (the shard-axis reduction inside also lets
            # shard_map infer the scales output is shard-replicated)
            scales = _leaf_scales(ctx, r, live, policy)
            delta_others = jax.lax.psum(residual, peer_ax) - residual
            v2 = jnp.clip(values + delta_others, -SAT, SAT)
            v2 = jnp.where(live.reshape(-1), v2, 0.0)
            return v2, jnp.zeros_like(residual), scales

        body = _compressed_body if compressed else _exact

        def _step(values, residual):
            # local blocks: (1, spec.total // n_shard)
            v2, r2, scales = body(values[0], residual[0])
            return v2[None], r2[None], scales[None]

        spec_vr = P(peer_ax, shard_ax)
        sharded = shard_map(
            _step,
            mesh=mesh,
            in_specs=(spec_vr, spec_vr),
            out_specs=(spec_vr, spec_vr, P(peer_ax, None)),
            # pallas_call outputs carry no varying-mesh-axes annotation; disable
            # the vma checker for the kernel body (the XLA body keeps it)
            check_vma=not (compressed and impl == "pallas"),
        )

        def sync_step(state: PeerSyncState) -> Tuple[PeerSyncState, jax.Array]:
            v, r, scales = sharded(state.values, state.residual)
            return PeerSyncState(v, r), scales

        if jit_compile:
            return jax.jit(sync_step, donate_argnums=(0,))
        # Raw (traceable) form for embedding into a larger jitted step
        # (train/async_sgd.py fuses grads + add_updates + sync into one program).
        return sync_step


def build_sync_phases(
    mesh: Mesh,
    spec: TableSpec,
    policy: ScalePolicy = ScalePolicy.POW2_RMS,
    per_leaf: bool = True,
    config: MeshConfig | None = None,
    impl: str = "auto",
):
    """The sync step split into its two halves, for the OVERLAP training mode
    (train/async_sgd.py ``overlap=True``):

      ``send(residual) -> (residual', words_all, scales_all)`` — quantize the
      outgoing residual (error feedback applied) and all-gather the packed
      frames over the peer axis. Depends ONLY on the residual.

      ``apply_gathered(values, words_all, scales_all) -> values'`` — apply
      every OTHER peer's frame (split horizon) to the local replica.

    Running ``send`` at the top of a fused train step and ``apply_gathered``
    after the backward pass gives XLA's latency-hiding scheduler a window the
    full width of the grad computation to run the all-gather in — the
    collective rides ICI while the MXU does the backward pass. This realizes
    the reference's core property, compute never waits for sync
    (README.md:24 "fully asynchronous"; SURVEY.md §7.4 hard part 1), at the
    cost that the local update added AFTER ``send`` rides the NEXT frame
    (one-step-later delivery — indistinguishable under the reference's
    always-streaming semantics, where a frame carries whatever residual mass
    exists at frame time).

    Composing ``apply_gathered(values, *send(residual)[1:])`` immediately is
    bit-for-bit ``build_sync_step`` (tests pin this).

    Shapes: ``words_all`` u32[n_peer, n_shard * words_rows, 128] (a shard's
    packed words 128 a row, ops.packing.words_rows of its rows) sharded over
    the shard axis;
    ``scales_all`` f32[n_peer, num_leaves] replicated (row p = the scales
    peer p transmitted — the same observability surface as build_sync_step).
    """
    cfg = config or MeshConfig()
    ctx = _make_ctx(mesh, spec, per_leaf, cfg)
    impl = resolve_impl(impl)
    spec_vr = P(ctx.peer_ax, ctx.shard_ax)

    def _send(residual_blk):
        r2, words_all, scales_all, _ = _codec_send(ctx, policy, impl, residual_blk[0])
        return r2[None], words_all, scales_all

    # check_vma off: the gathered outputs ARE peer-replicated (all_gather
    # over the peer axis returns identical stacks everywhere) but the
    # varying-mesh-axes inference cannot see that through a collective's
    # output; correctness is pinned by the compose-parity test against the
    # fused (vma-checked) step instead.
    send = shard_map(
        _send,
        mesh=mesh,
        in_specs=(spec_vr,),
        out_specs=(spec_vr, P(None, ctx.shard_ax), P(None, None)),
        check_vma=False,
    )

    def _apply(values_blk, words_all, scales_all):
        v2 = _codec_apply(ctx, impl, values_blk[0], words_all, scales_all)
        return v2[None]

    apply_gathered = shard_map(
        _apply,
        mesh=mesh,
        in_specs=(spec_vr, P(None, ctx.shard_ax), P(None, None)),
        out_specs=spec_vr,
        check_vma=False,
    )
    return send, apply_gathered


def frame_ici_bytes(spec: TableSpec, n_peer: int, compressed: bool = True) -> int:
    """Bytes received per peer per sync step over ICI — the wire-cost model
    behind the >=10x-at-matched-error target (BASELINE.md). Compressed: 1
    bit/element + scales from each other peer; exact: fp32 psum moves ~2x the
    full buffer through each link for large rings."""
    if compressed:
        per_frame = spec.total // BITS_PER_WORD * 4 + spec.num_leaves * 4
        return (n_peer - 1) * per_frame
    return 2 * spec.total * 4
