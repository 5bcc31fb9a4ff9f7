"""Experimental pod-tier sync steps for the codec-lab methods.

The codec lab (ops/codec_lab.py, host trajectories; ops/codec_lab_jax.py,
jitted single-buffer twins) measured where the alternative compression
methods win. This module takes the measured-best 2-bit design — Sign2:
``±s`` / ``±3s``, magnitude bit at ``|r| > 2s`` — into the REAL pod sync
path: the same GSPMD shard_map step as the production
parallel/ici.build_sync_step (same per-leaf cross-shard scale reduction,
same all-gather-over-ICI shape, same split horizon and SAT clamps), with a
2-bit wire (two packed planes: sign bits + magnitude bits = 2 bits/element
per peer over ICI, vs the production step's 1).

Deliberately a SEPARATE builder, not a flag on the production one: the
1-bit step is the reference-parity capability and stays byte-stable; this
is the lab's device-tier test bed, sharing ici.py's internals so the only
delta is the quantizer (Pareto differences stay attributable — the same
discipline as the host lab). Promotion path if a workload earns it:
ops/table.py dispatch + a wire frame tag, exactly like the host lab
documents.

Measured on the 8-virtual-device test mesh (tests/test_ici_lab.py): on
gaussian residuals the sign2 step drains RMS faster per frame than the
production step at every frame count checked, matching the host lab's
0.79-vs-0.85 per-frame decay; on uniform residuals the magnitude bit idles
and both steps drain identically (exact zero in ~28 frames); and the
flagship char-rnn TRAINS through the 2-bit sync to statistically
comparable loss on the same pinned data stream (the training-level A/B,
mirroring the overlap A/B in tests/test_trainer.py).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..config import MeshConfig, ScalePolicy
from ..ops.codec import SAT
from ..ops.packing import LANES, pack_bits, unpack_bits
from ..ops.table import TableSpec
from .ici import PeerSyncState, _leaf_scales, _make_ctx


def build_sign2_sync_step(
    mesh: Mesh,
    spec: TableSpec,
    policy: ScalePolicy = ScalePolicy.POW2_RMS,
    per_leaf: bool = True,
    config: MeshConfig | None = None,
    jit_compile: bool = True,
):
    """Compile one fused 2-bit pod sync step: ``state -> (state', scales)``.

    Contract mirrors ici.build_sync_step (same state layout, same scales
    observability output); only the quantizer differs. XLA tier only — the
    fused Pallas row kernels are pinned to the production 1-bit layout, and
    the lab's job is semantics + convergence measurement, not peak HBM
    throughput.
    """
    cfg = config or MeshConfig()
    ctx = _make_ctx(mesh, spec, per_leaf, cfg)
    peer_ax = ctx.peer_ax

    def _body(values, residual):
        r = residual.reshape(ctx.rows_local, LANES)
        live = ctx.live()
        scales = _leaf_scales(ctx, r, live, policy)
        s_row = ctx.over.expand(scales, ctx.window())[:, None]  # (rows, 1)
        # 2-bit sign-magnitude quantize + error feedback (the codec-lab
        # Sign2 rule; sign convention matches the production codec: r <= 0
        # sends negative, quirk Q3's zero-negative kept)
        neg = r <= 0.0
        big = jnp.abs(r) > 2.0 * s_row
        mag = jnp.where(big, 3.0 * s_row, s_row)
        sent = jnp.where(neg, -mag, mag)
        r2 = jnp.where(
            live & (s_row > 0), r - sent, jnp.where(live, r, 0.0)
        ).reshape(-1)
        sign_words = pack_bits(jnp.logical_and(live, neg).reshape(-1))
        mag_words = pack_bits(jnp.logical_and(live, big).reshape(-1))
        # 2 bits/element over ICI: both planes ride one all-gather
        words = jnp.stack([sign_words, mag_words])  # (2, W_local)
        words_all = jax.lax.all_gather(words, peer_ax)  # (n_peer, 2, W)
        scales_all = jax.lax.all_gather(scales, peer_ax)  # (n_peer, k)

        # receiver half: sum of every OTHER peer's 2-bit frame, one pass
        me = jax.lax.axis_index(peer_ax)
        s_all = jnp.where((jnp.arange(ctx.n_peer) == me)[:, None], 0.0, scales_all)
        s_all = ctx.over.expand(s_all, ctx.window())  # (n_peer, rows_local)
        neg_all = (
            unpack_bits(words_all[:, 0])
            .reshape(ctx.n_peer, ctx.rows_local, LANES)
            .astype(jnp.float32)
        )
        big_all = (
            unpack_bits(words_all[:, 1])
            .reshape(ctx.n_peer, ctx.rows_local, LANES)
            .astype(jnp.float32)
        )
        delta = jnp.sum(
            s_all[:, :, None] * (1.0 - 2.0 * neg_all) * (1.0 + 2.0 * big_all),
            axis=0,
        )
        v = values.reshape(ctx.rows_local, LANES)
        v2 = jnp.where(live, jnp.clip(v + delta, -SAT, SAT), 0.0)
        return v2.reshape(-1), r2, scales

    def _step(values, residual):
        v2, r2, scales = _body(values[0], residual[0])
        return v2[None], r2[None], scales[None]

    spec_vr = P(peer_ax, ctx.shard_ax)
    sharded = shard_map(
        _step,
        mesh=mesh,
        in_specs=(spec_vr, spec_vr),
        out_specs=(spec_vr, spec_vr, P(peer_ax, None)),
    )

    def sync_step(state: PeerSyncState) -> Tuple[PeerSyncState, jax.Array]:
        v, r, scales = sharded(state.values, state.residual)
        return PeerSyncState(v, r), scales

    if jit_compile:
        # NO buffer donation, deliberately (production donates): with many
        # live executables in one process (a full pytest run), donated
        # shard_map buffers on the virtual CPU mesh intermittently abort
        # the XLA CPU runtime (SIGABRT reproduced at suite position #132,
        # gone without donation). The lab step measures semantics, not
        # allocator throughput — correctness over the copy.
        return jax.jit(sync_step)
    return sync_step
