"""Table sync: the codec generalized to a pytree ("table") of tensors with an
independent scale per leaf.

The reference syncs exactly one flat float buffer with ONE global scale; its
README's top wishlist item is "Allow a table of tensors to be synced" because
mixed-magnitude parameter sets degrade badly under a single scale (reference
README.md:41; measured in BASELINE.md: 1000:1 mix leaves the small half at 24%
error after 48 frames). This module provides that capability natively:

- A pytree is flattened into ONE padded flat buffer, each leaf padded to a
  whole (8,128)-tile multiple so leaf boundaries are row-aligned.
- Quantization computes an independent power-of-2 RMS scale per leaf
  (dense reductions over the leaves' static row ranges, :func:`leaf_reduce`),
  then runs the same sign/error-feedback rule with a per-row scale — still a
  single pass over HBM, one frame on the wire.
- The wire frame carries k scales (one per leaf) + the packed bitmask.

With a single-leaf table this is byte-for-byte the reference codec.
"""

from __future__ import annotations

import dataclasses
import itertools
from functools import partial
from typing import Any, Iterator, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import ScalePolicy
from .codec import SAT, pad_flat, pow2_floor
from .packing import LANES, TILE, pack_bits, padded_len, unpack_bits


# (start, stop) rows of each leaf, in leaf order
LeafRanges = tuple[tuple[int, int], ...]


class TableFrame(NamedTuple):
    """One codec frame for a table: per-leaf scales + packed sign bits."""

    scales: jnp.ndarray  # f32[num_leaves]
    words: jnp.ndarray  # uint32[total_padded // 32]


@dataclasses.dataclass(frozen=True)
class TableSpec:
    """Static layout of a pytree flattened into one padded flat buffer.

    Hashable (all-tuple fields) so it can be a jit static argument. Leaf i
    occupies flat rows [row_offsets[i], row_offsets[i] + padded[i]//128) with
    ns[i] live elements.
    """

    treedef: Any
    shapes: tuple[tuple[int, ...], ...]
    ns: tuple[int, ...]  # true element count per leaf
    padded: tuple[int, ...]  # padded length per leaf (tile multiple)

    @property
    def num_leaves(self) -> int:
        return len(self.ns)

    @property
    def total(self) -> int:
        return sum(self.padded)

    @property
    def total_n(self) -> int:
        return sum(self.ns)

    def layout_digest(self) -> bytes:
        """16-byte digest identifying the full table layout (tree structure,
        leaf shapes, padding). Two specs with equal digests decode each
        other's frames leaf-for-leaf; (num_leaves, total_n) alone cannot
        distinguish e.g. {w:(8,128), b:(128,)} from {w:(128,), b:(8,128)}."""
        import hashlib

        desc = repr((str(self.treedef), self.shapes, self.ns, self.padded))
        return hashlib.sha256(desc.encode()).digest()[:16]

    @property
    def leaf_rows(self) -> LeafRanges:
        """Every leaf's ``(start, stop)`` 128-lane rows: contiguous, in leaf
        order, tiling ``[0, total // 128)``. The static form of the row ->
        leaf map; :func:`leaf_reduce` and :func:`leaf_expand` run over it."""
        stops = np.cumsum([p // LANES for p in self.padded]).tolist()
        return tuple(zip([0] + stops[:-1], stops))

    def row_leaf(self) -> np.ndarray:
        """int32[rows]: leaf index owning each 128-lane row (``leaf_rows`` as
        a vector, for host-side data generation; no device step indexes by
        it)."""
        return np.repeat(
            np.arange(self.num_leaves, dtype=np.int32),
            [p // LANES for p in self.padded],
        )

    def live_rowcount(self) -> np.ndarray:
        """int32[rows]: number of live lanes in each row (0..128)."""
        counts = []
        for n, p in zip(self.ns, self.padded):
            rows = p // LANES
            full, rem = divmod(n, LANES)
            c = np.zeros(rows, dtype=np.int32)
            c[:full] = LANES
            if rem:
                c[full] = rem
            counts.append(c)
        return np.concatenate(counts)


def clip_ranges(ranges: LeafRanges, lo: int, hi: int) -> LeafRanges:
    """``ranges`` cut to rows ``[lo, hi)`` and shifted to start at 0: what a
    shard holding those rows sees of each leaf. A leaf outside the window
    keeps its position as an empty range."""
    return tuple(
        (min(max(a, lo), hi) - lo, min(max(b, lo), hi) - lo) for a, b in ranges
    )


def _range_runs(ranges: LeafRanges) -> Iterator[tuple[int, int, int, int]]:
    """Runs of consecutive equally sized leaves, as ``(first_leaf, n_leaves,
    start_row, rows_each)``. One run is one reshape to ``(n_leaves,
    rows_each)``, so the op count follows the runs (7 for an OLMoE layer's
    201 leaves), not the leaves. Empty leaves form runs with rows_each 0."""
    first = 0
    for each, group in itertools.groupby(ranges, key=lambda r: r[1] - r[0]):
        n = len(list(group))
        yield first, n, ranges[first][0], each
        first += n


def leaf_reduce(x: jnp.ndarray, ranges: LeafRanges, op: str) -> jnp.ndarray:
    """Per-row partials ``x[rows]`` -> ``[len(ranges)]``: each leaf's ``op``
    ("max" or "sum") over its own rows, by static slice + reshape + dense
    reduce per run of equal leaves. What ``jax.ops.segment_max/segment_sum``
    compute over ``row_leaf``, without the index operand (a serial scatter on
    the TPU: 28.7 ms for 3.28 M rows, PERF.md section 6). An empty leaf
    yields the identity (-inf / 0)."""
    ident, red = (-jnp.inf, jnp.max) if op == "max" else (0.0, jnp.sum)
    parts = []
    for _, n, start, each in _range_runs(ranges):
        if each == 0:
            parts.append(jnp.full((n,), ident, x.dtype))
        else:
            seg = jax.lax.slice(x, (start,), (start + n * each,))
            parts.append(red(seg.reshape(n, each), axis=1))
    return jnp.concatenate(parts) if len(parts) > 1 else parts[0]


def leaf_expand(v: jnp.ndarray, ranges: LeafRanges) -> jnp.ndarray:
    """Per-leaf ``v[..., len(ranges)]`` -> per-row ``[..., rows]``: leaf i's
    value over its rows, by broadcast + reshape per run of equal leaves (the
    gather ``v[..., row_leaf]`` without the index operand). ``ranges`` must
    tile ``[0, rows)`` in order, empty leaves aside."""
    lead = v.shape[:-1]
    parts = []
    for first, n, _, each in _range_runs(ranges):
        if each:
            seg = jax.lax.slice_in_dim(v, first, first + n, axis=-1)
            parts.append(
                jnp.broadcast_to(seg[..., None], (*lead, n, each)).reshape(
                    *lead, n * each
                )
            )
    return jnp.concatenate(parts, axis=-1) if len(parts) > 1 else parts[0]


def make_spec(tree: Any) -> TableSpec:
    """Build the static layout for a pytree of arrays."""
    leaves, treedef = jax.tree.flatten(tree)
    shapes = tuple(tuple(np.shape(l)) for l in leaves)
    ns = tuple(int(np.prod(s)) if s else 1 for s in shapes)
    padded = tuple(padded_len(n, TILE) for n in ns)
    return TableSpec(treedef, shapes, ns, padded)


def flatten(tree: Any, spec: TableSpec) -> jnp.ndarray:
    """Pytree -> single padded flat float32 buffer (padding exactly 0)."""
    leaves, treedef = jax.tree.flatten(tree)
    if treedef != spec.treedef:
        # the reference raises THError("Not the right size!") on mismatch
        # (src/sharedtensor.c:335); a structural mismatch here would silently
        # merge deltas into the wrong leaves and flood the corruption to
        # every replica.
        raise ValueError(
            f"tree structure {treedef} does not match spec {spec.treedef}"
        )
    with jax.named_scope("st.flatten"):
        parts = []
        for i, (leaf, n, p) in enumerate(zip(leaves, spec.ns, spec.padded)):
            flat = jnp.ravel(jnp.asarray(leaf)).astype(jnp.float32)
            if flat.shape[0] != n:
                raise ValueError(
                    f"leaf {i} has {flat.shape[0]} elements, spec expects {n}"
                )
            parts.append(pad_flat(flat, p))
        return jnp.concatenate(parts) if len(parts) > 1 else parts[0]


def unflatten(flat: jnp.ndarray, spec: TableSpec) -> Any:
    """Inverse of :func:`flatten`."""
    with jax.named_scope("st.unflatten"):
        leaves = []
        off = 0
        for shape, n, p in zip(spec.shapes, spec.ns, spec.padded):
            leaves.append(flat[off : off + n].reshape(shape))
            off += p
        return jax.tree.unflatten(spec.treedef, leaves)


def _live_mask_flat(spec: TableSpec) -> np.ndarray:
    """bool[total]: True for live (non-padding) elements."""
    rows = spec.live_rowcount()
    lane = np.arange(LANES, dtype=np.int32)
    return (lane[None, :] < rows[:, None]).reshape(-1)


def compute_scales(
    residual: jnp.ndarray,
    spec: TableSpec,
    policy: ScalePolicy = ScalePolicy.POW2_RMS,
) -> jnp.ndarray:
    """Per-leaf step sizes (overflow-safe per-leaf RMS; see
    codec.compute_scale for the scalar version this generalizes)."""
    ranges = spec.leaf_rows
    rows = residual.reshape(-1, LANES)
    amax_row = jnp.max(jnp.abs(rows), axis=1)
    amax = jnp.maximum(leaf_reduce(amax_row, ranges, "max"), 0.0)  # empty leaf: -inf
    denom = jnp.where(amax > 0, amax, 1.0)
    norm = rows / leaf_expand(denom, ranges)[:, None]
    ns = jnp.asarray(np.asarray(spec.ns, dtype=np.float32))
    moment = jnp.abs(norm) if policy == ScalePolicy.ABS_MEAN else norm * norm
    part = jnp.sum(moment, axis=1, dtype=jnp.float32)
    mean = leaf_reduce(part, ranges, "sum") / ns
    if policy == ScalePolicy.ABS_MEAN:
        scales = amax * mean
    else:
        rms = amax * jnp.sqrt(mean)
        scales = pow2_floor(rms) if policy == ScalePolicy.POW2_RMS else rms
    rms_pos = amax > 0
    return jnp.where(rms_pos & jnp.isfinite(scales), scales, 0.0)


def _table_scales(
    residual: jnp.ndarray,
    spec: TableSpec,
    policy: ScalePolicy,
    per_leaf: bool,
) -> jnp.ndarray:
    """Per-leaf scales; ``per_leaf=False`` computes ONE scale over the whole
    table (the reference's behavior, src/sharedtensor.c:153-159 — wire-compat
    interop with C peers requires it) replicated to every leaf so the apply
    path is uniform."""
    if per_leaf:
        return compute_scales(residual, spec, policy)
    one_spec = dataclasses.replace(
        spec,
        shapes=((spec.total_n,),),
        ns=(spec.total_n,),
        padded=(spec.total,),
    )
    # NOTE: valid because padding lanes are 0 by invariant; the single-
    # leaf view only changes which elements each scale aggregates over.
    s = compute_scales(residual, one_spec, policy)[0]
    return jnp.full((spec.num_leaves,), s, jnp.float32)


def _resolve_impl(impl: str) -> str:
    """'auto' -> the Pallas kernels exactly when they would compile (TPU);
    pure XLA elsewhere (CPU tests/peers). See codec_pallas.use_pallas."""
    if impl != "auto":
        return impl
    from . import codec_pallas

    return "pallas" if codec_pallas.use_pallas() else "xla"


@partial(jax.jit, static_argnames=("spec", "policy", "per_leaf", "impl"))
def _quantize_table(
    residual: jnp.ndarray,
    spec: TableSpec,
    policy: ScalePolicy,
    per_leaf: bool,
    impl: str,
) -> tuple[TableFrame, jnp.ndarray]:
    scales = _table_scales(residual, spec, policy, per_leaf)
    s_row = leaf_expand(scales, spec.leaf_rows)
    if impl == "pallas":
        from . import codec_pallas

        words, new_flat = codec_pallas.quantize_rows(
            s_row, jnp.asarray(spec.live_rowcount()), residual
        )
        return TableFrame(scales, words), new_flat
    rows = residual.reshape(-1, LANES)
    s_row = s_row[:, None]  # (rows, 1)
    live = jnp.asarray(_live_mask_flat(spec)).reshape(-1, LANES)
    neg = rows <= 0
    bits = jnp.where(live, neg, False)
    sent = jnp.where(neg, -s_row, s_row)
    new_rows = jnp.where(live & (s_row > 0), rows - sent, jnp.where(live, rows, 0.0))
    return (
        TableFrame(scales, pack_bits(bits.reshape(-1))),
        new_rows.reshape(-1),
    )


def quantize_table(
    residual: jnp.ndarray,
    spec: TableSpec,
    policy: ScalePolicy = ScalePolicy.POW2_RMS,
    per_leaf: bool = True,
    impl: str = "auto",
) -> tuple[TableFrame, jnp.ndarray]:
    """Sender step over a table: one pass, per-leaf scales.

    Per-leaf semantics are identical to codec.quantize: bit set iff r <= 0,
    residual moves by -+scale of its own leaf, leaves with scale 0 idle.

    On TPU the sign/pack/error-feedback pass runs as the fused Pallas kernel
    (codec_pallas.quantize_rows) — the production tier; the XLA path is the
    golden reference and the CPU fallback. ``impl`` pins either ("xla" /
    "pallas") for parity tests."""
    return _quantize_table(residual, spec, policy, per_leaf, _resolve_impl(impl))


@partial(jax.jit, static_argnames=("spec", "k", "policy", "per_leaf", "impl"))
def _quantize_table_burst(
    residual: jnp.ndarray,
    spec: TableSpec,
    k: int,
    policy: ScalePolicy,
    per_leaf: bool,
    impl: str,
) -> tuple[TableFrame, jnp.ndarray]:
    def body(r, _):
        frame, r2 = _quantize_table(r, spec, policy, per_leaf, impl)
        return r2, (frame.scales, frame.words)

    new_r, (scales, words) = jax.lax.scan(body, residual, None, length=k)
    return TableFrame(scales, words), new_r


def quantize_table_burst(
    residual: jnp.ndarray,
    spec: TableSpec,
    k: int,
    policy: ScalePolicy = ScalePolicy.POW2_RMS,
    per_leaf: bool = True,
    impl: str = "auto",
) -> tuple[TableFrame, jnp.ndarray]:
    """K successive residual halvings in ONE device dispatch (lax.scan of
    the sender step): returns stacked (scales f32[K,L], words u32[K,W]) and
    the final residual. The point is the peer tier's device BURST path —
    one dispatch + ONE device->host fetch carries K frames, amortizing the
    device-link round trip exactly as the host burst amortizes per-message
    engine cost. Once the residual quantizes to all-zero scales every later
    frame in the scan is an exact no-op (scale 0 idles), so the host side
    trims the zero tail after the fetch."""
    return _quantize_table_burst(
        residual, spec, int(k), policy, per_leaf, _resolve_impl(impl)
    )


def _batch_layout(frames: TableFrame, spec: TableSpec):
    """(scales [K,L], words [K,W]) -> the row-major layout the Pallas batch
    kernel consumes: s_rows f32[rows, K], words2d u32[rows, K*4] (frame k's
    words for row r at [r, 4k:4k+4])."""
    k = frames.scales.shape[0]
    rows = spec.total // LANES
    s_rows = leaf_expand(frames.scales, spec.leaf_rows).T  # (rows, K)
    words2d = (
        frames.words.reshape(k, rows, LANES // 32)
        .transpose(1, 0, 2)
        .reshape(rows, k * (LANES // 32))
    )
    return s_rows, words2d


@partial(jax.jit, static_argnames=("spec", "impl"))
def _apply_table_many(
    arrays: tuple[jnp.ndarray, ...], frame: TableFrame, spec: TableSpec, impl: str
) -> tuple[jnp.ndarray, ...]:
    s_row = leaf_expand(frame.scales, spec.leaf_rows)[:, None]  # (rows, 1)
    if impl == "pallas":
        from . import codec_pallas

        rows = spec.total // LANES
        return codec_pallas.apply_rows_batch(
            s_row,
            jnp.asarray(spec.live_rowcount()),
            frame.words.reshape(rows, LANES // 32),
            arrays,
        )
    bits = unpack_bits(frame.words).reshape(-1, LANES)
    live = jnp.asarray(_live_mask_flat(spec)).reshape(-1, LANES)
    delta = jnp.where(live, s_row * (1.0 - 2.0 * bits.astype(jnp.float32)), 0.0)
    flat_delta = delta.reshape(-1)
    return tuple(
        jnp.where(live.reshape(-1), jnp.clip(a + flat_delta, -SAT, SAT), 0.0)
        for a in arrays
    )


def apply_table_many(
    arrays: tuple[jnp.ndarray, ...],
    frame: TableFrame,
    spec: TableSpec,
    impl: str = "auto",
) -> tuple[jnp.ndarray, ...]:
    """Receiver step over a table applied to several arrays (replica + other
    links' residuals — the flood), one fused pass (Pallas on TPU)."""
    return _apply_table_many(arrays, frame, spec, _resolve_impl(impl))


def apply_table(values: jnp.ndarray, frame: TableFrame, spec: TableSpec) -> jnp.ndarray:
    return apply_table_many((values,), frame, spec)[0]


@partial(jax.jit, static_argnames=("spec", "impl"))
def _apply_table_batch(
    arrays: tuple[jnp.ndarray, ...], frames: TableFrame, spec: TableSpec, impl: str
) -> tuple[jnp.ndarray, ...]:
    if impl == "pallas":
        from . import codec_pallas

        s_rows, words2d = _batch_layout(frames, spec)
        return codec_pallas.apply_rows_batch(
            s_rows, jnp.asarray(spec.live_rowcount()), words2d, arrays
        )
    k = frames.scales.shape[0]
    bits = unpack_bits(frames.words.reshape(-1)).reshape(k, -1, LANES)
    s_row = leaf_expand(frames.scales, spec.leaf_rows)[:, :, None]  # [K, rows, 1]
    live = jnp.asarray(_live_mask_flat(spec)).reshape(-1, LANES)
    delta = jnp.sum(s_row * (1.0 - 2.0 * bits.astype(jnp.float32)), axis=0)
    flat_delta = jnp.where(live, delta, 0.0).reshape(-1)
    live_flat = live.reshape(-1)
    return tuple(
        jnp.where(live_flat, jnp.clip(a + flat_delta, -SAT, SAT), 0.0)
        for a in arrays
    )


def apply_table_batch(
    arrays: tuple[jnp.ndarray, ...],
    frames: TableFrame,
    spec: TableSpec,
    impl: str = "auto",
) -> tuple[jnp.ndarray, ...]:
    """Apply a STACK of K frames (scales f32[K, L], words u32[K, W]) in one
    dispatch: the summed delta of all K frames lands in one pass.

    Equivalent to applying the frames sequentially — codec deltas are pure
    adds, so they commute — but one device round-trip instead of K. This is
    what keeps the receive path ahead of a fast sender: per-frame dispatch
    overhead on a busy device was measured to back the RX queue up by
    hundreds of frames (train/hierarchical.py's two-pod run). Zero-scale
    padding frames contribute exactly nothing, so callers can pad a partial
    batch up to a bucketed K to bound jit specializations.

    On TPU the unpack/sum/apply runs as ONE fused Pallas pass
    (codec_pallas.apply_rows_batch) instead of K XLA unpack passes."""
    return _apply_table_batch(arrays, frames, spec, _resolve_impl(impl))


@partial(jax.jit, static_argnames=("spec",))
def accumulate_table(
    arrays: tuple[jnp.ndarray, ...], update: jnp.ndarray, spec: TableSpec
) -> tuple[jnp.ndarray, ...]:
    """values += u and each link residual += u, sanitized (see
    codec.accumulate)."""
    live = jnp.asarray(_live_mask_flat(spec))
    u = jnp.where(live, update, 0.0)
    u = jnp.nan_to_num(u, nan=0.0, posinf=3.0e38, neginf=-3.0e38)
    return tuple(jnp.clip(a + u, -3.0e38, 3.0e38) for a in arrays)
