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
import functools
import itertools
from functools import partial
from typing import Any, Iterator, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import ScalePolicy
from .codec import SAT, pad_flat, pow2_floor
from .packing import (
    LANES,
    TILE,
    dense_words,
    flat_words,
    pack_bits,
    padded_len,
    unpack_bits,
)


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
        return _live_rowcount(self.leaf_rows, self.ns)


def _live_rowcount(ranges: LeafRanges, ns: tuple[int, ...]) -> np.ndarray:
    """int32[rows]: live lanes of each row; row r of a leaf that starts at
    row ``a`` and holds ``n`` elements has ``clip(n - 128 (r - a), 0, 128)``."""
    each = [b - a for a, b in ranges]
    start = np.repeat([a for a, _ in ranges], each)
    n = np.repeat(np.asarray(ns, np.int64), each)
    return np.clip(n - LANES * (np.arange(len(n)) - start), 0, LANES).astype(np.int32)


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


@dataclasses.dataclass(frozen=True)
class LeafRows:
    """Static: where a table's leaves lie in the rows of its flat buffer, as
    each of ``n_windows`` equal row windows sees them (a shard of the pod
    tier holds one window; a whole table is one). Whatever a codec pass
    needs per row of what is known per leaf comes from here: the XLA passes
    take :meth:`reduce`, :meth:`expand` and :meth:`rowcount`, the kernels
    :meth:`tables`. ``window`` is the index of the window at hand, a traced
    scalar (``axis_index``) with several windows and ``None`` with one; no
    per-row index vector exists."""

    ranges: LeafRanges  # every leaf's (start, stop) rows in the whole buffer
    ns: tuple[int, ...]  # every leaf's live element count
    rows: int  # rows of one window
    n_windows: int = 1

    @classmethod
    def of(cls, spec: TableSpec, n_windows: int = 1) -> "LeafRows":
        rows, rem = divmod(spec.total // LANES, n_windows)
        if rem or rows * LANES >= 2**31:
            raise ValueError(
                f"{spec.total // LANES} rows do not cut into {n_windows} "
                f"windows of whole rows with a 32-bit element index"
            )
        return cls(spec.leaf_rows, spec.ns, rows, n_windows)

    def whole(self) -> "LeafRows":
        """The same rows as ONE leaf, for the scale reductions of a single
        global scale (:meth:`reduce`, :meth:`expand`, ``ns``). Not for live
        lanes: the leaves' padding lies inside it."""
        return LeafRows(
            ((0, self.rows * self.n_windows),), (sum(self.ns),), self.rows, self.n_windows
        )

    @functools.cached_property
    def window_ranges(self) -> tuple[LeafRanges, ...]:
        """Per window, the leaves' ranges clipped to it (window-local)."""
        return tuple(
            clip_ranges(self.ranges, w * self.rows, (w + 1) * self.rows)
            for w in range(self.n_windows)
        )

    def _pick(self, table: np.ndarray, window) -> jnp.ndarray:
        """``table[window]`` of a static per-window table."""
        if window is None:
            return jnp.asarray(table[0])
        return jax.lax.dynamic_index_in_dim(jnp.asarray(table), window, keepdims=False)

    def _on_window(self, fn, x, window):
        """``fn(x, ranges)`` with the window's leaf ranges: static per
        window, so with several it is one ``lax.switch`` over a branch a
        window (XLA passes only; the kernels take :meth:`tables`)."""
        if window is None:
            return fn(x, self.window_ranges[0])
        return jax.lax.switch(
            window, [partial(fn, ranges=r) for r in self.window_ranges], x
        )

    def reduce(self, x: jnp.ndarray, op: str, window=None) -> jnp.ndarray:
        """The window's per-row partials ``[rows]`` -> ``[k]`` ("max" or
        "sum"); leaves outside the window read the identity."""
        return self._on_window(partial(leaf_reduce, op=op), x, window)

    def expand(self, v: jnp.ndarray, window=None) -> jnp.ndarray:
        """Per-leaf ``[..., k]`` -> the window's rows ``[..., rows]``."""
        return self._on_window(leaf_expand, v, window)

    def rowcount(self, window=None) -> jnp.ndarray:
        """i32[rows]: live lanes (0..128) of each row of the window."""
        return self._pick(self._rowcounts, window)

    @functools.cached_property
    def _rowcounts(self) -> np.ndarray:
        return _live_rowcount(self.ranges, self.ns).reshape(self.n_windows, self.rows)

    @functools.cached_property
    def _block_tables(self) -> dict[int, tuple]:
        """``block`` -> :meth:`_cut_tables` of it: cut once a block size for
        the life of this object (a built sync step's), whichever kernel or
        trace asks."""
        return {}

    def _cut_tables(self, block: int) -> tuple:
        """(leaves_max, lo, end, first, last) of :meth:`tables`, for every
        window: static ``[n_windows, ...]`` int32 tables."""
        starts, stops = (np.asarray(x, np.int64) for x in zip(*self.ranges))
        edges = np.arange(0, self.rows, block)
        lo, end, first, last = [], [], [], []
        for w in range(self.n_windows):
            a, b = starts - w * self.rows, stops - w * self.rows
            ca, cb = a.clip(0, self.rows), b.clip(0, self.rows)
            lo.append(ca * LANES)
            end.append((a * LANES + self.ns).clip(0, self.rows * LANES))
            first.append(np.searchsorted(cb, edges, side="right"))
            last.append(
                np.searchsorted(ca, np.minimum(edges + block, self.rows), side="left") - 1
            )
        leaves_max = int((np.asarray(last) - np.asarray(first)).max()) + 1
        return leaves_max, *(np.asarray(t, np.int32) for t in (lo, end, first, last))

    def tables(self, block: int, window=None):
        """The window's :class:`~.codec_pallas.LeafTables` for kernels that
        step through it ``block`` rows at a time: each leaf's first element
        and live end, flat within the window and clipped to it, and per block
        the first and the last leaf it meets; k + k + 2 x blocks scalars,
        picked from static ``[n_windows, ...]`` tables by ``window``."""
        from .codec_pallas import LeafTables

        if block not in self._block_tables:
            self._block_tables[block] = self._cut_tables(block)
        leaves_max, *tabs = self._block_tables[block]
        return LeafTables(block, leaves_max, *(self._pick(t, window) for t in tabs))

    def one_a_leaf(self, scales: jnp.ndarray) -> jnp.ndarray:
        """``scales`` [..., k] as they are, or a single global scale [..., 1]
        (taken over :meth:`whole`) repeated to one a leaf."""
        return jnp.broadcast_to(scales, (*scales.shape[:-1], len(self.ns)))


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


def resolve_impl(impl: str) -> str:
    """'auto' -> the Pallas kernels exactly when they would compile (TPU);
    pure XLA elsewhere (CPU tests/peers). See codec_pallas.use_pallas."""
    if impl != "auto":
        return impl
    from . import codec_pallas

    return "pallas" if codec_pallas.use_pallas() else "xla"


# --- the row codec ----------------------------------------------------------
#
# One 1-bit codec pass at row granularity, written once: per-leaf scales, the
# sender's quantize pass and the receiver's K-frame apply pass, each with its
# Pallas call and its XLA twin in one body. Callers (the table functions
# below, the pod step of parallel/ici.py) hand over what is per leaf as it
# is, ``scales`` f32[k] or f32[K, k], with the static :class:`LeafRows` and
# the window at hand. What is per row is built here and nowhere else: the
# XLA twins expand scales and live lanes inside their own bodies
# (``LeafRows.expand`` / ``rowcount``), and the kernels of
# ops/codec_pallas.py get ``LeafRows.tables`` in scalar memory and derive a
# block's per-row scale and live lanes themselves, so no per-row operand is
# built, stored or streamed for them. The packed words pass between the two
# passes (and through an all-gather) 128 words a row, ``u32[words_rows,
# 128]`` (ops/packing.py ``dense_words``: the wire's flat word vector,
# bitcast): the sender pass writes that array, the receiver pass takes K of
# them stacked, and nothing relays them out in between. ``impl`` is a
# resolved tier ("pallas" or "xla", :func:`resolve_impl`).


def live_lanes(rowcount: jnp.ndarray) -> jnp.ndarray:
    """bool[rows, 128]: True for live (non-padding) lanes."""
    rows = rowcount.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1)
    return lane < jax.lax.broadcast_in_dim(rowcount, (rows, LANES), (0,))


def leaf_scales(
    rows: jnp.ndarray,
    live: jnp.ndarray,
    reduce,
    expand,
    ns: jnp.ndarray,
    policy: ScalePolicy,
    shard_axis: str | None = None,
) -> jnp.ndarray:
    """Per-leaf step sizes f32[k] of ``rows`` f32[rows, 128]: the
    overflow-safe normalized RMS (codec.compute_scale is the scalar version
    this generalizes). Two dense row passes, their per-row partials reduced
    per leaf by ``reduce(x[rows], "max" | "sum") -> [k]``, the leaf maximum
    brought back to rows by ``expand([k]) -> [rows]``; ``ns`` f32[k] is each
    leaf's live element count. With ``shard_axis`` the rows are one shard's
    and both reductions cross that mesh axis (k floats a frame)."""
    with jax.named_scope("st.leaf_scales"):
        amax_row = jnp.max(jnp.where(live, jnp.abs(rows), 0.0), axis=1)
        # a leaf with no row here (empty, or on another shard) reads -inf
        amax = jnp.maximum(reduce(amax_row, "max"), 0.0)
        if shard_axis is not None:
            amax = jax.lax.pmax(amax, shard_axis)
        denom = jnp.where(amax > 0, amax, 1.0)
        norm = jnp.where(live, rows / expand(denom)[:, None], 0.0)
        moment = jnp.abs(norm) if policy == ScalePolicy.ABS_MEAN else norm * norm
        total = reduce(jnp.sum(moment, axis=1, dtype=jnp.float32), "sum")
        if shard_axis is not None:
            total = jax.lax.psum(total, shard_axis)
        mean = total / ns
        if policy == ScalePolicy.ABS_MEAN:
            scales = amax * mean
        else:
            rms = amax * jnp.sqrt(mean)
            scales = pow2_floor(rms) if policy == ScalePolicy.POW2_RMS else rms
        return jnp.where((amax > 0) & jnp.isfinite(scales), scales, 0.0)


def quantize_rows(
    scales: jnp.ndarray, leaves: LeafRows, window, residual: jnp.ndarray, impl: str
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Sender pass: sign-quantize + LSB-first pack + error feedback with a
    scale per leaf. ``scales`` f32[k] (or the one global scale, f32[1]),
    ``residual`` f32[rows*128] flat (the rows of ``window``) -> (words
    u32[words_rows(rows), 128], residual'): the flat word vector 128 words a
    row (``packing.flat_words`` gives the wire's ``u32[rows*4]`` back). Bit
    set iff r <= 0; a leaf at scale 0 idles; padding lanes are forced to 0,
    pad words too."""
    with jax.named_scope("st.quantize"):
        scales = leaves.one_a_leaf(scales)
        if impl == "pallas":
            from . import codec_pallas

            block = codec_pallas.quantize_block_rows(leaves.rows)
            return codec_pallas.quantize_rows(
                scales, leaves.tables(block, window), residual
            )
        r = residual.reshape(-1, LANES)
        live = live_lanes(leaves.rowcount(window))
        s = leaves.expand(scales, window)[:, None]  # (rows, 1)
        neg = r <= 0.0
        sent = jnp.where(neg, -s, s)
        r2 = jnp.where(live & (s > 0), r - sent, jnp.where(live, r, 0.0))
        words = pack_bits(jnp.logical_and(live, neg).reshape(-1))
        return dense_words(words, leaves.rows), r2.reshape(-1)


def apply_rows(
    scales: jnp.ndarray,
    leaves: LeafRows,
    window,
    words: jnp.ndarray,
    arrays: tuple[jnp.ndarray, ...],
    impl: str,
) -> tuple[jnp.ndarray, ...]:
    """Receiver pass: the summed +/-scale delta of K frames (codec deltas are
    pure adds, so they commute) added to every array in one pass, clamped to
    +/-codec.SAT, padding lanes forced to 0. ``scales`` f32[K, k] or, one
    global scale a frame, f32[K, 1] (a frame's entry is 0 where it
    contributes nothing), ``words`` u32[K, words_rows(rows), 128] (K frames'
    words as :func:`quantize_rows` returns them, stacked: what an all-gather
    gives, and the kernel's operand as it arrives; ``packing.dense_words``
    makes it of the wire's ``u32[K, rows*4]``), ``arrays`` flat
    f32[rows*128] each (the rows of ``window``)."""
    k, rows = scales.shape[0], leaves.rows
    scales = leaves.one_a_leaf(scales)
    with jax.named_scope("st.apply"):
        if impl == "pallas":
            from . import codec_pallas

            block = codec_pallas.apply_block_rows(rows, k, len(arrays))
            return codec_pallas.apply_rows_batch(
                scales, leaves.tables(block, window), words, arrays
            )
        bits = unpack_bits(flat_words(words, rows)).reshape(k, rows, LANES)
        live = live_lanes(leaves.rowcount(window))
        s_rows = leaves.expand(scales, window)  # (K, rows)
        # elementwise + sum on the VPU: under the RMS policy a scale is
        # arbitrary, so the arithmetic stays exact f32, no MXU
        delta = jnp.sum(
            s_rows[:, :, None] * (1.0 - 2.0 * bits.astype(jnp.float32)), axis=0
        )
        return tuple(
            jnp.where(
                live, jnp.clip(a.reshape(rows, LANES) + delta, -SAT, SAT), 0.0
            ).reshape(-1)
            for a in arrays
        )


# --- the table codec --------------------------------------------------------


def _table_scales(
    residual: jnp.ndarray, leaves: LeafRows, policy: ScalePolicy, per_leaf: bool
) -> jnp.ndarray:
    """Per-leaf scales; ``per_leaf=False`` computes ONE scale over the whole
    table (the reference's behavior, src/sharedtensor.c:153-159 — wire-compat
    interop with C peers requires it) replicated to every leaf so the apply
    path is uniform."""
    over = leaves if per_leaf else leaves.whole()
    scales = leaf_scales(
        residual.reshape(-1, LANES),
        live_lanes(leaves.rowcount()),
        over.reduce,
        over.expand,
        jnp.asarray(np.asarray(over.ns, dtype=np.float32)),
        policy,
    )
    return leaves.one_a_leaf(scales)


@partial(jax.jit, static_argnames=("spec", "policy", "per_leaf", "impl"))
def _quantize_table(
    residual: jnp.ndarray,
    spec: TableSpec,
    policy: ScalePolicy,
    per_leaf: bool,
    impl: str,
) -> tuple[TableFrame, jnp.ndarray]:
    leaves = LeafRows.of(spec)
    scales = _table_scales(residual, leaves, policy, per_leaf)
    words, new_flat = quantize_rows(scales, leaves, None, residual, impl)
    return TableFrame(scales, flat_words(words, leaves.rows)), new_flat


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
    (:func:`quantize_rows`) — the production tier; its XLA twin is the CPU
    fallback. ``impl`` pins either ("xla" / "pallas") for parity tests."""
    return _quantize_table(residual, spec, policy, per_leaf, resolve_impl(impl))


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
        residual, spec, int(k), policy, per_leaf, resolve_impl(impl)
    )


@partial(jax.jit, static_argnames=("spec", "impl"))
def _apply_table_batch(
    arrays: tuple[jnp.ndarray, ...], frames: TableFrame, spec: TableSpec, impl: str
) -> tuple[jnp.ndarray, ...]:
    # one frame (scales [L], words [W]) is the K = 1 stack
    leaves = LeafRows.of(spec)
    return apply_rows(
        jnp.atleast_2d(frames.scales),
        leaves,
        None,
        dense_words(jnp.atleast_2d(frames.words), leaves.rows),
        arrays,
        impl,
    )


def apply_table_many(
    arrays: tuple[jnp.ndarray, ...],
    frame: TableFrame,
    spec: TableSpec,
    impl: str = "auto",
) -> tuple[jnp.ndarray, ...]:
    """Receiver step over a table applied to several arrays (replica + other
    links' residuals — the flood), one fused pass (Pallas on TPU): the K = 1
    case of :func:`apply_table_batch`."""
    return _apply_table_batch(arrays, frame, spec, resolve_impl(impl))


def apply_table(values: jnp.ndarray, frame: TableFrame, spec: TableSpec) -> jnp.ndarray:
    return apply_table_many((values,), frame, spec)[0]


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
    (:func:`apply_rows`) instead of K XLA unpack passes."""
    return _apply_table_batch(arrays, frames, spec, resolve_impl(impl))


@partial(jax.jit, static_argnames=("spec",))
def accumulate_table(
    arrays: tuple[jnp.ndarray, ...], update: jnp.ndarray, spec: TableSpec
) -> tuple[jnp.ndarray, ...]:
    """values += u and each link residual += u, sanitized (see
    codec.accumulate)."""
    live = live_lanes(jnp.asarray(spec.live_rowcount())).reshape(-1)
    u = jnp.where(live, update, 0.0)
    u = jnp.nan_to_num(u, nan=0.0, posinf=3.0e38, neginf=-3.0e38)
    return tuple(jnp.clip(a + u, -3.0e38, 3.0e38) for a in arrays)
