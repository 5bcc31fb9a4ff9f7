"""Pallas TPU kernels for the approximate-delta codec at row granularity.

The reference's hot path is 4-6 sequential CPU passes of n float ops per frame
(quantize src/sharedtensor.c:153-174, apply :106-111 — measured codec-CPU-bound
at 202 M elem/s, BASELINE.md). These two kernels move that work onto the TPU
VPU with the minimum number of HBM passes. The table codec (ops/table.py) runs
the sign/error-feedback rule with a scale per leaf, and per-leaf padding is
row-aligned, so a (1, 128) row has one scale and so many live lanes:

- ``quantize_rows``: ONE fused pass that sign-quantizes, packs the bits into
  LSB-first uint32 words, and applies the error feedback to the residual (the
  scales are a dependency of every element, so their reduction passes come
  first, in XLA, exactly as in the reference).
- ``apply_rows_batch``: ONE fused pass that unpacks K frames once, sums their
  +/-scale deltas and adds the sum to N arrays (replica + other links'
  residuals — the split-horizon flood), instead of K x N unpack+apply passes.

Who builds what. What is per leaf stays per leaf all the way into the kernel:
the caller hands the scales as ``f32[k]`` / ``f32[K, k]`` and a
:class:`LeafTables` (each leaf's first element and live end, and the first and
last leaf every grid block meets), all of which go to scalar memory
(``PrefetchScalarGridSpec``); the kernel derives its block's per-row scale and
live lanes from them (:func:`_on_block_leaves`). No ``(rows, 1)`` operand is
built, stored or streamed: XLA pads such an array to 128 lanes, 1.68 GB for
13 MB of numbers at 3.28 M rows, and its block costs a grid step half the DMA
of a full float32 block (PERF.md section 6, PR 32). The streamed operands are
the float32 rows and the packed words.

Each kernel has exactly one caller, ops/table.py (``quantize_rows`` /
``apply_rows``), which holds their XLA twins and builds ``LeafTables`` from
the table's static leaf ranges (``LeafRows.tables``); both are deliberately
UN-jitted, since the table functions wrap them in their own jit and
parallel/ici.py embeds them inside a shard_map'd step. A traced call is
counted (``st_codec_kernel_traces_total{kernel}``,
``st_codec_leaves_per_block_max`` in ``utils.profiling.pod_registry()``).

Bit layout is identical to ops/codec.py (flat bit i -> word[i//32] bit i%32),
so frames from either implementation interoperate; tests/test_codec_pallas.py
and tests/test_table_pallas.py require bit-for-bit equality with the golden
codec, a plain NumPy statement of the rule, and the XLA twins.

Kernels run compiled on TPU and fall back to the interpreter on CPU (tests).

Layout: a flat padded buffer (a multiple of 1024) viewed as (rows, 128)
float32 rows; packed words viewed as (rows, 4) uint32 rows. Row r, word k
covers flat bits 128*r + 32*k .. +31, so ``words2d.reshape(-1)`` is the flat
word vector used by the wire layer.
"""

from __future__ import annotations

import os
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .codec import SAT
from .packing import LANES, BITS_PER_WORD

WORDS_PER_ROW = LANES // BITS_PER_WORD  # 4
#: Most rows per grid step: 1024 rows x 128 lanes x 4 B = 512 KiB per buffer in
#: VMEM. A kernel that waits on its DMAs (quantize_rows; apply_rows_batch at
#: K = 1) takes them all: fewer, longer transfers (8.13 -> 7.86 ms and 8.50 ->
#: 8.09 ms at 3.28 M rows on a v5e, 2048 no better; PERF.md section 6, PR 32).
BLOCK_ROWS = 1024


def _interpret() -> bool:
    """Compiled on the TPU, interpreter on the CPU (tests). Any other
    backend is an error: the kernels are Mosaic-only, and a quiet
    interpreter run there would pass for a kernel run."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas codec kernels need a tpu backend (compiled) or cpu "
        f"(interpreter); jax.default_backend() is {backend!r}"
    )


def use_pallas() -> bool:
    """Should the production codec path (ops/table.py's row codec) run
    these kernels? Default: yes exactly on a tpu backend, where they
    compile; elsewhere the pure-XLA codec runs (on CPU it is faster than the
    Pallas interpreter). ``ST_CODEC=pallas|xla`` overrides (tests use it to
    pin either tier)."""
    mode = os.environ.get("ST_CODEC", "auto").lower()
    if mode == "pallas":
        return True
    if mode == "xla":
        return False
    return jax.default_backend() == "tpu"


def _exact_pow2(e_i32):
    """2^e as exact float32 via exponent-field construction (e in [0, 15]).
    TPU exp2 is approximate and must never be used for codec bit math."""
    return jax.lax.bitcast_convert_type((e_i32 + 127) << 23, jnp.float32)


def _pack_rows(bits_i32):
    """(rows, 128) 0/1 int32 -> (rows, 4) uint32, LSB-first per 32 lanes.

    Mosaic supports neither unsigned reductions nor lane-splitting reshapes
    ((rows,128)->(rows,4,32) fails "unsupported shape cast"), so the
    lane-group reduction runs on the MXU instead: two (rows,128)x(128,4) dots
    with constant weight matrices W_half[l, k] = [l//32 == k] * 2^(l%16),
    one for the low 16 bits of each word and one for the high 16. Every value
    stays <= 65535, so the f32 dot is exact; the halves are recombined with
    integer shifts.
    """
    rows = bits_i32.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (LANES, WORDS_PER_ROW), 0)
    word = jax.lax.broadcasted_iota(jnp.int32, (LANES, WORDS_PER_ROW), 1)
    in_word = lane // BITS_PER_WORD == word
    e = lane % BITS_PER_WORD  # bit position within the word, 0..31
    w_lo = jnp.where(in_word & (e < 16), _exact_pow2(e % 16), 0.0)
    w_hi = jnp.where(in_word & (e >= 16), _exact_pow2(e % 16), 0.0)
    bits_f = bits_i32.astype(jnp.float32)
    lo = jnp.dot(bits_f, w_lo, preferred_element_type=jnp.float32)
    hi = jnp.dot(bits_f, w_hi, preferred_element_type=jnp.float32)
    words_i32 = lo.astype(jnp.int32) | (hi.astype(jnp.int32) << 16)
    return jax.lax.bitcast_convert_type(words_i32, jnp.uint32)


def _unpack_rows(words_u32):
    """(rows, 4) uint32 -> (rows, 128) 0/1 int32 (inverse of _pack_rows).

    The lane replication (lane l <- word[l//32]) must stay in integer domain:
    an MXU dot would round its f32 inputs to bf16 and corrupt word values
    above 2^8. Each word column is lane-broadcast to its 32 lanes and the
    four spans concatenated; bit extraction is then shift+mask in int32
    (`& 1` discards arithmetic-shift sign extension).
    """
    rows = words_u32.shape[0]
    words = jax.lax.bitcast_convert_type(words_u32, jnp.int32)
    wrep = jnp.concatenate(
        [
            jnp.broadcast_to(words[:, k : k + 1], (rows, BITS_PER_WORD))
            for k in range(WORDS_PER_ROW)
        ],
        axis=1,
    )
    shift = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1) % BITS_PER_WORD
    return (wrep >> shift) & jnp.int32(1)


class LeafTables(NamedTuple):
    """What the kernels read from scalar memory about the leaves under their
    rows, built by ops/table.py (``LeafRows.tables``) from the table's static
    leaf ranges for one row window (a shard's rows) and one block size.
    Elements are counted flat within the window: row r, lane l is element
    128 r + l. Leaves ascend, and each is a whole number of 8-row tiles."""

    block: int  # rows per grid step these tables were cut for (static)
    leaves_max: int  # most leaves any block meets (static)
    lo: jnp.ndarray  # i32[k] each leaf's first element
    end: jnp.ndarray  # i32[k] one past each leaf's last live element
    first: jnp.ndarray  # i32[blocks] first leaf each grid block meets
    last: jnp.ndarray  # i32[blocks] last leaf each grid block meets

    def scalars(self) -> tuple[jnp.ndarray, ...]:
        """The four tables, in the order the kernels take them after the
        scales."""
        return self.lo, self.end, self.first, self.last


def _count_trace(kernel: str, tables: LeafTables) -> None:
    from ..utils.profiling import pod_tier

    pod_tier().count_codec_kernel_trace(kernel, tables.leaves_max)


def _on_block_leaves(lo_ref, first_ref, last_ref, rows: int, body) -> None:
    """Run ``body(flat, off, at)`` for this grid step's (rows, 128) block:
    ``flat`` is every element's flat index counted from the block's first,
    ``off`` the window's flat index of that first element, and ``at(pick)``
    gives what ``pick`` (leaf index -> a scalar read from scalar memory)
    holds for the leaf of every element. A block inside one leaf (nearly
    all of a large table's) gets the scalar itself, so the body's selects
    and products take a splat; a block that meets several leaves (up to 64
    of ResNet's 8-row BatchNorm leaves) gets a (rows, 128) array filled by
    a loop over those leaves. Leaves ascend, so each later one overwrites
    from its first element on: one compare and one select a leaf, by select
    alone, never by arithmetic on the picked value. The body is traced once
    a case."""
    b = pl.program_id(0)
    off = b * (rows * LANES)
    j0, j1 = first_ref[b], last_ref[b]
    flat = (
        jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 0) * LANES
        + jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1)
    )

    def over_leaves(pick):
        def step(j, acc):
            return jnp.where(flat >= lo_ref[j] - off, pick(j), acc)

        return jax.lax.fori_loop(j0 + 1, j1 + 1, step, jnp.full(flat.shape, pick(j0)))

    pl.when(j0 == j1)(lambda: body(flat, off, lambda pick: pick(j0)))
    pl.when(j0 != j1)(lambda: body(flat, off, over_leaves))


def _quantize_rows_kernel(
    s_ref, lo_ref, end_ref, first_ref, last_ref, resid_ref, words_ref, new_resid_ref
):
    def body(flat, off, at):
        s = at(lambda j: s_ref[j])
        live = flat < at(lambda j: end_ref[j] - off)
        r = resid_ref[...]  # (block, LANES)
        neg = r <= 0.0  # bit set => send -scale (zero counts as negative, Q3)
        bits = jnp.logical_and(live, neg)
        words_ref[...] = _pack_rows(bits.astype(jnp.int32))
        sent = jnp.where(neg, -s, s)
        # rows whose leaf idles at scale 0 keep their residual; padding lanes
        # are forced back to 0 (the ops/table.py invariant, bit-for-bit)
        new_resid_ref[...] = jnp.where(
            jnp.logical_and(live, s > 0.0), r - sent, jnp.where(live, r, 0.0)
        )

    _on_block_leaves(lo_ref, first_ref, last_ref, resid_ref.shape[0], body)


def _row_spec(block: int, width: int) -> pl.BlockSpec:
    # index maps of a scalar-prefetch grid also receive the prefetched refs
    return pl.BlockSpec((block, width), lambda i, *_: (i, 0), memory_space=pltpu.VMEM)


def quantize_block_rows(rows: int) -> int:
    """Rows per grid step of quantize_rows."""
    return min(BLOCK_ROWS, rows)


def quantize_rows(
    scales: jnp.ndarray, tables: LeafTables, residual: jnp.ndarray
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused sender pass: sign-quantize + LSB-first pack + error feedback in
    ONE pass over HBM.

    ``scales`` f32[k] (one a leaf) and ``tables`` (cut for
    :func:`quantize_block_rows`) go to scalar memory; ``residual``
    f32[rows*128] flat is the only streamed input. Returns (words u32[rows*4]
    flat, new_residual flat). Traceable — callers jit. Bit-for-bit equal to
    its XLA twin in ops/table.py.
    """
    rows = residual.shape[0] // LANES
    block = tables.block
    _count_trace("quantize_rows", tables)
    words2d, new_resid = pl.pallas_call(
        _quantize_rows_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(pl.cdiv(rows, block),),
            in_specs=[_row_spec(block, LANES)],
            out_specs=[_row_spec(block, WORDS_PER_ROW), _row_spec(block, LANES)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((rows, WORDS_PER_ROW), jnp.uint32),
            jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
        ],
        input_output_aliases={5: 1},
        interpret=_interpret(),
        name="st_quantize_rows",
    )(scales, *tables.scalars(), residual.reshape(rows, LANES))
    return words2d.reshape(-1), new_resid.reshape(-1)


def _apply_rows_kernel(
    s_ref, lo_ref, end_ref, first_ref, last_ref, words_ref, *refs, k_frames, n_arrays
):
    def body(flat, off, at):
        live = flat < at(lambda j: end_ref[j] - off)
        delta = jnp.zeros(flat.shape, jnp.float32)
        for kf in range(k_frames):
            w = words_ref[:, kf * WORDS_PER_ROW : (kf + 1) * WORDS_PER_ROW]
            bits = _unpack_rows(w)
            s = at(lambda j: s_ref[kf, j])
            delta = delta + s * (1.0 - 2.0 * bits.astype(jnp.float32))
        delta = jnp.where(live, delta, 0.0)
        in_refs, out_refs = refs[:n_arrays], refs[n_arrays:]
        for i_ref, o_ref in zip(in_refs, out_refs):
            o_ref[...] = jnp.where(
                live, jnp.clip(i_ref[...] + delta, -SAT, SAT), 0.0
            )

    _on_block_leaves(lo_ref, first_ref, last_ref, words_ref.shape[0], body)


#: VMEM the apply kernel may plan for: 14 of the 16 MiB a Mosaic kernel gets
#: by default on a v5e ("Scoped allocation with size ... and limit 16.00M").
_APPLY_VMEM_BUDGET = 14 << 20
#: One (1, 128) row of any 32-bit operand or temporary in VMEM. A narrower
#: block — the (block, 4K) words — is padded to whole 128-lane rows there,
#: so it costs the same.
_ROW_BYTES = LANES * 4


def apply_block_rows(rows: int, k_frames: int, n_arrays: int) -> int:
    """Rows per grid step of apply_rows_batch: BLOCK_ROWS at K = 1; half as
    many from K = 2 on, where the unrolled unpack and not the DMAs sets the
    pace and a larger block's temporaries spill (K = 4 on a v5e: 14.07 ms at
    512 rows, 14.68 at 1024); fewer when K frames would not fit VMEM. Counted
    per block row, in lane-padded 128-lane rows: every streamed operand twice
    (the pipeline double-buffers) — ceil(4K/128) rows of words, N arrays in
    and N out — plus 4 rows per frame for what the unrolled frame loop keeps
    live (word broadcast, bits, the frame's scale over the block, running
    delta; Mosaic does not reuse them across iterations) plus 4 for the
    epilogue (tests/test_tpu_compile.py compiles K = 1, 8, 16, 64 without a
    chip)."""
    words_rows = -(-k_frames * WORDS_PER_ROW // LANES)
    per_row = _ROW_BYTES * (2 * (words_rows + 2 * n_arrays) + 4 * k_frames + 4)
    fit = max(8, _APPLY_VMEM_BUDGET // per_row // 8 * 8)
    most = BLOCK_ROWS if k_frames == 1 else BLOCK_ROWS // 2
    return min(most, rows, fit)


def apply_rows_batch(
    scales: jnp.ndarray,
    tables: LeafTables,
    words2d: jnp.ndarray,
    arrays: tuple[jnp.ndarray, ...],
) -> tuple[jnp.ndarray, ...]:
    """Fused receive pass for K frames x N target arrays: the frames are
    unpacked ONCE, their +/-scale deltas summed (codec deltas are pure adds —
    they commute, ops/table.py apply_table_batch rationale), and the sum
    applied to every array in one HBM pass.

    ``scales`` f32[K, k] — per frame, per leaf (a frame's entry is 0 where it
    contributes nothing: idle leaves, split-horizon self-masking in
    parallel/ici.py) — and ``tables`` (cut for :func:`apply_block_rows`) go
    to scalar memory; ``words2d`` u32[rows, K*4] — frame k's packed bits for
    row r at [r, 4k:4k+4]; ``arrays`` flat f32[rows*128] each.
    """
    rows = arrays[0].shape[0] // LANES
    k = scales.shape[0]
    n_arr = len(arrays)
    block = tables.block
    _count_trace("apply_rows_batch", tables)
    vspec = _row_spec(block, LANES)
    outs = pl.pallas_call(
        partial(_apply_rows_kernel, k_frames=k, n_arrays=n_arr),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(pl.cdiv(rows, block),),
            in_specs=[_row_spec(block, k * WORDS_PER_ROW)] + [vspec] * n_arr,
            out_specs=[vspec] * n_arr,
        ),
        out_shape=[jax.ShapeDtypeStruct((rows, LANES), jnp.float32)] * n_arr,
        input_output_aliases={6 + i: i for i in range(n_arr)},
        interpret=_interpret(),
        name="st_apply_rows_batch",
    )(scales, *tables.scalars(), words2d, *[a.reshape(rows, LANES) for a in arrays])
    return tuple(o.reshape(-1) for o in outs)
