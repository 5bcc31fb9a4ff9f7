"""Pallas TPU kernels for the approximate-delta codec at row granularity.

The reference's hot path is 4-6 sequential CPU passes of n float ops per frame
(quantize src/sharedtensor.c:153-174, apply :106-111 — measured codec-CPU-bound
at 202 M elem/s, BASELINE.md). These two kernels move that work onto the TPU
with the minimum number of HBM passes. The table codec (ops/table.py) runs
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
the float32 rows and the packed words, and nothing is built for the words
either: ``quantize_rows`` writes them in the layout below, an all-gather
stacks K peers' arrays as they are, and ``apply_rows_batch`` takes the stack
as it arrives (PERF.md section 6, PR 37).

Each kernel has exactly one caller, ops/table.py (``quantize_rows`` /
``apply_rows``), which holds their XLA twins and builds ``LeafTables`` from
the table's static leaf ranges (``LeafRows.tables``); both are deliberately
UN-jitted, since the table functions wrap them in their own jit and
parallel/ici.py embeds them inside a shard_map'd step. A traced call is
counted (``st_codec_kernel_traces_total{kernel}``,
``st_codec_leaves_per_block_max``, ``st_codec_words_rows_per_block{kernel}``
in ``utils.profiling.pod_registry()``). A kernel's traced size does not grow
with K or with the block: whole-block vector operations and loops with one
body, nothing unrolled in Python (tests/test_codec_pallas.py guards it: a
body unrolled over frames and sublane groups cost a sync step 5.5 s of
set-up, PERF.md section 6, PR 36).

Bit layout is identical to ops/codec.py (flat bit i -> word[i//32] bit i%32),
so frames from either implementation interoperate; tests/test_codec_pallas.py
and tests/test_table_pallas.py require bit-for-bit equality with the golden
codec, a plain NumPy statement of the rule, and the XLA twins.

Kernels run compiled on TPU and fall back to the interpreter on CPU (tests).

Layout: a flat padded buffer (a multiple of 1024) viewed as (rows, 128)
float32 rows; the packed words are the wire layer's flat word vector viewed
128 words a row, ``u32[ceil(rows / 32), 128]`` (ops/packing.py
``dense_words``: a bitcast). Table row r's word k, which covers flat bits
128 r + 32 k .. +31, is flat word 4 r + k: words row r // 32, lane
4 (r % 32) + k. One words row holds the words of 32 consecutive table rows,
so the array is dense in HBM (52 MB at 3.28 M rows; a ``u32[rows, 4]`` is
lane-padded 32x there, 1.68 GB), a block of 1 024 table rows is a (32, 128)
tile of words, and ``words.reshape(-1)[: rows * 4]`` is the flat word
vector. ``rows`` is whole 8-row tiles, not whole words rows: the pad words
behind the last table row are 0 (no lane past a leaf's live end sets a bit)
and never read as live.
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
from .packing import BITS_PER_WORD, LANES, WORDS_PER_ROW, words_rows

#: Table rows whose packed words fill one 128-lane row of the words array.
ROWS_PER_WORDS_ROW = LANES // WORDS_PER_ROW  # 32
#: Most rows per grid step: 1024 rows x 128 lanes x 4 B = 512 KiB per buffer in
#: VMEM, 32 words rows. Both kernels wait on their DMAs and take them all:
#: fewer, longer transfers (PERF.md section 6, PR 32 and PR 37).
BLOCK_ROWS = 1024
#: Most frames the apply kernel unpacks back to back with no loop between
#: them: the scheduler overlaps one frame's lane gathers with the next's
#: arithmetic, a loop trip a frame does not (11.0 ms against 8.2 at K = 4,
#: 39.9 against 33.1 at K = 16 in groups of 8; PERF.md section 6, PR 37).
#: Past it a loop runs over groups of this many.
_FRAMES_UNROLLED = 8


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


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _split(x, n: int):
    """``(x // n, x % n)`` of non-negative int32 ``x`` by a power of two: a
    shift and a mask (``//`` and ``%`` each trace, lower and run a sign
    fix-up: 3 ms of lowering apiece, once a frame)."""
    return x >> (n.bit_length() - 1), x & (n - 1)


def _gather(x, index, axis: int):
    """``x`` with every element replaced by the one ``index`` names along
    ``axis`` in its own row (axis 1) or column (axis 0): what
    ``jnp.take_along_axis`` lowers to, without its pass over the indices
    (they are in bounds) — one XLU gather a vreg in Mosaic."""
    other = 1 - axis
    dnums = jax.lax.GatherDimensionNumbers(
        offset_dims=(),
        collapsed_slice_dims=(axis,),
        start_index_map=(axis,),
        operand_batching_dims=(other,),
        start_indices_batching_dims=(other,),
    )
    return jax.lax.gather(
        x, index[..., None], dnums, slice_sizes=(1, 1),
        mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS,
    )


def _spread_words(bits, spread_ref) -> None:
    """First half of the pack: (rows, 128) bool -> ``spread_ref`` i32[rows,
    128] with row r's word ``L % 4`` (LSB-first over its 32 lanes) at EVERY
    lane L, so that the word is already at the lane the words array wants it
    at, ``4 (r % 32) + L % 4``, whatever r is.

    The lane-group reduction runs on the MXU (Mosaic has no unsigned
    reduction, and a lane-splitting reshape, (rows, 128) -> (rows, 4, 32),
    fails "unsupported shape cast"): two (rows, 128) x (128, 128) dots with
    the constant weights W_half[l, L] = [l // 32 == L % 4] * 2^(l % 16), one
    for the low 16 bits of each word and one for the high 16. Every value
    stays <= 65535, so the f32 dot is exact; the halves are recombined with
    integer shifts. A weight tile is 128 columns wide whether 4 or 128 of
    them are used, so the dots cost what the (128, 4) weights cost.
    """
    # the bit's lane: its word, its position in the word (0..31)
    bit_word, e = _split(_iota((LANES, LANES), 0), BITS_PER_WORD)
    _, word = _split(_iota((LANES, LANES), 1), WORDS_PER_ROW)  # the output lane's
    in_word = bit_word == word
    w_lo = jnp.where(in_word & (e < 16), _exact_pow2(e & 15), 0.0)
    w_hi = jnp.where(in_word & (e >= 16), _exact_pow2(e & 15), 0.0)
    bits_f = bits.astype(jnp.float32)
    lo = jnp.dot(bits_f, w_lo, preferred_element_type=jnp.float32)
    hi = jnp.dot(bits_f, w_hi, preferred_element_type=jnp.float32)
    spread_ref[...] = lo.astype(jnp.int32) | (hi.astype(jnp.int32) << 16)


def _pack_rows(spread_ref, words_ref) -> None:
    """Second half of the pack: ``spread_ref`` i32[rows, 128] (see
    :func:`_spread_words`) -> ``words_ref`` u32[rows / 32, 128], words row R
    lane L = the word of table row ``32 R + L // 4`` that ``spread_ref``
    holds at that row's lane L: the diagonal of each 32-row group.

    Lane L's row lies in the group's vreg ``L // 32`` at sublane ``(L % 32)
    // 4``: three selects by lane pick the vreg, one sublane gather (XLU)
    picks the row; integer moves only, no arithmetic touches a word. One
    loop trip a words row, one traced body."""
    vreg, in_vreg = _split(_iota((8, LANES), 1), 8 * WORDS_PER_ROW)
    sublane, _ = _split(in_vreg, WORDS_PER_ROW)

    def one(r, carry):
        base = pl.multiple_of(r * ROWS_PER_WORDS_ROW, ROWS_PER_WORDS_ROW)
        v = spread_ref[pl.ds(base, 8), :]
        for j in range(1, ROWS_PER_WORDS_ROW // 8):
            v = jnp.where(vreg == j, spread_ref[pl.ds(base + 8 * j, 8), :], v)
        row = _gather(v, sublane, 0)[0:1]
        words_ref[pl.ds(r, 1), :] = jax.lax.bitcast_convert_type(row, jnp.uint32)
        return carry

    jax.lax.fori_loop(0, words_ref.shape[0], one, 0)


def _unpack_rows(words_i32, rows: int):
    """(rows / 32, 128) int32 words rows -> (rows, 128) 0/1 int32: table row
    ``32 R + q`` lane l = bit ``l % 32`` of words row R's lane ``4 q + l //
    32`` (inverse of :func:`_pack_rows`).

    A words row reaches its 32 table rows by a sublane broadcast and each
    word its 32 lanes by one lane gather a vreg (XLU; the (rows, 4) layout
    took four lane broadcasts a vreg), then shift and mask: all in the
    integer domain (an MXU dot would round its f32 inputs to bf16 and
    corrupt word values above 2^8; `& 1` discards the arithmetic shift's
    sign extension)."""
    _, q = _split(_iota((rows, LANES), 0), ROWS_PER_WORDS_ROW)
    k, bit = _split(_iota((rows, LANES), 1), BITS_PER_WORD)
    wrep = jnp.repeat(words_i32, ROWS_PER_WORDS_ROW, axis=0)
    return (_gather(wrep, WORDS_PER_ROW * q + k, 1) >> bit) & jnp.int32(1)


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

    pod_tier().count_codec_kernel_trace(
        kernel, tables.leaves_max, tables.block // ROWS_PER_WORDS_ROW
    )


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
    s_ref, lo_ref, end_ref, first_ref, last_ref,
    resid_ref, words_ref, new_resid_ref, spread_ref,
):
    def body(flat, off, at):
        s = at(lambda j: s_ref[j])
        # rows past the window's end (the last block's tail) lie past every
        # leaf's live end too, so their bits are 0 like any padding's
        live = flat < at(lambda j: end_ref[j] - off)
        r = resid_ref[...]  # (block, LANES)
        neg = r <= 0.0  # bit set => send -scale (zero counts as negative, Q3)
        _spread_words(jnp.logical_and(live, neg), spread_ref)
        sent = jnp.where(neg, -s, s)
        # rows whose leaf idles at scale 0 keep their residual; padding lanes
        # are forced back to 0 (the ops/table.py invariant, bit-for-bit)
        new_resid_ref[...] = jnp.where(
            jnp.logical_and(live, s > 0.0), r - sent, jnp.where(live, r, 0.0)
        )

    _on_block_leaves(lo_ref, first_ref, last_ref, resid_ref.shape[0], body)
    _pack_rows(spread_ref, words_ref)


def _row_spec(block: int) -> pl.BlockSpec:
    # index maps of a scalar-prefetch grid also receive the prefetched refs
    return pl.BlockSpec((block, LANES), lambda i, *_: (i, 0), memory_space=pltpu.VMEM)


def quantize_block_rows(rows: int) -> int:
    """Rows per grid step of quantize_rows: whole words rows, so a multiple
    of 32 (a block may reach past a short table's end)."""
    return min(BLOCK_ROWS, words_rows(rows) * ROWS_PER_WORDS_ROW)


def quantize_rows(
    scales: jnp.ndarray, tables: LeafTables, residual: jnp.ndarray
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused sender pass: sign-quantize + LSB-first pack + error feedback in
    ONE pass over HBM.

    ``scales`` f32[k] (one a leaf) and ``tables`` (cut for
    :func:`quantize_block_rows`) go to scalar memory; ``residual``
    f32[rows*128] flat is the only streamed input. Returns (words
    u32[words_rows(rows), 128], new_residual flat). Traceable — callers jit.
    Bit-for-bit equal to its XLA twin in ops/table.py.
    """
    rows = residual.shape[0] // LANES
    block = tables.block
    _count_trace("quantize_rows", tables)
    words, new_resid = pl.pallas_call(
        _quantize_rows_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(pl.cdiv(rows, block),),
            in_specs=[_row_spec(block)],
            out_specs=[_row_spec(block // ROWS_PER_WORDS_ROW), _row_spec(block)],
            scratch_shapes=[pltpu.VMEM((block, LANES), jnp.int32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((words_rows(rows), LANES), jnp.uint32),
            jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
        ],
        input_output_aliases={5: 1},
        interpret=_interpret(),
        name="st_quantize_rows",
    )(scales, *tables.scalars(), residual.reshape(rows, LANES))
    return words, new_resid.reshape(-1)


def _apply_rows_kernel(
    s_ref, lo_ref, end_ref, first_ref, last_ref, words_ref, *refs, k_frames, n_arrays
):
    unrolled = max(u for u in range(1, _FRAMES_UNROLLED + 1) if k_frames % u == 0)

    def body(flat, off, at):
        live = flat < at(lambda j: end_ref[j] - off)

        def frame(kf, delta):
            w = jax.lax.bitcast_convert_type(words_ref[kf], jnp.int32)
            s = at(lambda j: s_ref[kf, j])
            bits = _unpack_rows(w, flat.shape[0])
            return delta + s * (1.0 - 2.0 * bits.astype(jnp.float32))

        def group(g, delta):
            return jax.lax.fori_loop(
                0, unrolled, lambda u, d: frame(g * unrolled + u, d), delta, unroll=True
            )

        delta = jax.lax.fori_loop(
            0, k_frames // unrolled, group, jnp.zeros(flat.shape, jnp.float32)
        )
        delta = jnp.where(live, delta, 0.0)
        in_refs, out_refs = refs[:n_arrays], refs[n_arrays:]
        for i_ref, o_ref in zip(in_refs, out_refs):
            o_ref[...] = jnp.where(
                live, jnp.clip(i_ref[...] + delta, -SAT, SAT), 0.0
            )

    _on_block_leaves(lo_ref, first_ref, last_ref, refs[0].shape[0], body)


#: VMEM the apply kernel may plan for: 14 of the 16 MiB a Mosaic kernel gets
#: by default on a v5e ("Scoped allocation with size ... and limit 16.00M").
_APPLY_VMEM_BUDGET = 14 << 20
#: One (1, 128) row of any 32-bit operand or temporary in VMEM.
_ROW_BYTES = LANES * 4


def apply_block_rows(rows: int, k_frames: int, n_arrays: int) -> int:
    """Rows per grid step of apply_rows_batch: :func:`quantize_block_rows`'
    unless K frames and N arrays would not fit VMEM. Counted per block row,
    in 128-lane rows: every streamed operand twice (the pipeline
    double-buffers) — K / 32 rows of words, N arrays in and N out — plus 12
    for the temporaries (element index, live lanes, the running delta, one
    group of frames' gather index, words, bits and scale over the block).
    The frame loop keeps them from growing with K. A smaller block is whole
    8-row tiles of words, 256 rows (tests/test_tpu_compile.py compiles K =
    1, 4, 8, 16, 64 without a chip)."""
    per_row = _ROW_BYTES * (2 * 2 * n_arrays + 12) + 2 * 4 * WORDS_PER_ROW * k_frames
    fit = max(256, _APPLY_VMEM_BUDGET // per_row // 256 * 256)
    return min(quantize_block_rows(rows), fit)


def apply_rows_batch(
    scales: jnp.ndarray,
    tables: LeafTables,
    words: jnp.ndarray,
    arrays: tuple[jnp.ndarray, ...],
) -> tuple[jnp.ndarray, ...]:
    """Fused receive pass for K frames x N target arrays: the frames are
    unpacked ONCE, their +/-scale deltas summed (codec deltas are pure adds —
    they commute, ops/table.py apply_table_batch rationale), and the sum
    applied to every array in one HBM pass.

    ``scales`` f32[K, k] — per frame, per leaf (a frame's entry is 0 where it
    contributes nothing: idle leaves, split-horizon self-masking in
    parallel/ici.py) — and ``tables`` (cut for :func:`apply_block_rows`) go
    to scalar memory; ``words`` u32[K, words_rows(rows), 128] — the frames'
    words arrays as :func:`quantize_rows` writes them and an all-gather
    stacks them, a grid step taking ``(K, block / 32, 128)``; ``arrays``
    flat f32[rows*128] each.
    """
    rows = arrays[0].shape[0] // LANES
    k = scales.shape[0]
    n_arr = len(arrays)
    block = tables.block
    _count_trace("apply_rows_batch", tables)
    vspec = _row_spec(block)
    words_spec = pl.BlockSpec(
        (k, block // ROWS_PER_WORDS_ROW, LANES),
        lambda i, *_: (0, i, 0),
        memory_space=pltpu.VMEM,
    )
    outs = pl.pallas_call(
        partial(_apply_rows_kernel, k_frames=k, n_arrays=n_arr),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(pl.cdiv(rows, block),),
            in_specs=[words_spec] + [vspec] * n_arr,
            out_specs=[vspec] * n_arr,
        ),
        out_shape=[jax.ShapeDtypeStruct((rows, LANES), jnp.float32)] * n_arr,
        input_output_aliases={6 + i: i for i in range(n_arr)},
        interpret=_interpret(),
        name="st_apply_rows_batch",
    )(scales, *tables.scalars(), words, *[a.reshape(rows, LANES) for a in arrays])
    return tuple(o.reshape(-1) for o in outs)
