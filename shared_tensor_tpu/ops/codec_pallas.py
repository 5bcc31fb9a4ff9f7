"""Pallas TPU kernels for the approximate-delta codec at row granularity.

The reference's hot path is 4-6 sequential CPU passes of n float ops per frame
(quantize src/sharedtensor.c:153-174, apply :106-111 — measured codec-CPU-bound
at 202 M elem/s, BASELINE.md). These two kernels move that work onto the TPU
VPU with the minimum number of HBM passes. The table codec (ops/table.py) runs
the sign/error-feedback rule with a scale per leaf, and per-leaf padding is
row-aligned, so at kernel granularity that is "a scale per (1, 128) row" plus
"live lanes per row":

- ``quantize_rows``: ONE fused pass that sign-quantizes, packs the bits into
  LSB-first uint32 words, and applies the error feedback to the residual (the
  scales are a dependency of every element, so their reduction passes come
  first, in XLA, exactly as in the reference).
- ``apply_rows_batch``: ONE fused pass that unpacks K frames once, sums their
  +/-scale deltas and adds the sum to N arrays (replica + other links'
  residuals — the split-horizon flood), instead of K x N unpack+apply passes.

Each has exactly one caller, ops/table.py (``quantize_rows`` / ``apply_rows``),
which holds their XLA twins and builds their operands; both are deliberately
UN-jitted, since the table functions wrap them in their own jit and
parallel/ici.py embeds them inside a shard_map'd step.

Bit layout is identical to ops/codec.py (flat bit i -> word[i//32] bit i%32),
so frames from either implementation interoperate; tests/test_codec_pallas.py
and tests/test_table_pallas.py require bit-for-bit equality with the golden
codec and with the XLA twins.

Kernels run compiled on TPU and fall back to the interpreter on CPU (tests).

Layout: a flat padded buffer (a multiple of 1024) viewed as (rows, 128)
float32 rows; packed words viewed as (rows, 4) uint32 rows. Row r, word k
covers flat bits 128*r + 32*k .. +31, so ``words2d.reshape(-1)`` is the flat
word vector used by the wire layer.
"""

from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu  # noqa: F401  (memory spaces)

from .codec import SAT
from .packing import LANES, BITS_PER_WORD

WORDS_PER_ROW = LANES // BITS_PER_WORD  # 4
#: Rows per grid step: 512 rows x 128 lanes x 4 B = 256 KiB per buffer in
#: VMEM — small enough to leave room for the multi-array apply, large enough
#: to amortize grid overhead.
BLOCK_ROWS = 512


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


def _quantize_rows_kernel(s_ref, cnt_ref, resid_ref, words_ref, new_resid_ref):
    s = s_ref[...]  # (block, 1) per-row scale
    c = cnt_ref[...]  # (block, 1) live lanes per row (0..128)
    r = resid_ref[...]  # (block, LANES)
    lane = jax.lax.broadcasted_iota(jnp.int32, r.shape, 1)
    live = lane < c
    neg = r <= 0.0  # bit set => send -scale (zero counts as negative, Q3)
    bits = jnp.logical_and(live, neg)
    words_ref[...] = _pack_rows(bits.astype(jnp.int32))
    sent = jnp.where(neg, -s, s)
    # rows whose leaf idles at scale 0 keep their residual; padding lanes are
    # forced back to 0 (the ops/table.py invariant, bit-for-bit)
    new_resid_ref[...] = jnp.where(
        jnp.logical_and(live, s > 0.0), r - sent, jnp.where(live, r, 0.0)
    )


def quantize_rows(
    s_row: jnp.ndarray, rowcount: jnp.ndarray, residual: jnp.ndarray
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused sender pass with per-row scales: sign-quantize + LSB-first pack +
    error feedback in ONE pass over HBM.

    ``s_row`` f32[rows] (leaf scale broadcast to its rows), ``rowcount``
    i32[rows] (live lanes per row), ``residual`` f32[rows*128] flat.
    Returns (words u32[rows*4] flat, new_residual flat). Traceable — callers
    jit. Bit-for-bit equal to its XLA twin in ops/table.py.
    """
    rows = residual.shape[0] // LANES
    block = min(BLOCK_ROWS, rows)
    row_spec = lambda w: pl.BlockSpec((block, w), lambda i: (i, 0), memory_space=pltpu.VMEM)
    words2d, new_resid = pl.pallas_call(
        _quantize_rows_kernel,
        grid=(pl.cdiv(rows, block),),
        in_specs=[row_spec(1), row_spec(1), row_spec(LANES)],
        out_specs=[row_spec(WORDS_PER_ROW), row_spec(LANES)],
        out_shape=[
            jax.ShapeDtypeStruct((rows, WORDS_PER_ROW), jnp.uint32),
            jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
        ],
        input_output_aliases={2: 1},
        interpret=_interpret(),
        name="st_quantize_rows",
    )(
        s_row.reshape(rows, 1),
        rowcount.reshape(rows, 1).astype(jnp.int32),
        residual.reshape(rows, LANES),
    )
    return words2d.reshape(-1), new_resid.reshape(-1)


def _apply_rows_kernel(s_ref, cnt_ref, words_ref, *refs, k_frames, n_arrays):
    c = cnt_ref[...]  # (block, 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (c.shape[0], LANES), 1)
    live = lane < c
    delta = jnp.zeros((c.shape[0], LANES), jnp.float32)
    for kf in range(k_frames):
        w = words_ref[:, kf * WORDS_PER_ROW : (kf + 1) * WORDS_PER_ROW]
        bits = _unpack_rows(w)
        s = s_ref[:, kf : kf + 1]  # (block, 1)
        delta = delta + s * (1.0 - 2.0 * bits.astype(jnp.float32))
    delta = jnp.where(live, delta, 0.0)
    in_refs, out_refs = refs[:n_arrays], refs[n_arrays:]
    for i_ref, o_ref in zip(in_refs, out_refs):
        o_ref[...] = jnp.where(
            live, jnp.clip(i_ref[...] + delta, -SAT, SAT), 0.0
        )


#: VMEM the apply kernel may plan for: 14 of the 16 MiB a Mosaic kernel gets
#: by default on a v5e ("Scoped allocation with size ... and limit 16.00M").
_APPLY_VMEM_BUDGET = 14 << 20
#: One (1, 128) row of any 32-bit operand or temporary in VMEM. Narrower
#: blocks — (block, 1) counts, (block, K) scales, (block, 4K) words — are
#: padded to whole 128-lane rows there, so they cost the same.
_ROW_BYTES = LANES * 4


def _apply_block_rows(rows: int, k_frames: int, n_arrays: int) -> int:
    """Rows per grid step of apply_rows_batch: as many as BLOCK_ROWS, fewer
    when K frames would not fit VMEM. Counted per block row, in lane-padded
    128-lane rows: every operand twice (the pipeline double-buffers) —
    scales, counts, ceil(4K/128) rows of words, N arrays in and N out — plus
    4 rows per frame for what the unrolled frame loop keeps live (word
    broadcast, bits, scale broadcast, running delta; Mosaic does not reuse
    them across iterations) plus 4 for the epilogue. The model overstates
    what the v5e compiler reported for K = 16..64 by 5-15 %
    (tests/test_tpu_compile.py compiles K = 1, 8, 16 without a chip)."""
    words_rows = -(-k_frames * WORDS_PER_ROW // LANES)
    per_row = _ROW_BYTES * (
        2 * (2 + words_rows + 2 * n_arrays) + 4 * k_frames + 4
    )
    fit = max(8, _APPLY_VMEM_BUDGET // per_row // 8 * 8)
    return min(BLOCK_ROWS, rows, fit)


def apply_rows_batch(
    s_rows: jnp.ndarray,
    rowcount: jnp.ndarray,
    words2d: jnp.ndarray,
    arrays: tuple[jnp.ndarray, ...],
) -> tuple[jnp.ndarray, ...]:
    """Fused receive pass for K frames x N target arrays, per-row scales: the
    frames are unpacked ONCE, their +/-scale deltas summed (codec deltas are
    pure adds — they commute, ops/table.py apply_table_batch rationale), and
    the sum applied to every array in one HBM pass.

    ``s_rows`` f32[rows, K] — per-frame, per-row scales (a frame's column is 0
    where it contributes nothing: idle leaves, split-horizon self-masking in
    parallel/ici.py); ``words2d`` u32[rows, K*4] — frame k's packed bits for
    row r at [r, 4k:4k+4]; ``arrays`` flat f32[rows*128] each.
    """
    rows = arrays[0].shape[0] // LANES
    k = s_rows.shape[1]
    n_arr = len(arrays)
    block = _apply_block_rows(rows, k, n_arr)
    row_spec = lambda w: pl.BlockSpec((block, w), lambda i: (i, 0), memory_space=pltpu.VMEM)
    vspec = row_spec(LANES)
    outs = pl.pallas_call(
        partial(_apply_rows_kernel, k_frames=k, n_arrays=n_arr),
        grid=(pl.cdiv(rows, block),),
        in_specs=[row_spec(k), row_spec(1), row_spec(k * WORDS_PER_ROW)]
        + [vspec] * n_arr,
        out_specs=[vspec] * n_arr,
        out_shape=[jax.ShapeDtypeStruct((rows, LANES), jnp.float32)] * n_arr,
        input_output_aliases={3 + i: i for i in range(n_arr)},
        interpret=_interpret(),
        name="st_apply_rows_batch",
    )(
        s_rows,
        rowcount.reshape(rows, 1).astype(jnp.int32),
        words2d,
        *[a.reshape(rows, LANES) for a in arrays],
    )
    return tuple(o.reshape(-1) for o in outs)
