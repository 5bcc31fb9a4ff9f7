"""The expert loop's combine as a Pallas (Mosaic) kernel: ``acc[tokens[i]] +=
y[i]`` for the live rows ``i`` of one tile, ``acc`` the token-major
accumulator ``f32[T, hidden / 128, 128]`` of ``models/mla_moe.py``'s tile
loop, ``y [tile, hidden]`` the tile's rows as the experts' products leave
them.

Why a kernel where XLA has a scatter-add: on the token-major accumulator a
token's row is whole (8, 128) tiles of its own, 10 KB that one DMA moves, but
XLA's scatter makes its round trips (rows in, wait, add, rows out, wait) 16
rows at a time, one after the other, for padding rows and empty tiles alike:
0.049 ms a tile of 512 rows of 2 560 floats, a quarter of it bytes (my chip
run, PR 34). Here the token ids and the tile's live-row count arrive in
scalar memory, ``acc`` stays in HBM (aliased in to out) and ``y`` comes in
blocks of rows; a block's row reads are all started before the first wait,
and the next block's reads are in flight under this block's add and writes.
A tile's live tokens are distinct (a token chooses an expert once), so
nothing orders its rows; the rows past the live count, which XLA's scatter
adds as zeros to the table's first token, are skipped, and a block with no
live row moves nothing, not even its rows of ``y``. ``y`` is added to the
rows' own tiles in VMEM (``y[:, 128 j : 128 (j + 1)]`` to sublane ``j``), so
no relayout of it goes through HBM.

The sums are the scatter's to the bit: one float32 add a live row, tile by
tile; a skipped row would have added a zero, and an accumulator that starts
at +0 never holds a -0 that adding +0 would change.

Compiled on a tpu backend, interpreted on the CPU (``codec_pallas._interpret``);
:func:`takes` says whether the kernel runs at all. The XLA line it stands in
for (``mla_moe._add_rows``) is its twin and its oracle.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import codec_pallas

LANES = 128
#: rows a block, largest first: a block's row copies are issued by unrolled
#: scalar code (17 bundles a copy), and 64 rows of 2 560 floats are 0.64 MB
#: of ``y`` and 0.79 MB of rows in each of two slots
BLOCKS = (64, 32, 16, 8)


def _block(tile: int) -> int:
    return next((b for b in BLOCKS if tile % b == 0), 0)


def takes(acc, y) -> bool:
    """Does the kernel run for this accumulator and these rows? Where the
    codec's kernels do (``use_pallas``), on a token-major float32 accumulator
    ``[T, S, 128]`` and float32 rows ``[tile, S * 128]`` in whole blocks."""
    return bool(
        codec_pallas.use_pallas() and acc.ndim == 3 and acc.shape[2] == LANES
        and y.ndim == 2 and y.shape[1] == acc.shape[1] * LANES and _block(y.shape[0])
        and acc.dtype == y.dtype == jnp.float32)


def _combine_kernel(tok_ref, live_ref, y_ref, acc_in, acc, rows, arrived, left, *, rb: int):
    """One block of ``rb`` rows a grid step, two slots of ``rows [2, rb, S,
    128]``: this step waits for its block's rows (started a step ago), adds
    and starts their way back; before that it starts the next block's reads
    into the other slot, once that slot's writes (two blocks ago) have left.
    ``arrived`` / ``left`` are the slots' DMA flags for reads / writes."""
    del acc_in  # the same buffer as ``acc``
    b, nb = pl.program_id(0), pl.num_programs(0)
    slot = b % 2

    def copies(block, slot, back: bool, start: bool):
        """Start, or wait for, the row copies of ``block``'s live rows: HBM
        to ``rows[slot]``, or ``back``."""
        live = jnp.clip(live_ref[0] - block * rb, 0, rb)
        flag = (left if back else arrived).at[slot]

        def row(i, carry):
            here, there = rows.at[slot, i], acc.at[tok_ref[block * rb + i]]
            copy = pltpu.make_async_copy(*((here, there) if back else (there, here)), flag)
            copy.start() if start else copy.wait()
            return carry

        @pl.when(live == rb)
        def _():
            if start:  # straight-line code: the scalar core overlaps the copies' addresses
                lax.fori_loop(0, rb, row, 0, unroll=True)
            else:  # one wait for the whole block: a flag counts what has arrived
                pltpu.make_async_copy(acc.at[pl.ds(0, rb)], rows.at[slot], flag).wait()

        @pl.when(live < rb)
        def _():
            lax.fori_loop(0, live, row, 0)

    @pl.when(b == 0)
    def _():
        copies(0, 0, False, True)

    @pl.when(b + 1 < nb)
    def _():
        @pl.when(b >= 1)
        def _():
            copies(b - 1, 1 - slot, True, False)

        copies(b + 1, 1 - slot, False, True)

    copies(b, slot, False, False)

    @pl.when(live_ref[0] > b * rb)
    def _():
        # rows past the live count hold what the slot held: they are not written back
        rows[slot] = rows[slot] + y_ref[...].reshape(rows.shape[1:])

    copies(b, slot, True, True)

    @pl.when(b == nb - 1)
    def _():
        @pl.when(b >= 1)
        def _():
            copies(b - 1, 1 - slot, True, False)

        copies(b, slot, True, False)


def combine_rows(acc: jax.Array, y: jax.Array, tokens: jax.Array, live) -> jax.Array:
    """``acc [T, S, 128]`` with ``y[i]`` added to row ``tokens[i]`` for ``i <
    live``: ``y [tile, S * 128]`` float32, ``tokens [tile]`` int32 with the
    first ``live`` distinct. In place where the caller donates ``acc`` (the
    tile loop's carry)."""
    tile, d = y.shape
    rb = _block(tile)

    def y_block(b, tok, live):
        # a block with no live row fetches nothing: it names the block before it
        return jnp.minimum(b, jnp.maximum(live[0] - 1, 0) // rb), 0

    return pl.pallas_call(
        partial(_combine_kernel, rb=rb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(tile // rb,),
            in_specs=[pl.BlockSpec((rb, d), y_block), pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.VMEM((2, rb, *acc.shape[1:]), jnp.float32),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(acc.shape, acc.dtype),
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=codec_pallas._interpret(),
        name="st_moe_combine",
    )(tokens.astype(jnp.int32), jnp.asarray(live, jnp.int32).reshape(1), y, acc)
