"""Causal softmax attention as fused Pallas (Mosaic) kernels, forward and
backward, over ``q [H, T, d_qk]``, ``k [H_kv, T, d_qk]`` and ``v [H_kv, T,
d_v]`` (heads are a batch; ``d_qk`` need not equal ``d_v`` nor be a multiple
of 128), over the whole causal triangle or, with a ``window``, over the band
of the ``window`` newest keys of every query (its own among them). Query head
``h`` reads K/V head ``h // (H // H_kv)``: grouped queries.

A tile's scores, probabilities and their cotangents live in VMEM between the
products, so no ``[H, tile, tile]`` array goes through HBM and no carry is
updated in place: the forward kernel holds a query tile and walks the key
tiles up to its diagonal with the online softmax (running maximum,
denominator, numerator in scratch) and writes ``o`` and ``lse`` once; the
backward kernel holds a key tile (``dk``, ``dv`` in scratch), walks the query
tiles from its diagonal down, makes each tile's probabilities again from
``lse``, and adds every tile's part of ``dq`` into one float32 accumulator of
the whole head that stays in VMEM until the head is done. Key tiles after the
diagonal are never visited (the grid is the list of the causal triangle's
tiles, handed to the index maps as prefetched scalars) and only tiles that
straddle the diagonal build a mask. With a window the list holds the band's
tiles only, a tile that straddles the window's far edge builds the mask too,
and the forward kernel walks a query tile's key tiles from its diagonal
*back*: the diagonal tile holds every query's own key, so the running maximum
is finite from the first tile on, which the band's oldest tile (where a late
query of the tile sees nothing) could not promise. With fewer K/V heads than
query heads the K/V blocks are the query head's group's, and the backward
kernel writes ``dk``, ``dv`` a query head in float32, which XLA adds over
each group (a group's queries cannot share one ``dk`` in scratch: the kernel
holds one head's whole ``dq`` in VMEM, 16.8 MB at 16 384 x 128, and seven do
not fit).
A window narrower than a tile is a band like any other (a tile may straddle
both of its edges, and a late query of a far tile may see nothing of it),
and whole tiles then visit several times the scores the band holds; the chip
still runs the widest tiles fastest (:func:`_fwd_tiles` has the readings), so
a window does not narrow them. The one thing the forward kernel asks is that
a query tile lie inside one key tile, so that its walk starts on its queries'
own keys.
With no window and as many K/V heads the tile lists, the masks and the
compiled bodies are what they were before either existed.

Precision, the same as ``models/mla_moe.py``'s scan, which is the portable
path and these kernels' oracle: bfloat16 operands into every product with
float32 accumulation; scale, mask, maximum, ``exp``, sums and ``lse`` in
float32; the probabilities cast to bfloat16 for ``p v`` and ``p^T g``, ``ds``
for ``dq`` and ``dk``.

Compiled on a tpu backend, interpreted on the CPU (``codec_pallas._interpret``,
read through the module so that what forces the codec's kernels to compile
forces these); :func:`takes` says whether the kernels run at all
(``codec_pallas.use_pallas``, bfloat16 operands, whole tiles, the accumulator
fits).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import codec_pallas

LANES = 128
#: the tiles tried, largest first: a float32 tile of 512 x 512 scores is 1 MB
#: and the backward pass holds four of them
TILES = (512, 256, 128)
#: the most the kernels' own estimate of their VMEM may come to (they ask the
#: compiler for twice the estimate, over its default limit of 16 MiB): sized
#: for the 128 MiB of a v5e or v6e core
VMEM_BUDGET = 48 << 20

_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


def _tile(t: int) -> int:
    """The tile of ``t`` positions: the largest of ``TILES`` that divides
    ``t``, 0 if none does."""
    return next((b for b in TILES if t % b == 0), 0)


def _fwd_tiles(t: int) -> tuple[int, int]:
    """Queries by keys a forward tile: twice as many keys as queries where
    they divide ``t``. A query tile's running maximum, denominator and
    numerator are rescaled once a key tile, so wider key tiles spare work:
    the compiled kernel's bundles a score fall by a quarter from 512 x 512 to
    512 x 1 024 and no further at 2 048 (chip-free compile for a v5e, PR 30);
    the backward kernel rescales nothing and reads the same at every tile.

    A window does not narrow them, though whole tiles visit several times the
    scores a narrow band holds (3.0 times at 512 x 1 024 under a window of
    512, 2.0 at 512 x 512, 1.5 at 256 x 256): a tile's time is far from
    proportional to its scores. 72 heads over 8 192 positions under a window
    of 512, forward: 5.04 ms at 512 x 1 024, 5.63 at 512 x 512, 6.33 at 256 x
    512, 8.69 at 256 x 256, 15.8 at 128 x 128; backward: 7.50 at 512 x 512,
    8.38 at 256 x 512, 9.01 at 256 x 256, 17.5 at 128 x 128 (my chip run, PR
    35; the chip-free price of the bundles orders them alike)."""
    b = _tile(t)
    return b, (2 * b if b and t % (2 * b) == 0 else b)


def _lanes(d: int) -> int:
    return -(-d // LANES) * LANES


def _bwd_vmem_bytes(t: int, d_qk: int, d_v: int, bq: int, bk: int,
                    grouped: bool = False) -> int:
    """What the backward kernel holds in VMEM: the head's ``dq`` (a float32
    accumulator and the output block's two buffers), the tiles of its six
    operands twice each, ``dk`` and ``dv`` with their outputs (float32 ones
    where the queries are ``grouped``), and a tile's float32 scores and their
    three companions with their bfloat16 casts."""
    dq = t * _lanes(d_qk) * (4 + 2 * 2)
    operands = 2 * 2 * ((bq + bk) * _lanes(d_qk) + (bq + bk) * _lanes(d_v)) + 4 * 2 * 8 * bq * 4
    dkv = bk * (_lanes(d_qk) + _lanes(d_v)) * (4 + 2 * (4 if grouped else 2))
    tiles = bq * bk * (4 * 4 + 2 * 2)
    return dq + operands + dkv + tiles


def takes(q, k, v) -> bool:
    """Do the kernels run for these operands? Where the codec's kernels do
    (``use_pallas``: a tpu backend, or ``ST_CODEC=pallas``), on bfloat16
    ``[H, T, d]`` operands whose ``T`` is whole tiles, whose query heads are
    whole groups of the K/V heads and whose ``dq`` of one head fits VMEM."""
    if not codec_pallas.use_pallas() or q.ndim != 3 or q.shape[0] % k.shape[0]:
        return False
    if not all(a.dtype == jnp.bfloat16 for a in (q, k, v)):
        return False
    t, b = q.shape[1], _tile(q.shape[1])
    return b > 0 and _bwd_vmem_bytes(
        t, q.shape[-1], v.shape[-1], b, b, q.shape[0] != k.shape[0]) <= VMEM_BUDGET


def tile_list(t: int, bq: int, bk: int, by_key: bool, window: int | None = None):
    """``[(query tile, key tile)]`` of every tile of the causal triangle over
    ``t`` positions (one that holds a key at or before one of its queries)
    or, with a ``window``, of the band (a key at most ``window - 1`` before
    one of its queries as well). A query tile's key tiles one after the
    other, oldest first (newest first with a window), or, ``by_key``, a key
    tile's query tiles."""
    pairs = [(i, j) for i in range(t // bq) for j in range(t // bk)
             if j * bk < (i + 1) * bq and (window is None or (j + 1) * bk > i * bq - window + 1)]
    if by_key:
        pairs.sort(key=lambda p: (p[1], p[0]))
    elif window is not None:
        pairs.sort(key=lambda p: (p[0], -p[1]))
    return pairs


def _tiles(t: int, bq: int, bk: int, by_key: bool, window: int | None = None):
    i, j = np.asarray(tile_list(t, bq, bk, by_key, window), np.int32).T
    return jnp.asarray(i), jnp.asarray(j)


def _params(need: int):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=int(min(max(2 * need, 32 << 20), 2 * VMEM_BUDGET)),
    )


def _on_an_edge(first_q, first_k, bq: int, bk: int, window: int | None):
    """Has the tile a mask to build: a key after its first query (it
    straddles the diagonal) or, with a window, a key ``window`` or more
    before its last query (it straddles the band's far edge)?"""
    edge = first_k + bk - 1 > first_q
    if window is not None:
        edge = jnp.logical_or(edge, first_q + bq - first_k > window)
    return edge


def _masked(s, first_q, first_k, q_axis: int, window: int | None):
    """The tile's scores ``s`` (queries along ``q_axis``, keys along the
    other) with keys after their query, and keys ``window`` or more before
    it, at ``-inf``."""
    q_pos = first_q + lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
    k_pos = first_k + lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
    seen = k_pos <= q_pos
    if window is not None:
        seen = jnp.logical_and(seen, q_pos - k_pos < window)
    return jnp.where(seen, s, -jnp.inf)


def _kv_head(h_q: int, h_kv: int):
    """A query head's K/V head, for the index maps."""
    group = h_q // h_kv
    return (lambda h: h) if group == 1 else (lambda h: h // group)


def _either(flag, fn):
    """``fn(True)`` where ``flag``, else ``fn(False)``: two bodies, one run."""
    pl.when(flag)(partial(fn, True))
    pl.when(jnp.logical_not(flag))(partial(fn, False))


# --- forward ------------------------------------------------------------------


def _fwd_kernel(qi_ref, kj_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                top_ref, den_ref, num_ref, *, scale: float, bq: int, bk: int,
                window: int | None):
    t = pl.program_id(1)
    i, j = qi_ref[t], kj_ref[t]
    first_q, first_k = i * bq, j * bk
    # the key tile that holds the queries' own keys: a query tile's last
    # (oldest first, key tile 0 to the diagonal) or, with a window, its first
    # (newest first, the diagonal back to the band's oldest tile)
    newest = lambda: first_k + bk >= first_q + bq

    @pl.when(j == 0 if window is None else newest())
    def _():
        top_ref[...] = jnp.full_like(top_ref, -jnp.inf)
        den_ref[...] = jnp.zeros_like(den_ref)
        num_ref[...] = jnp.zeros_like(num_ref)

    def tile(masked: bool):
        s = lax.dot_general(q_ref[...], k_ref[...], _NT,
                            preferred_element_type=jnp.float32) * scale
        if masked:
            s = _masked(s, first_q, first_k, 0, window)
        top = top_ref[...]
        # the first tile holds a key every query sees (key 0, or with a
        # window each query's own): the maximum is finite from it on
        new_top = jnp.maximum(top, jnp.max(s, axis=-1, keepdims=True))
        shrink = jnp.exp(top - new_top)
        e = jnp.exp(s - new_top)
        top_ref[...] = new_top
        den_ref[...] = den_ref[...] * shrink + jnp.sum(e, axis=-1, keepdims=True)
        num_ref[...] = num_ref[...] * shrink + jnp.dot(
            e.astype(v_ref.dtype), v_ref[...], preferred_element_type=jnp.float32)

    _either(_on_an_edge(first_q, first_k, bq, bk, window), tile)

    @pl.when(newest() if window is None
             else j == jnp.maximum(first_q - window + 1, 0) // bk)
    def _():
        den = den_ref[...]
        o_ref[...] = (num_ref[...] / den).astype(o_ref.dtype)
        lse = top_ref[...] + jnp.log(den)  # a column; the output wants a row
        lse_ref[...] = jnp.transpose(jnp.broadcast_to(lse, (bq, LANES)))[:1]


def attention_fwd(q, k, v, *, window: int | None = None,
                  block_q: int | None = None, block_k: int | None = None):
    """``(o [H, T, d_v], lse [H, T])`` of softmax(q k^T / sqrt(d_qk)) v under
    the causal mask (and, with a ``window``, over each query's ``window``
    newest keys); ``o`` in the operands' dtype, ``lse`` float32."""
    h, t, d = q.shape
    dv = v.shape[-1]
    kv = _kv_head(h, k.shape[0])
    bq, bk = _fwd_tiles(t)
    bq, bk = block_q or bq, block_k or bk
    if window is not None and bk % bq:
        # the walk back from the diagonal starts on the tile of the queries' own keys
        raise ValueError(f"with a window a query tile ({bq}) lies inside one key tile ({bk})")
    qi, kj = _tiles(t, bq, bk, False, window)
    need = (2 * 2 * (bq * _lanes(d) + bk * _lanes(d) + bk * _lanes(dv) + bq * _lanes(dv))
            + bq * _lanes(dv) * 4 + bq * bk * (3 * 4 + 2))
    o, lse = pl.pallas_call(
        partial(_fwd_kernel, scale=1.0 / math.sqrt(d), bq=bq, bk=bk, window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(h, qi.shape[0]),
            in_specs=[
                pl.BlockSpec((None, bq, d), lambda h, t, qi, kj: (h, qi[t], 0)),
                pl.BlockSpec((None, bk, d), lambda h, t, qi, kj: (kv(h), kj[t], 0)),
                pl.BlockSpec((None, bk, dv), lambda h, t, qi, kj: (kv(h), kj[t], 0)),
            ],
            out_specs=[
                pl.BlockSpec((None, bq, dv), lambda h, t, qi, kj: (h, qi[t], 0)),
                pl.BlockSpec((None, 1, bq), lambda h, t, qi, kj: (h, 0, qi[t])),
            ],
            scratch_shapes=[
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, dv), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((h, t, dv), v.dtype),
            jax.ShapeDtypeStruct((h, 1, t), jnp.float32),
        ],
        compiler_params=_params(need),
        interpret=codec_pallas._interpret(),
        name="st_attn_fwd",
    )(qi, kj, q, k, v)
    return o, lse.reshape(h, t)


# --- backward -----------------------------------------------------------------


def _bwd_kernel(qi_ref, kj_ref, q_ref, k_ref, v_ref, g_ref, lse_ref, drop_ref,
                dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc,
                *, scale: float, bq: int, bk: int, n_q: int, window: int | None):
    """One tile, keys down the sublanes and queries along the lanes (``lse``
    and ``drop`` are rows of the query tile, as they lie in memory)."""
    t = pl.program_id(1)
    i, j = qi_ref[t], kj_ref[t]
    first_q, first_k = i * bq, j * bk

    @pl.when(t == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when(i == first_k // bq)  # the key tile's first query tile
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def tile(masked: bool):
        q, k, v, g = q_ref[...], k_ref[...], v_ref[...], g_ref[...]
        mm = partial(lax.dot_general, preferred_element_type=jnp.float32)
        s = mm(k, q, _NT) * scale  # [bk, bq]
        if masked:
            s = _masked(s, first_q, first_k, 1, window)
        p = jnp.exp(s - lse_ref[...])
        dp = mm(v, g, _NT)
        ds = (p * (dp - drop_ref[...]) * scale).astype(q.dtype)
        dv_acc[...] += jnp.dot(p.astype(g.dtype), g, preferred_element_type=jnp.float32)
        dk_acc[...] += jnp.dot(ds, q, preferred_element_type=jnp.float32)
        rows = pl.ds(pl.multiple_of(first_q, bq), bq)
        dq_acc[rows, :] += mm(ds, k, _TN)

    _either(_on_an_edge(first_q, first_k, bq, bk, window), tile)

    last_q = n_q - 1  # the key tile's last query tile: the sequence's, or the band's
    if window is not None:
        last_q = jnp.minimum(last_q, (first_k + bk + window - 2) // bq)

    @pl.when(i == last_q)
    def _():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)

    @pl.when(t == pl.num_programs(1) - 1)
    def _():
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)


def attention_bwd(q, k, v, o, lse, g, *, window: int | None = None,
                  block_q: int | None = None, block_k: int | None = None):
    """Cotangents of ``(q, k, v)`` for the cotangent ``g`` of ``o``, in the
    operands' dtypes, every tile's probabilities made again from ``lse``."""
    h, t, d = q.shape
    dv = v.shape[-1]
    h_kv = k.shape[0]
    kv = _kv_head(h, h_kv)
    bq, bk = block_q or _tile(t), block_k or _tile(t)
    qi, kj = _tiles(t, bq, bk, True, window)
    # sum_k p dp, a row: what the softmax's normalisation takes back
    drop = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    by_q = lambda width: pl.BlockSpec((None, bq, width), lambda h, t, qi, kj: (h, qi[t], 0))
    by_k = lambda width, of=lambda h: h: pl.BlockSpec(
        (None, bk, width), lambda h, t, qi, kj: (of(h), kj[t], 0))
    row = pl.BlockSpec((None, 1, bq), lambda h, t, qi, kj: (h, 0, qi[t]))
    dq, dk, dv_ = pl.pallas_call(
        partial(_bwd_kernel, scale=1.0 / math.sqrt(d), bq=bq, bk=bk, n_q=t // bq,
                window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(h, qi.shape[0]),
            in_specs=[by_q(d), by_k(d, kv), by_k(dv, kv), by_q(dv), row, row],
            out_specs=[
                pl.BlockSpec((None, t, d), lambda h, t, qi, kj: (h, 0, 0)),
                by_k(d), by_k(dv),
            ],
            scratch_shapes=[
                pltpu.VMEM((t, d), jnp.float32),
                pltpu.VMEM((bk, d), jnp.float32),
                pltpu.VMEM((bk, dv), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            # a query head each; a group's are added below, in float32 as the
            # scratch held them (470 MB a layer at 28 x 16 384 x 128, written
            # and read once: 1.2 ms of HBM time beside a kernel of tens)
            jax.ShapeDtypeStruct((h, t, d), k.dtype if h_kv == h else jnp.float32),
            jax.ShapeDtypeStruct((h, t, dv), v.dtype if h_kv == h else jnp.float32),
        ],
        compiler_params=_params(_bwd_vmem_bytes(t, d, dv, bq, bk, h_kv != h)),
        interpret=codec_pallas._interpret(),
        name="st_attn_bwd",
    )(qi, kj, q, k, v, g, lse.reshape(h, 1, t), drop.reshape(h, 1, t))
    if h_kv != h:
        dk, dv_ = (jnp.sum(a.reshape(h_kv, h // h_kv, t, -1), axis=1).astype(like.dtype)
                   for a, like in ((dk, k), (dv_, v)))
    return dq, dk, dv_
