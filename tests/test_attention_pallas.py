"""ops/attention_pallas.py in the interpreter against its two oracles: the
scan of models/mla_moe.py (the portable path, the same precision by design)
and a plain float32 ``softmax(q k^T) v``; and which path ``causal_attention``
takes for which operands (``st_attn_traces_total{path}``)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shared_tensor_tpu.models import mla_moe as M
from shared_tensor_tpu.obs.schema import label_key
from shared_tensor_tpu.ops import attention_pallas as A
from shared_tensor_tpu.utils.profiling import pod_registry


def plain(q, k, v, window=None):
    """``(o, lse)`` of causal softmax attention, float32 at precision
    ``highest``, the ``[H, T, T]`` scores whole under a dense mask: key j for
    query i iff ``j <= i`` and, with a ``window``, ``i - j < window``. Fewer
    K/V heads than query heads are repeated over their groups."""
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    group = q.shape[0] // k.shape[0]
    k, v = jnp.repeat(k, group, axis=0), jnp.repeat(v, group, axis=0)
    s = jnp.einsum("hqd,hkd->hqk", q, k, precision="highest") / np.sqrt(q.shape[-1])
    t = q.shape[1]
    i = jnp.arange(t)
    seen = i[None, :] <= i[:, None]
    if window is not None:
        seen &= i[:, None] - i[None, :] < window
    s = jnp.where(seen, s, -jnp.inf)
    o = jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(s, axis=-1), v, precision="highest")
    return o, jax.nn.logsumexp(s, axis=-1)


@functools.lru_cache(maxsize=None)
def operands(h, t, d_qk, d_v, h_kv=None):
    """``q, k, v, g``; ``k`` and ``v`` of ``h_kv`` heads (``h`` by default)."""
    keys = jax.random.split(jax.random.key(t + d_qk), 4)
    return tuple(jax.random.normal(key, (heads, t, d), jnp.float32).astype(jnp.bfloat16)
                 for key, heads, d in zip(keys, (h, h_kv or h, h_kv or h, h),
                                          (d_qk, d_qk, d_v, d_v)))


def worst(a, b):
    return float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))


CASES = {
    # the model's head widths, two query tiles by two key tiles
    "mla_widths_2x2_tiles": (2, 256, 192, 128, 128, 128),
    # a width of q and k that is no multiple of the 128 lanes, nor is v's
    "widths_80_64": (1, 256, 80, 64, 128, 128),
    # one tile holds the whole sequence: the diagonal tile alone
    "one_tile": (2, 128, 192, 128, None, None),
    # the tile the kernels choose themselves at this length
    "own_tile_choice": (1, 512, 192, 128, None, None),
    # a query tile of two key tiles, both on its diagonal
    "diagonal_only_wide_queries": (2, 256, 192, 128, 256, 128),
    # a key tile of two query tiles: the first sees half of it masked out whole
    "wide_keys": (1, 512, 192, 128, 128, 256),
    "three_by_three": (1, 384, 128, 128, 128, 128),
    # the forward kernel's own choice of keys twice as wide as queries
    "own_wide_key_tiles": (1, 1024, 192, 128, None, None),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernels_equal_the_scan_and_the_plain_softmax(case):
    """Forward ``o`` and ``lse`` and the three cotangents. The tolerance is
    the scan's own error against float32 on the same operands: the kernels
    round where the scan rounds, so they may differ from it by less than it
    differs from float32, and from float32 by little more than it does."""
    h, t, d_qk, d_v, bq, bk = CASES[case]
    q, k, v, g = operands(h, t, d_qk, d_v)
    scan_block = min(128, t)
    o_scan, lse_scan = M._attention_fwd_tiles(q, k, v, scan_block)
    o_plain, lse_plain = plain(q, k, v)
    o, lse = A.attention_fwd(q, k, v, block_q=bq, block_k=bk)
    assert o.dtype == jnp.bfloat16 and lse.dtype == jnp.float32
    assert o.shape == v.shape and lse.shape == q.shape[:2]
    room = worst(o_scan, o_plain)
    assert 0 < room < 0.02
    assert worst(o, o_scan) <= room
    assert worst(o, o_plain) <= 1.5 * room
    lse_room = worst(lse_scan, lse_plain)
    assert worst(lse, lse_scan) <= max(lse_room, 1e-5)
    assert worst(lse, lse_plain) <= 1.5 * lse_room + 1e-5

    # the same residuals into both backward passes
    got = A.attention_bwd(q, k, v, o_scan, lse_scan, g, block_q=bq, block_k=bk)
    scan = M._attention_bwd_tiles(q, k, v, o_scan, lse_scan, g, scan_block)
    exact = jax.grad(lambda *a: jnp.sum(plain(*a)[0] * g.astype(jnp.float32)), (0, 1, 2))(
        *(a.astype(jnp.float32) for a in (q, k, v)))
    for name, a, b, c in zip(("dq", "dk", "dv"), got, scan, exact):
        assert a.dtype == jnp.bfloat16 and a.shape == c.shape, name
        room = worst(b, c)
        assert 0 < room < 0.05 * float(jnp.max(jnp.abs(c))), name
        assert worst(a, b) <= room, name
        assert worst(a, c) <= 1.5 * room, name


BAND = {
    # (H, H_kv, T, window, block_q, block_k): lengths of several windows
    # the second model's layout in small: 7 query heads a K/V head, 3.1 windows
    "grouped_7_window_of_no_whole_tile": (7, 1, 640, 200, 128, 128),
    # ungrouped, keys twice as wide as queries, a window just over a tile
    "ungrouped_wide_keys": (2, 2, 512, 130, 128, 256),
    # the window is exactly one tile: every far-edge tile is half masked
    "window_of_one_tile": (4, 2, 384, 128, 128, 128),
    # the kernels' own tiles (512 x 1 024 forward) and a window under one
    "own_tiles_short_window": (2, 1, 1024, 300, None, None),
    # grouped over the whole triangle: no window, the K/V index maps alone
    "grouped_no_window": (4, 2, 256, None, 128, 128),
    # a window the sequence never fills: the triangle under the band's code
    "window_longer_than_the_sequence": (2, 2, 256, 1000, 128, 128),
    # the third model's layouts in small. Groups of 6 (its full layers' 48 on
    # 8) under a window narrower than the query tile and than the key tile and
    # a multiple of neither: a query tile's band lies inside two key tiles
    "group_of_6_window_under_both_tile_sides": (6, 1, 512, 100, 128, 256),
    # groups of 9 (its window layers' 72 on 8), two K/V heads, the window
    # between the query tile and the key tile
    "group_of_9_window_between_the_tile_sides": (18, 2, 512, 200, 128, 256),
    # square tiles of 256 and a window far under them: every tile but the
    # first of a row straddles both of the band's edges
    "window_far_under_square_tiles": (6, 1, 512, 72, 256, 256),
    # the kernels' own tiles (512 x 1 024 forward, 512 backward) at a group of
    # 9 under a window narrower than either side
    "own_tiles_group_of_9": (9, 1, 1024, 300, None, None),
}


@pytest.mark.parametrize("case", sorted(BAND))
def test_band_and_groups_equal_the_scan_and_the_dense_mask(case):
    """The band and grouped queries, forward and backward, three ways: the
    interpreted kernels, the scan (their oracle, whose tile of 128 the
    windows here do not divide into) and a dense masked softmax in float32.
    Tolerances as in the triangle's test: the scan's own error against
    float32."""
    h, h_kv, t, window, bq, bk = BAND[case]
    q, k, v, g = operands(h, t, 128, 128, h_kv)
    o_scan, lse_scan = M._attention_fwd_tiles(q, k, v, 128, window)
    o_plain, lse_plain = plain(q, k, v, window)
    o, lse = A.attention_fwd(q, k, v, window=window, block_q=bq, block_k=bk)
    assert o.dtype == jnp.bfloat16 and o.shape == q.shape and lse.shape == q.shape[:2]
    room = worst(o_scan, o_plain)
    assert 0 < room < 0.02
    assert worst(o, o_scan) <= room and worst(o, o_plain) <= 1.5 * room
    assert worst(lse, lse_scan) <= 1e-5 and worst(lse_scan, lse_plain) <= 1e-5

    got = A.attention_bwd(q, k, v, o_scan, lse_scan, g, window=window, block_q=bq, block_k=bk)
    scan = M._attention_bwd_tiles(q, k, v, o_scan, lse_scan, g, 128, window)
    exact = jax.grad(lambda *a: jnp.sum(plain(*a, window)[0] * g.astype(jnp.float32)),
                     (0, 1, 2))(*(a.astype(jnp.float32) for a in (q, k, v)))
    for name, a, b, c, like in zip(("dq", "dk", "dv"), got, scan, exact, (q, k, v)):
        assert a.dtype == jnp.bfloat16 and a.shape == b.shape == c.shape == like.shape, name
        room = worst(b, c)
        assert 0 < room < 0.05 * float(jnp.max(jnp.abs(c))), name
        assert worst(a, b) <= room, name
        assert worst(a, c) <= 1.5 * room, name


@pytest.mark.parametrize("case", [
    "grouped_7_window_of_no_whole_tile", "group_of_6_window_under_both_tile_sides",
    "own_tiles_group_of_9"])
def test_a_window_one_key_short_is_told_apart(case):
    """The far edge is exact: the band of ``window - 1`` keys differs from
    the band of ``window`` in the kernels, the scan and the dense mask alike,
    by far more than their rounding; also where the window is narrower than a
    tile."""
    h, h_kv, t, window, bq, bk = BAND[case]
    q, k, v, _ = operands(h, t, 128, 128, h_kv)
    right = plain(q, k, v, window)[0]
    for short in (A.attention_fwd(q, k, v, window=window - 1, block_q=bq, block_k=bk)[0],
                  M._attention_fwd_tiles(q, k, v, 128, window - 1)[0],
                  plain(q, k, v, window - 1)[0]):
        assert worst(short, right) > 0.05
    assert worst(A.attention_fwd(q, k, v, window=window, block_q=bq, block_k=bk)[0], right) < 0.01


def test_without_a_window_the_tile_lists_are_the_triangles():
    """No window and as many K/V heads: the lists are what they were before
    either existed (the triangle, a query tile's key tiles oldest first; by
    key, a key tile's query tiles), and the index maps take the head as it
    is. With a window the band's tiles only, each query tile's newest
    first."""
    for t, bq, bk in ((8192, 512, 1024), (8192, 512, 512), (1024, 128, 256)):
        triangle = [(i, j) for i in range(t // bq) for j in range(t // bk) if j * bk < (i + 1) * bq]
        assert A.tile_list(t, bq, bk, False) == triangle
        assert A.tile_list(t, bq, bk, True) == sorted(triangle, key=lambda p: (p[1], p[0]))
    i, j = np.tril_indices(16)
    assert [tuple(p) for p in zip(*map(np.asarray, M._tile_pairs(16 * 512, 512, None)))] == \
        list(zip(i.tolist(), j.tolist()))
    assert A._kv_head(32, 32)(5) == 5 and A._kv_head(28, 4)(13) == 1
    # 16 384 positions, a window of 4 096: what the second model's cell runs
    band = A.tile_list(16384, 512, 512, True, 4096)
    assert len(band) == 252 and len(A.tile_list(16384, 512, 512, True)) == 528
    assert all(0 <= i * 512 + 511 - j * 512 and i * 512 - (j * 512 + 511) < 4096 for i, j in band)
    fwd = A.tile_list(16384, 512, 1024, False, 4096)
    assert fwd[:5] == [(0, 0), (1, 0), (2, 1), (2, 0), (3, 1)]  # the diagonal back


def test_a_window_wants_a_query_tile_inside_one_key_tile():
    """The forward kernel walks a window's key tiles from the diagonal back
    and counts on the first of them holding every query's own key: a query
    tile wider than its key tiles would start some rows on a tile they see
    nothing of. It says so; the tiles it chooses itself never are, and the
    backward kernel, which keeps no running maximum, takes any."""
    q, k, v, g = operands(2, 512, 128, 128, 1)
    with pytest.raises(ValueError, match="inside one key tile"):
        A.attention_fwd(q, k, v, window=72, block_q=256, block_k=128)
    o, lse = plain(q, k, v, 72)
    got = A.attention_bwd(q, k, v, o.astype(q.dtype), lse, g, window=72, block_q=256, block_k=128)
    want = A.attention_bwd(q, k, v, o.astype(q.dtype), lse, g, window=72, block_q=128, block_k=128)
    for a, b in zip(got, want):
        assert worst(a, b) <= 0.01 * float(jnp.max(jnp.abs(b.astype(jnp.float32))))
    for t in (8192, 16384, 1024, 384, 128):
        bq, bk = A._fwd_tiles(t)
        assert bk % bq == 0


def test_a_window_does_not_narrow_the_tiles():
    """With no window, with a window of eight tiles (4 096 at 16 384 tokens)
    and with one of a single tile (512 at 8 192) the tiles are the length's
    own, 512 queries by 1 024 keys forward and 512 by 512 backward, and the
    lists what they were: 272 forward tiles of the triangle and 140 of the
    band at 16 384. Under the window of 512 whole tiles visit 3.0 times the
    band's scores forward (an odd query tile's band lies inside one key tile,
    an even one's in two) and 2.0 backward, where tiles of 256 would visit
    1.5: the chip runs the wide ones faster all the same (``_fwd_tiles``)."""
    assert A._fwd_tiles(8192) == A._fwd_tiles(16384) == (512, 1024)
    assert A._tile(8192) == A._tile(16384) == 512
    assert len(A.tile_list(16384, 512, 1024, False)) == 272
    assert len(A.tile_list(16384, 512, 1024, False, 4096)) == 140
    assert len(A.tile_list(16384, 512, 512, True, 4096)) == 252
    band = 512 * 513 // 2 + (8192 - 512) * 512  # pairs a head
    visited = lambda bq, bk: len(A.tile_list(8192, bq, bk, False, 512)) * bq * bk / band
    assert len(A.tile_list(8192, 512, 1024, False, 512)) == 23
    assert visited(512, 1024) == pytest.approx(3.0, abs=0.05)
    assert visited(512, 512) == pytest.approx(2.0, abs=0.05)
    assert visited(256, 256) == pytest.approx(1.5, abs=0.03)
    assert round(1000 * band / (8192 * 8193 // 2)) == 121  # 12.1 % of the triangle


def _trace_counts():
    snap = pod_registry().snapshot()
    return {p: snap[label_key("st_attn_traces_total", "path", p)] for p in ("pallas", "scan")}


PATHS = {
    # (ST_CODEC, dtype, T) -> the path that runs
    "bfloat16_whole_tiles_pallas_tier": ("pallas", "bfloat16", 256, "pallas"),
    "float32_program": ("pallas", "float32", 256, "scan"),
    "length_of_no_whole_tile": ("pallas", "bfloat16", 192, "scan"),
    "shorter_than_a_tile": ("pallas", "bfloat16", 64, "scan"),
    "xla_tier": ("xla", "bfloat16", 256, "scan"),
    "cpu_backend_by_default": (None, "bfloat16", 256, "scan"),
}


@pytest.mark.parametrize("case", sorted(PATHS))
def test_which_path_causal_attention_takes(monkeypatch, case):
    """The kernels run where the codec's do and the operands are theirs;
    everything else is the scan. Either way the result and the gradients,
    under a ``vmap`` over a peer axis of one as ``build_train_step`` maps the
    loss, are the scan's to a bfloat16 rounding."""
    tier, dtype, t, path = PATHS[case]
    if tier is None:
        monkeypatch.delenv("ST_CODEC", raising=False)
    else:
        monkeypatch.setenv("ST_CODEC", tier)
    h, d_qk, d_v = 2, 192, 128
    keys = jax.random.split(jax.random.key(3), 4)
    q, k, v, g = (jax.random.normal(key, (1, t, h, d), jnp.float32).astype(dtype)
                  for key, d in zip(keys, (d_qk, d_qk, d_v, d_v)))

    def loss(q, k, v):
        return jnp.sum(M.causal_attention(q, k, v, 64).astype(jnp.float32)
                       * g[0].astype(jnp.float32))

    before = _trace_counts()
    value, grads = jax.vmap(jax.value_and_grad(loss, (0, 1, 2)))(q, k, v)
    after = _trace_counts()
    other = "scan" if path == "pallas" else "pallas"
    assert after[path] - before[path] == 1 and after[other] == before[other]

    monkeypatch.setenv("ST_CODEC", "xla")  # the scan, whatever ran above
    value_scan, grads_scan = jax.vmap(jax.value_and_grad(loss, (0, 1, 2)))(q, k, v)
    if path == "scan":
        assert float(value[0]) == float(value_scan[0])
    for a, b in zip(grads, grads_scan):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert worst(a, b) <= (0.004 if path == "pallas" else 0.0) * float(jnp.max(jnp.abs(b)))


def test_takes_refuses_a_head_whose_dq_does_not_fit_vmem(monkeypatch):
    monkeypatch.setenv("ST_CODEC", "pallas")
    arg = lambda t, d, h=32: jax.ShapeDtypeStruct((h, t, d), jnp.bfloat16)
    assert A.takes(arg(8192, 192), arg(8192, 192), arg(8192, 128))
    assert not A.takes(arg(1 << 16, 192), arg(1 << 16, 192), arg(1 << 16, 128))
    # the second model's heads at its whole context: one head's dq is 16.8 MB
    assert A.takes(arg(16384, 128, 28), arg(16384, 128, 4), arg(16384, 128, 4))
    assert A._bwd_vmem_bytes(16384, 128, 128, 512, 512, grouped=True) < A.VMEM_BUDGET // 2
    assert not A.takes(arg(16384, 128, 28), arg(16384, 128, 8), arg(16384, 128, 8))  # 28 % 8
