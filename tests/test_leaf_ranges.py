"""Static leaf row ranges (``TableSpec.leaf_rows``) and the two helpers that
run over them, ``leaf_reduce`` and ``leaf_expand``, against what they replace:
``jax.ops.segment_max`` / ``segment_sum`` over ``row_leaf`` and the gather
``v[..., row_leaf]``. ``max`` and the expansion are exact; ``sum`` differs
only by the order of the float32 additions inside a leaf."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shared_tensor_tpu.ops.packing import LANES
from shared_tensor_tpu.ops.table import (
    _range_runs,
    clip_ranges,
    leaf_expand,
    leaf_reduce,
    make_spec,
)


def _spec(shapes):
    return make_spec([jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes])


def _unequal():
    rng = np.random.default_rng(7)
    return [(int(rng.integers(1, 9000)),) for _ in range(9)]


# name -> leaf shapes; every leaf is padded to whole 8-row tiles
SPECS = {
    "unequal": _unequal(),
    "single": [(40, 300)],
    "one_tile_each": [(1000,), (3,), (1024,), (128,), (77,)],
    # the shape of a decoder layer's table: a norm, a run of equal expert
    # matrices, a router, two norms, four projections of two sizes
    "equal_runs": [(64,)] + [(32, 128)] * 12 + [(24, 128), (64,), (64,)]
    + [(16, 128), (64, 128), (64, 128), (40, 128)],
}


def _ranges_and_index(name, per_leaf):
    spec = _spec(SPECS[name])
    rows = spec.total // LANES
    if per_leaf:
        return spec.leaf_rows, spec.row_leaf(), rows
    return ((0, rows),), np.zeros(rows, np.int32), rows


def test_leaf_rows_tile_the_table_in_leaf_order():
    spec = _spec(SPECS["unequal"])
    ranges = spec.leaf_rows
    assert len(ranges) == spec.num_leaves
    assert ranges[0][0] == 0 and ranges[-1][1] == spec.total // LANES
    row_leaf = spec.row_leaf()
    for i, (a, b) in enumerate(ranges):
        assert b - a == spec.padded[i] // LANES and (b - a) % 8 == 0
        assert (row_leaf[a:b] == i).all()
    for (_, b), (a, _) in zip(ranges, ranges[1:]):
        assert a == b


def test_equal_leaves_batch_into_runs():
    """The op count follows the runs, not the leaves: 20 leaves, 7 runs."""
    ranges = _spec(SPECS["equal_runs"]).leaf_rows
    runs = list(_range_runs(ranges))
    assert len(ranges) == 20 and len(runs) == 7
    assert runs[1] == (1, 12, 8, 32)  # the twelve (32, 128) leaves: one reshape
    assert sum(n for _, n, _, _ in runs) == len(ranges)
    # a shard that cuts a run leaves its whole leaves batched
    cut = list(_range_runs(clip_ranges(ranges, 8 + 32 * 5 + 8, 8 + 32 * 9)))
    assert (6, 1, 0, 24) in cut and (7, 3, 24, 32) in cut


def test_clip_ranges_keeps_position_and_tiles_the_shard():
    ranges = ((0, 8), (8, 40), (40, 48), (48, 64))
    assert clip_ranges(ranges, 0, 64) == ranges
    assert clip_ranges(ranges, 16, 48) == ((0, 0), (0, 24), (24, 32), (32, 32))
    assert clip_ranges(ranges, 48, 64) == ((0, 0), (0, 0), (0, 0), (0, 16))


@pytest.mark.parametrize("n_shard", [1, 2, 4])
@pytest.mark.parametrize("per_leaf", [True, False], ids=["per_leaf", "one_range"])
@pytest.mark.parametrize("name", sorted(SPECS))
def test_helpers_match_segment_ops_on_every_shard(name, per_leaf, n_shard):
    ranges, row_leaf, rows = _ranges_and_index(name, per_leaf)
    k = len(ranges)
    per = rows // n_shard
    rng = np.random.default_rng(rows + n_shard)
    x = jnp.asarray(rng.normal(size=rows).astype(np.float32) ** 3)
    v = jnp.asarray(rng.normal(size=(3, k)).astype(np.float32))
    cut = 0
    for j in range(n_shard):
        lo, hi = j * per, (j + 1) * per
        local = clip_ranges(ranges, lo, hi)
        cut += sum(0 < b - a < rb - ra for (a, b), (ra, rb) in zip(local, ranges))
        idx = jnp.asarray(row_leaf[lo:hi])
        np.testing.assert_array_equal(
            np.asarray(leaf_reduce(x[lo:hi], local, "max")),
            np.asarray(jax.ops.segment_max(x[lo:hi], idx, num_segments=k)),
        )
        np.testing.assert_allclose(
            np.asarray(leaf_reduce(x[lo:hi], local, "sum")),
            np.asarray(jax.ops.segment_sum(x[lo:hi], idx, num_segments=k)),
            rtol=2e-5, atol=1e-4,  # f32 reassociation over at most 2^13 rows
        )
        for vv in (v, v[0]):
            np.testing.assert_array_equal(
                np.asarray(leaf_expand(vv, local)), np.asarray(vv[..., idx])
            )
    if n_shard > 1 and per_leaf and name != "one_tile_each":
        assert cut, "no leaf was cut by a shard boundary: the case tests nothing"


def test_helpers_trace_without_an_index_operand():
    """Under jit the helpers are slices, reshapes, reduces, broadcasts and one
    concatenate: nothing indexed."""
    ranges = _spec(SPECS["equal_runs"]).leaf_rows
    rows = ranges[-1][1]

    def f(x, v):
        return leaf_reduce(x, ranges, "max"), leaf_reduce(x, ranges, "sum"), leaf_expand(v, ranges)

    text = jax.jit(f).lower(jnp.zeros(rows), jnp.zeros((2, len(ranges)))).as_text()
    assert "gather" not in text and "scatter" not in text
    assert text.count("stablehlo.concatenate") == 3
