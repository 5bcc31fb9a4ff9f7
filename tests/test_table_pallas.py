"""Parity: the table-tier Pallas row kernels vs the pure-XLA table codec.

These are the PRODUCTION kernels — ops/table.py and parallel/ici.py dispatch
to them on TPU (round-2 verdict item 1: the benched kernels must be the
shipped kernels). Single-frame paths must match bit-for-bit; K-frame batch
sums may differ only by f32 summation order.

Runs in interpret mode on CPU (conftest forces JAX_PLATFORMS=cpu); with
ST_TEST_PLATFORM=tpu the same tests compile on a chip.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from shared_tensor_tpu.config import ScalePolicy
from shared_tensor_tpu.ops import table as T
from shared_tensor_tpu.ops.packing import dense_words, flat_words, words_rows


def _table(seed, shapes=((40, 70), (256,), (3, 5, 7)), scale_per_leaf=None):
    rng = np.random.default_rng(seed)
    tree = {}
    for i, s in enumerate(shapes):
        mag = 1.0 if scale_per_leaf is None else scale_per_leaf[i]
        tree[f"leaf{i}"] = (rng.normal(size=s) * mag).astype(np.float32)
    return tree


@pytest.mark.parametrize("per_leaf", [True, False])
@pytest.mark.parametrize(
    "policy", [ScalePolicy.POW2_RMS, ScalePolicy.RMS, ScalePolicy.ABS_MEAN]
)
def test_quantize_table_parity(per_leaf, policy):
    tree = _table(1, scale_per_leaf=[1.0, 1000.0, 0.001])
    spec = T.make_spec(tree)
    r = T.flatten(tree, spec)
    fg, rg = T.quantize_table(r, spec, policy, per_leaf, impl="xla")
    fp, rp = T.quantize_table(r, spec, policy, per_leaf, impl="pallas")
    np.testing.assert_array_equal(np.asarray(fp.scales), np.asarray(fg.scales))
    np.testing.assert_array_equal(np.asarray(fp.words), np.asarray(fg.words))
    np.testing.assert_array_equal(np.asarray(rp), np.asarray(rg))


def test_quantize_table_idle_leaf_parity():
    """A leaf whose residual is exactly zero idles (scale 0, residual kept)."""
    tree = {"a": np.ones((100,), np.float32), "b": np.zeros((2000,), np.float32)}
    spec = T.make_spec(tree)
    r = T.flatten(tree, spec)
    fg, rg = T.quantize_table(r, spec, impl="xla")
    fp, rp = T.quantize_table(r, spec, impl="pallas")
    assert float(fp.scales[1]) == 0.0
    np.testing.assert_array_equal(np.asarray(fp.scales), np.asarray(fg.scales))
    np.testing.assert_array_equal(np.asarray(fp.words), np.asarray(fg.words))
    np.testing.assert_array_equal(np.asarray(rp), np.asarray(rg))


def test_apply_table_many_parity():
    tree = _table(2)
    spec = T.make_spec(tree)
    r = T.flatten(tree, spec)
    frame, _ = T.quantize_table(r, spec, impl="xla")
    arrays = tuple(T.flatten(_table(10 + i), spec) for i in range(3))
    outs_g = T.apply_table_many(arrays, frame, spec, impl="xla")
    outs_p = T.apply_table_many(arrays, frame, spec, impl="pallas")
    for g, p in zip(outs_g, outs_p):
        np.testing.assert_array_equal(np.asarray(p), np.asarray(g))


@pytest.mark.parametrize("k", [1, 2, 5, 8])
def test_apply_table_batch_parity(k):
    tree = _table(3)
    spec = T.make_spec(tree)
    scales = []
    words = []
    r = T.flatten(tree, spec)
    for i in range(k):
        frame, r = T.quantize_table(r, spec, impl="xla")
        scales.append(np.asarray(frame.scales))
        words.append(np.asarray(frame.words))
    stacked = T.TableFrame(jnp.asarray(np.stack(scales)), jnp.asarray(np.stack(words)))
    arrays = (T.flatten(_table(30), spec), T.flatten(_table(31), spec))
    outs_g = T.apply_table_batch(arrays, stacked, spec, impl="xla")
    outs_p = T.apply_table_batch(arrays, stacked, spec, impl="pallas")
    for g, p in zip(outs_g, outs_p):
        # K-frame sums may round differently per f32 summation order
        np.testing.assert_allclose(np.asarray(p), np.asarray(g), rtol=1e-6, atol=1e-6)


def test_pallas_roundtrip_convergence():
    """Full sender->receiver loop on the Pallas tier alone: mixed-magnitude
    table converges to the target per-leaf (the README.md:41 capability).
    Uniform targets: the homogeneous regime where residual RMS halves per
    frame (SURVEY.md §6 convergence table)."""
    rng = np.random.default_rng(4)
    tree = {
        f"leaf{i}": (rng.uniform(-mag, mag, size=s)).astype(np.float32)
        for i, (s, mag) in enumerate(
            zip([(40, 70), (256,), (3, 5, 7)], [1.0, 500.0, 0.01])
        )
    }
    spec = T.make_spec(tree)
    r = T.flatten(tree, spec)
    v = jnp.zeros_like(r)
    for _ in range(80):
        frame, r = T.quantize_table(r, spec, impl="pallas")
        if not np.asarray(frame.scales).any():
            break
        v = T.apply_table_many((v,), frame, spec, impl="pallas")[0]
    target = T.flatten(tree, spec)
    # per-leaf relative convergence (each leaf's own magnitude is the yardstick)
    for leaf, got in zip(
        jax.tree.leaves(T.unflatten(target, spec)),
        jax.tree.leaves(T.unflatten(v, spec)),
    ):
        mag = float(np.abs(np.asarray(leaf)).max()) or 1.0
        np.testing.assert_allclose(
            np.asarray(got) / mag, np.asarray(leaf) / mag, rtol=0, atol=1e-4
        )


# --- the kernels derive per-row scale and live lanes from per-leaf scalars -----
#
# The row codec is handed scales per leaf and the static LeafRows; its Pallas
# tier (interpreter here) is held bit for bit to a plain NumPy statement of
# the rule at the same scales, and to its XLA twin.

#: name -> leaf sizes (elements). A grid block is 1024 rows, 32 rows of packed
#: words (32 table rows' words fill one); a leaf of n elements takes
#: ceil(n / 1024) * 8 rows.
_LEAF_TABLES = {
    # 1536 rows in one leaf: two blocks, each inside it
    "one_leaf_a_block": [1536 * 128 - 5],
    # 704 + 904 rows: the first block meets both leaves, the second one only
    "two_leaves_a_block": [704 * 128 - 77, 904 * 128],
    # 64 leaves of 8 rows (ResNet's BatchNorm leaves), one of them of a single
    # live element and one idling at scale 0, then 600 rows: every words row
    # of the run spans four leaves, and a 1024-row block meets 65 leaves
    "64_leaves_a_block": [1 + (37 * i) % 1024 for i in range(63)] + [1, 600 * 128 - 1],
    # 1288 rows, 8 (mod 32): the last words row holds 8 table rows and 96
    # lanes of pad bits; the last block is 264 rows of 1024
    "rows_8_mod_32": [1288 * 128 - 3],
    # 520 + 528 = 1048 rows, 24 (mod 256) and (mod 32): the last block is 24
    # rows, less than one words row, inside the second leaf
    "rows_24_mod_256": [520 * 128 - 9, 528 * 128],
}


def _leaf_table(name, seed):
    sizes = _LEAF_TABLES[name]
    spec = T.make_spec({f"l{i:03d}": np.zeros(n, np.float32) for i, n in enumerate(sizes)})
    rng = np.random.default_rng(seed)
    live = np.repeat(spec.live_rowcount(), 128) > np.tile(np.arange(128), spec.total // 128)
    return spec, rng, live


def _scales(spec, rng, k=None):
    """Distinct per-leaf scales, not powers of two; leaf 1 (or the only leaf's
    second frame) idles at scale 0."""
    s = rng.uniform(0.1, 3.0, size=(k or 1, spec.num_leaves)).astype(np.float32)
    s[-1, min(1, spec.num_leaves - 1)] = 0.0
    return s if k else s[0]


def _per_element(v, spec):
    return np.repeat(np.asarray(v, np.float32), spec.padded)


def _np_quantize(scales, spec, live, r):
    s = _per_element(scales, spec)
    neg = r <= 0
    words = np.packbits(neg & live, bitorder="little").view("<u4")
    sent = np.where(neg, -s, s)
    return words, np.where(live & (s > 0), r - sent, np.where(live, r, 0)).astype(np.float32)


def _np_apply(scales, spec, live, words, a):
    delta = np.zeros(spec.total, np.float32)
    for s, w in zip(scales, words):  # frame by frame, as the kernel sums them
        bits = np.unpackbits(np.ascontiguousarray(w).view(np.uint8), bitorder="little")
        delta = delta + _per_element(s, spec) * (np.float32(1) - np.float32(2) * bits)
    return np.where(live, np.clip(a + np.where(live, delta, 0), -T.SAT, T.SAT), 0).astype(np.float32)


@pytest.mark.parametrize("name", list(_LEAF_TABLES))
def test_quantize_rows_from_leaf_scalars(name):
    spec, rng, live = _leaf_table(name, 11)
    leaves = T.LeafRows.of(spec)
    scales = _scales(spec, rng)
    # residual with garbage in the padding lanes: the pass forces them to 0
    r = rng.normal(size=spec.total).astype(np.float32)
    want_w, want_r = _np_quantize(scales, spec, live, r)
    for impl in ("pallas", "xla"):
        w, r2 = T.quantize_rows(jnp.asarray(scales), leaves, None, jnp.asarray(r), impl)
        assert w.shape == (words_rows(leaves.rows), 128)
        # the wire's flat word vector, then nothing but zero pad bits
        np.testing.assert_array_equal(np.asarray(w).reshape(-1)[: want_w.size], want_w, err_msg=impl)
        assert not np.asarray(w).reshape(-1)[want_w.size:].any(), impl
        np.testing.assert_array_equal(np.asarray(r2), want_r, err_msg=impl)


@pytest.mark.parametrize("k", [1, 2, 4, 5, 16])
@pytest.mark.parametrize("name", list(_LEAF_TABLES))
def test_apply_rows_from_leaf_scalars(name, k):
    spec, rng, live = _leaf_table(name, 12)
    leaves = T.LeafRows.of(spec)
    scales = _scales(spec, rng, k)
    words = rng.integers(0, 2**32, size=(k, spec.total // 32), dtype=np.uint32)
    arrays = tuple(rng.normal(size=spec.total).astype(np.float32) for _ in range(2))
    outs = {
        impl: T.apply_rows(
            jnp.asarray(scales), leaves, None, dense_words(jnp.asarray(words), leaves.rows),
            tuple(jnp.asarray(a) for a in arrays), impl,
        )
        for impl in ("pallas", "xla")
    }
    for a, got_p, got_x in zip(arrays, outs["pallas"], outs["xla"]):
        np.testing.assert_array_equal(np.asarray(got_p), _np_apply(scales, spec, live, words, a))
        if k == 1:
            np.testing.assert_array_equal(np.asarray(got_x), np.asarray(got_p))
        else:  # the XLA twin sums the K frames in its own order
            np.testing.assert_allclose(np.asarray(got_x), np.asarray(got_p), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize(
    "name", ["two_leaves_a_block", "64_leaves_a_block", "rows_24_mod_256"]
)
def test_row_codec_on_a_window_that_cuts_a_leaf(name):
    """Two windows of the table's rows, as two shards hold them: the cut falls
    inside a leaf, and each window's pass equals the whole table's on its
    rows (tables picked from the static [n_windows, ...] ones by index)."""
    spec, rng, live = _leaf_table(name, 13)
    halves = T.LeafRows.of(spec, 2)
    cut = halves.rows
    assert any(a < cut < b for a, b in spec.leaf_rows)
    scales = _scales(spec, rng, 4)
    r = rng.normal(size=spec.total).astype(np.float32)
    words = rng.integers(0, 2**32, size=(4, spec.total // 32), dtype=np.uint32)
    v = rng.normal(size=spec.total).astype(np.float32)
    want_w, want_r = _np_quantize(scales[0], spec, live, r)
    want_v = _np_apply(scales, spec, live, words, v)
    for w in (0, 1):
        el = slice(w * cut * 128, (w + 1) * cut * 128)
        wd = slice(w * cut * 4, (w + 1) * cut * 4)
        for impl in ("pallas", "xla"):
            got_w, got_r = T.quantize_rows(
                jnp.asarray(scales[0]), halves, jnp.int32(w), jnp.asarray(r[el]), impl
            )
            np.testing.assert_array_equal(np.asarray(flat_words(got_w, cut)), want_w[wd])
            np.testing.assert_array_equal(np.asarray(got_r), want_r[el])
        (got_v,) = T.apply_rows(
            jnp.asarray(scales), halves, jnp.int32(w),
            dense_words(jnp.asarray(words[:, wd]), cut), (jnp.asarray(v[el]),), "pallas",
        )
        np.testing.assert_array_equal(np.asarray(got_v), want_v[el])


def test_leaf_tables_name_the_leaves_each_block_meets():
    spec, _, _ = _leaf_table("64_leaves_a_block", 0)
    whole = T.LeafRows.of(spec).tables(512)
    assert whole.leaves_max == 64
    np.testing.assert_array_equal(whole.first, [0, 64, 64])
    np.testing.assert_array_equal(whole.last, [63, 64, 64])
    assert int(whole.end[63]) == 63 * 1024 + 1  # the leaf of one live element
    halves = T.LeafRows.of(spec, 2)  # 556 rows a window
    second = halves.tables(512, jnp.int32(1))
    np.testing.assert_array_equal(second.first, [64, 64])
    assert int(second.lo[64]) == 0 and int(second.end[64]) == 556 * 128 - 1
    assert int(second.end[0]) == 0  # a leaf before the window: nothing live


@pytest.mark.parametrize("per_leaf", [True, False])
def test_ici_sync_step_pallas_parity(per_leaf):
    """The fused pod sync step built on the Pallas tier matches the XLA tier
    exactly (same state in, same state out) on a (4 peers x 2 shards) mesh,
    whose shard boundary cuts the first leaf; per leaf and with the one
    global scale."""
    from shared_tensor_tpu.ops.table import make_spec, flatten
    from shared_tensor_tpu.parallel.ici import build_sync_step, init_state
    from shared_tensor_tpu.parallel.mesh import make_mesh

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    mesh = make_mesh(4, 2)
    tree = _table(5, scale_per_leaf=[1.0, 100.0, 0.01])
    spec = make_spec(tree)
    rng = np.random.default_rng(6)
    upd = jnp.asarray(
        np.stack([np.asarray(flatten(_table(7 + p), spec)) for p in range(4)])
    )

    def run(impl):
        state = init_state(mesh, spec, template=tree)
        from shared_tensor_tpu.parallel.ici import add_updates

        state = add_updates(state, upd)
        step = build_sync_step(mesh, spec, per_leaf=per_leaf, impl=impl)
        for _ in range(3):
            state, scales = step(state)
        return np.asarray(state.values), np.asarray(state.residual), np.asarray(scales)

    vg, rg, sg = run("xla")
    vp, rp, sp = run("pallas")
    np.testing.assert_array_equal(sp, sg)
    np.testing.assert_array_equal(rp, rg)
    # values accumulate (n_peer-1) frame deltas per step; summation order may
    # differ between the XLA sum-reduction and the kernel's sequential loop
    np.testing.assert_allclose(vp, vg, rtol=1e-6, atol=1e-6)
