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


def test_ici_sync_step_pallas_parity():
    """The fused pod sync step built on the Pallas tier matches the XLA tier
    exactly (same state in, same state out) on a (4 peers x 2 shards) mesh."""
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
        step = build_sync_step(mesh, spec, impl=impl)
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
