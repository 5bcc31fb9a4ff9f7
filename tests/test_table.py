"""Table sync (per-leaf scale) tests — the reference README.md:41 TODO turned
capability, exercised against the single-scale golden codec and the
mixed-magnitude failure mode it fixes (BASELINE.md)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shared_tensor_tpu.ops import codec
from shared_tensor_tpu.ops.packing import padded_len, unpack_bits
from shared_tensor_tpu.ops.table import (
    TableFrame,
    accumulate_table,
    apply_table_batch,
    apply_table,
    apply_table_many,
    flatten,
    make_spec,
    quantize_table,
    unflatten,
)


def _tree(seed=0, scales=(1.0, 1.0, 1.0)):
    # uniform data: converges to exact zero quickly (gaussian tails take
    # hundreds of frames, same as the C reference — see BASELINE.md)
    rng = np.random.default_rng(seed)
    return {
        "w": (rng.uniform(-1, 1, size=(40, 30)) * scales[0]).astype(np.float32),
        "b": (rng.uniform(-1, 1, size=(77,)) * scales[1]).astype(np.float32),
        "emb": (rng.uniform(-1, 1, size=(10, 11, 3)) * scales[2]).astype(np.float32),
    }


def test_flatten_roundtrip():
    t = _tree()
    spec = make_spec(t)
    flat = flatten(t, spec)
    assert flat.shape[0] == spec.total and spec.total % 1024 == 0
    back = unflatten(flat, spec)
    for k in t:
        np.testing.assert_array_equal(np.asarray(back[k]), t[k])
    # padding invariant
    live = flat.shape[0]
    assert spec.total_n == sum(v.size for v in t.values())


def test_single_leaf_matches_scalar_codec():
    """A one-leaf table must reproduce codec.quantize bit-for-bit."""
    rng = np.random.default_rng(3)
    n = 3000
    x = rng.normal(size=n).astype(np.float32)
    spec = make_spec(x)
    flat = flatten(x, spec)
    tframe, tresid = quantize_table(flat, spec)

    n_pad = padded_len(n)
    r = np.zeros(n_pad, np.float32)
    r[:n] = x
    gframe, gresid = codec.quantize(jnp.asarray(r), n)

    assert float(tframe.scales[0]) == float(gframe.scale)
    np.testing.assert_array_equal(np.asarray(tframe.words), np.asarray(gframe.words))
    np.testing.assert_array_equal(np.asarray(tresid), np.asarray(gresid))


def test_per_leaf_scales_differ():
    t = _tree(seed=1, scales=(1000.0, 1.0, 0.001))
    spec = make_spec(t)
    frame, _ = quantize_table(flatten(t, spec), spec)
    s = np.asarray(frame.scales)
    # dict leaves flatten in sorted key order: b (x1), emb (x0.001), w (x1000)
    assert s[2] > 100 * s[0] > 100 * s[1] > 0


def test_table_link_convergence():
    """One-way link over a mixed-magnitude table: with per-leaf scales, BOTH
    magnitude groups converge fast — the exact scenario that stalls the
    reference's single global scale (BASELINE.md: 24% error after 48 frames;
    here every leaf is exact after ~35)."""
    t = _tree(seed=2, scales=(1000.0, 1.0, 0.001))
    spec = make_spec(t)
    target = flatten(t, spec)
    resid = target
    values = jnp.zeros(spec.total, jnp.float32)
    for _ in range(64):
        frame, resid = quantize_table(resid, spec)
        if not bool(jnp.any(frame.scales > 0)):
            break
        values = apply_table(values, frame, spec)
    got = unflatten(values, spec)
    for k in t:
        tol = 1e-5 * max(1.0, float(np.abs(t[k]).max()))
        np.testing.assert_allclose(np.asarray(got[k]), t[k], rtol=0, atol=tol)


def test_idle_leaf_keeps_residual():
    """A leaf with zero residual idles (scale 0) while other leaves stream."""
    t = {"a": np.zeros(100, np.float32), "b": np.ones(100, np.float32)}
    spec = make_spec(t)
    frame, resid = quantize_table(flatten(t, spec), spec)
    s = np.asarray(frame.scales)
    assert s[0] == 0.0 and s[1] > 0
    back = unflatten(resid, spec)
    np.testing.assert_array_equal(np.asarray(back["a"]), 0.0)


def test_apply_many_and_accumulate():
    t = _tree(seed=4)
    spec = make_spec(t)
    flat = flatten(t, spec)
    frame, _ = quantize_table(flat, spec)
    a1 = jnp.zeros(spec.total, jnp.float32)
    a2 = flat
    o1, o2 = apply_table_many((a1, a2), frame, spec)
    e1 = apply_table(a1, frame, spec)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(e1))

    u1, u2 = accumulate_table((a1, a2), flat, spec)
    np.testing.assert_allclose(np.asarray(u1), np.asarray(flat))
    np.testing.assert_allclose(np.asarray(u2), np.asarray(flat) * 2)


def test_accumulate_sanitizes_table():
    t = {"a": np.ones(10, np.float32)}
    spec = make_spec(t)
    bad = np.full(10, np.nan, np.float32)
    flat = flatten({"a": np.ones(10, np.float32)}, spec)
    out, = accumulate_table((flat,), flatten({"a": bad}, spec), spec)
    np.testing.assert_array_equal(np.asarray(unflatten(out, spec)["a"]), 1.0)


def test_global_scale_mode():
    """per_leaf=False: one scale over the whole table (reference behavior),
    replicated across the frame's scales vector."""
    t = _tree(seed=7, scales=(1000.0, 1.0, 0.001))
    spec = make_spec(t)
    frame, _ = quantize_table(flatten(t, spec), spec, per_leaf=False)
    s = np.asarray(frame.scales)
    assert s[0] == s[1] == s[2] > 0


def test_flatten_rejects_wrong_sizes():
    t = _tree(seed=8)
    spec = make_spec(t)
    bad = dict(t)
    bad["b"] = np.zeros(12, np.float32)
    try:
        flatten(bad, spec)
        raise AssertionError("expected ValueError")
    except ValueError as e:
        assert "elements" in str(e)


def test_flatten_rejects_wrong_structure():
    t = _tree(seed=9)
    spec = make_spec(t)
    as_list = list(t.values())  # same leaf sizes, different structure
    try:
        flatten(as_list, spec)
        raise AssertionError("expected ValueError")
    except ValueError as e:
        assert "structure" in str(e)


def test_apply_table_batch_matches_sequential():
    """Batched K-frame apply (one dispatch) must equal K sequential applies
    — and zero-scale padding frames must be exact no-ops."""
    import jax

    from shared_tensor_tpu.config import ScalePolicy
    from shared_tensor_tpu.ops.table import TableFrame, apply_table_batch

    tpl = {
        "a": jax.random.normal(jax.random.key(0), (37,)),
        "b": jax.random.normal(jax.random.key(1), (5, 9)) * 100.0,
    }
    spec = make_spec(tpl)
    frames = []
    resid = flatten(tpl, spec)  # live-masked by construction
    for _ in range(5):
        f, resid = quantize_table(resid, spec, ScalePolicy.POW2_RMS, True)
        frames.append(f)

    values0 = flatten({"a": jnp.zeros((37,)), "b": jnp.zeros((5, 9))}, spec)
    seq = values0
    for f in frames:
        seq = apply_table(seq, f, spec)

    # pad with 3 zero-scale no-op frames to k=8
    k = 8
    scales = np.zeros((k, spec.num_leaves), np.float32)
    words = np.zeros((k, spec.total // 32), np.uint32)
    for i, f in enumerate(frames):
        scales[i] = np.asarray(f.scales)
        words[i] = np.asarray(f.words)
    words[6] = 0xFFFFFFFF  # garbage bits under zero scale must not matter
    stacked = TableFrame(jnp.asarray(scales), jnp.asarray(words))
    (batched,) = apply_table_batch((values0,), stacked, spec)
    np.testing.assert_allclose(np.asarray(batched), np.asarray(seq), rtol=1e-6, atol=1e-6)


def test_receive_frames_batch_floods_other_links():
    """core.receive_frames applies the summed delta to the replica AND other
    links' residuals (split horizon), identically to one-at-a-time."""
    import numpy as np

    from shared_tensor_tpu.config import ScalePolicy
    from shared_tensor_tpu.core import SharedTensor
    from shared_tensor_tpu.ops.table import quantize_table

    tpl = {"w": jnp.zeros((64,), jnp.float32)}
    sender = SharedTensor(tpl)
    sender.new_link(1, seed=False)
    sender.add({"w": jnp.linspace(-1, 1, 64)})

    frames = [sender.make_frame(1) for _ in range(4)]
    frames = [f for f in frames if f is not None]

    a = SharedTensor(tpl)
    a.new_link(1, seed=False)
    a.new_link(2, seed=False)
    b = SharedTensor(tpl)
    b.new_link(1, seed=False)
    b.new_link(2, seed=False)

    for f in frames:
        a.receive_frame(1, f)
    b.receive_frames(1, frames)

    np.testing.assert_allclose(
        np.asarray(a.snapshot_flat()), np.asarray(b.snapshot_flat()), atol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(a._links[2]), np.asarray(b._links[2]), atol=1e-6
    )
    assert b.frames_in == len(frames)


def _largest_constant(lowered_text):
    """Element count of the largest ``stablehlo.constant`` in a lowered text."""
    largest = 0
    for line in lowered_text.splitlines():
        if "stablehlo.constant" not in line:
            continue
        dims = re.findall(r"(\d+)x", line.rsplit("tensor<", 1)[1])
        largest = max(largest, int(np.prod([int(d) for d in dims])) if dims else 1)
    return largest


@pytest.mark.parametrize("program", ["quantize_table", "apply_table_batch", "accumulate_table"])
def test_xla_tier_programs_hold_no_table_sized_constant(program):
    """The live mask comes from ``live_rowcount`` (one int a row), compared
    with a lane index on the device: no program bakes in a ``bool[total]``
    (420 MB at an OLMoE layer's size)."""
    spec = make_spec({"w": np.zeros((300, 77), np.float32), "b": np.zeros((77,), np.float32)})
    flat = jax.ShapeDtypeStruct((spec.total,), jnp.float32)
    frames = TableFrame(
        jax.ShapeDtypeStruct((3, spec.num_leaves), jnp.float32),
        jax.ShapeDtypeStruct((3, spec.total // 32), jnp.uint32),
    )
    lowered = {
        "quantize_table": lambda: jax.jit(
            lambda r: quantize_table(r, spec, impl="xla")).lower(flat),
        "apply_table_batch": lambda: jax.jit(
            lambda a, f: apply_table_batch((a,), f, spec, impl="xla")).lower(flat, frames),
        "accumulate_table": lambda: jax.jit(
            lambda a, u: accumulate_table((a,), u, spec)).lower(flat, flat),
    }[program]()
    assert _largest_constant(lowered.as_text()) <= spec.total // 128
