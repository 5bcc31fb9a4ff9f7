"""Bit-for-bit parity: the Pallas row kernels vs the pure-JAX scalar golden codec.

A single-leaf table of ``n`` elements goes through the table codec pinned to
the kernels (``impl="pallas"``: ``codec_pallas.quantize_rows`` /
``apply_rows_batch`` behind ``ops/table.py``'s row codec) and must reproduce
``ops/codec.py`` exactly, at ragged live-lane counts (the last live row of
``n = 17`` holds 17 lanes, the rest of the tile none).

Runs in interpret mode on CPU (conftest forces JAX_PLATFORMS=cpu); the same
kernels compile for the chip in tests/test_tpu_compile.py.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from shared_tensor_tpu.config import ScalePolicy
from shared_tensor_tpu.ops import codec
from shared_tensor_tpu.ops.packing import padded_len
from shared_tensor_tpu.ops.table import (
    TableFrame,
    apply_table_many,
    make_spec,
    quantize_table,
)


def _spec(n):
    return make_spec(np.zeros(n, np.float32))


def _rand_resid(n, seed, scale=1.0):
    rng = np.random.default_rng(seed)
    n_pad = padded_len(n)
    r = np.zeros(n_pad, dtype=np.float32)
    r[:n] = (rng.normal(size=n) * scale).astype(np.float32)
    return r


def _quantize(r, n, policy=ScalePolicy.POW2_RMS):
    return quantize_table(jnp.asarray(r), _spec(n), policy, impl="pallas")


def _apply_many(arrays, frame, n):
    """The scalar golden frame, applied by the row kernel."""
    tframe = TableFrame(jnp.reshape(frame.scale, (1,)), frame.words)
    return apply_table_many(arrays, tframe, _spec(n), impl="pallas")


#: 1048 rows, 24 (mod 256) and (mod 32): a last grid block of less than one
#: words row; 1288 rows, 8 (mod 32): a last words row of 8 table rows and 96
#: lanes of pad bits, in a last block of 264 rows
_ROWS_24_MOD_256 = 1048 * 128 - 7
_ROWS_8_MOD_32 = 1288 * 128 - 3


@pytest.mark.parametrize("n", [17, 240, 1024, 4096, 40000, _ROWS_24_MOD_256, _ROWS_8_MOD_32])
def test_quantize_parity(n):
    """The wire's flat word vector is the golden codec's, bit for bit,
    whatever the kernel's own layout of the words is."""
    r = _rand_resid(n, n)
    frame_g, resid_g = codec.quantize(jnp.asarray(r), n)
    frame_p, resid_p = _quantize(r, n)
    assert float(frame_p.scales[0]) == float(frame_g.scale)
    np.testing.assert_array_equal(np.asarray(frame_p.words), np.asarray(frame_g.words))
    np.testing.assert_array_equal(np.asarray(resid_p), np.asarray(resid_g))


@pytest.mark.parametrize("policy", [ScalePolicy.POW2_RMS, ScalePolicy.RMS, ScalePolicy.ABS_MEAN])
def test_quantize_parity_policies(policy):
    n = 3000
    r = _rand_resid(n, 5)
    frame_g, resid_g = codec.quantize(jnp.asarray(r), n, policy)
    frame_p, resid_p = _quantize(r, n, policy)
    s = np.float32(frame_p.scales[0])
    np.testing.assert_array_equal(np.asarray(frame_p.words), np.asarray(frame_g.words))
    if policy == ScalePolicy.POW2_RMS:
        assert s == float(frame_g.scale)
        np.testing.assert_array_equal(np.asarray(resid_p), np.asarray(resid_g))
    else:
        # the table sums the moment by row, then by leaf, the scalar codec in
        # one reduction: without the power-of-2 floor the two scales may
        # differ in the last places. The kernel is held to the golden rule at
        # the scale it was given.
        np.testing.assert_allclose(s, float(frame_g.scale), rtol=1e-6)
        live = np.arange(r.shape[0]) < n
        expect = np.where(live, r - np.where(r <= 0, -s, s), np.float32(0))
        np.testing.assert_array_equal(np.asarray(resid_p), expect)


def test_quantize_zero_residual_parity():
    n = 1024
    z = jnp.zeros(padded_len(n), jnp.float32)
    frame_g, _ = codec.quantize(z, n)
    frame_p, resid_p = _quantize(z, n)
    assert float(frame_p.scales[0]) == 0.0
    np.testing.assert_array_equal(np.asarray(frame_p.words), np.asarray(frame_g.words))
    np.testing.assert_array_equal(np.asarray(resid_p), 0.0)


@pytest.mark.parametrize("n", [17, 1024, 40000, _ROWS_24_MOD_256, _ROWS_8_MOD_32])
def test_apply_parity(n):
    r = _rand_resid(n, n + 1)
    v = _rand_resid(n, n + 2)
    frame, _ = codec.quantize(jnp.asarray(r), n)
    out_g = codec.apply_frame(jnp.asarray(v), frame, n)
    (out_p,) = _apply_many((jnp.asarray(v),), frame, n)
    np.testing.assert_array_equal(np.asarray(out_p), np.asarray(out_g))


def test_apply_many_parity():
    n = 5000
    r = _rand_resid(n, 30)
    frame, _ = codec.quantize(jnp.asarray(r), n)
    arrays = tuple(jnp.asarray(_rand_resid(n, 40 + i)) for i in range(3))
    outs_g = codec.apply_frame_many(arrays, frame, n)
    outs_p = _apply_many(arrays, frame, n)
    for g, p in zip(outs_g, outs_p):
        np.testing.assert_array_equal(np.asarray(p), np.asarray(g))


def test_link_convergence_with_pallas():
    """Full link loop driven by the row kernels: exact convergence holds."""
    rng = np.random.default_rng(50)
    n = 2048
    spec = _spec(n)
    target = rng.uniform(-1, 1, size=n).astype(np.float32)
    r = jnp.asarray(target)
    v = jnp.zeros(n, dtype=jnp.float32)
    for _ in range(40):
        frame, r = quantize_table(r, spec, impl="pallas")
        if float(frame.scales[0]) == 0.0:
            break
        (v,) = apply_table_many((v,), frame, spec, impl="pallas")
    assert float(jnp.max(jnp.abs(r))) == 0.0
    np.testing.assert_allclose(np.asarray(v), target, rtol=0, atol=1.5e-7)


def _equations(jaxpr):
    """Equations of a jaxpr and of every jaxpr inside it."""
    from jax import core

    return sum(
        1 + sum(_equations(sub) for sub in core.jaxprs_in_params(eqn.params))
        for eqn in jaxpr.eqns
    )


def test_apply_kernel_trace_does_not_grow_with_frames():
    """The set-up guard (PERF.md section 6, PR 36 and PR 37): the apply
    kernel's traced size is the frame loop's one body, not K copies of it.
    At K = 16 (two groups of eight frames) the traced call holds no more
    than 2 times the equations it holds at K = 1."""
    import jax

    from shared_tensor_tpu.ops.table import _apply_table_batch

    spec = _spec(3000 * 128)
    counts = {}
    for k in (1, 16):
        frames = TableFrame(
            jnp.ones((k, 1), jnp.float32), jnp.zeros((k, spec.total // 32), jnp.uint32)
        )
        jaxpr = jax.make_jaxpr(
            lambda a, f: _apply_table_batch.__wrapped__((a,), f, spec=spec, impl="pallas")
        )(jnp.zeros(spec.total, jnp.float32), frames)
        counts[k] = _equations(jaxpr.jaxpr)
    assert counts[1] > 50  # the kernel's body is counted, not the call alone
    assert counts[16] <= 2 * counts[1], counts
