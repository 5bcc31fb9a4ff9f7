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


def plain(q, k, v):
    """``(o, lse)`` of causal softmax attention, float32 at precision
    ``highest``, the ``[H, T, T]`` scores whole."""
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    s = jnp.einsum("hqd,hkd->hqk", q, k, precision="highest") / np.sqrt(q.shape[-1])
    t = q.shape[1]
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    o = jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(s, axis=-1), v, precision="highest")
    return o, jax.nn.logsumexp(s, axis=-1)


@functools.lru_cache(maxsize=None)
def operands(h, t, d_qk, d_v):
    keys = jax.random.split(jax.random.key(t + d_qk), 4)
    return tuple(jax.random.normal(key, (h, t, d), jnp.float32).astype(jnp.bfloat16)
                 for key, d in zip(keys, (d_qk, d_qk, d_v, d_v)))


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
    arg = lambda t, d: jax.ShapeDtypeStruct((32, t, d), jnp.bfloat16)
    assert A.takes(arg(8192, 192), arg(8192, 192), arg(8192, 128))
    assert not A.takes(arg(1 << 16, 192), arg(1 << 16, 192), arg(1 << 16, 128))
