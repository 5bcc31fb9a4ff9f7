"""The benchmark's plain references against the program at a small size on
the CPU, and the yardstick's counts against hand-worked figures."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import counts, harness  # noqa: E402
from chipbench.jobs import table_sync  # noqa: E402
from chipbench.reference import codec_np, resnet_ref  # noqa: E402
from shared_tensor_tpu.models import resnet  # noqa: E402
from shared_tensor_tpu.ops import table  # noqa: E402


def _config(name):
    with open(os.path.join(ROOT, "chipbench", "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("n_peer", [1, 3])
def test_numpy_codec_equals_ops_table(n_peer):
    """quantize_table + apply_table on a three-leaf table (one leaf with
    padding, magnitudes three decades apart) against codec_np, bit for bit in
    scale, bits and residual."""
    rng = np.random.default_rng(7)
    tree = {"a": np.zeros((8, 128), np.float32), "b": np.zeros((300,), np.float32),
            "c": np.zeros((16, 64), np.float32)}
    spec = table.make_spec(tree)
    mags = {"a": 1e-1, "b": 1e-4, "c": 3e-3}
    resid = [table.flatten({k: (rng.standard_normal(v.shape) * mags[k]).astype(np.float32)
                            for k, v in tree.items()}, spec) for _ in range(n_peer)]
    values = [table.flatten({k: rng.standard_normal(v.shape).astype(np.float32)
                             for k, v in tree.items()}, spec) for _ in range(n_peer)]
    frames, new_r = zip(*(table.quantize_table(r, spec, impl="xla") for r in resid))
    off = np.concatenate([[0], np.cumsum(spec.padded)])
    for i, n in enumerate(spec.ns):
        sl = slice(off[i], off[i] + n)
        v = np.stack([np.asarray(x)[sl] for x in values])
        r = np.stack([np.asarray(x)[sl] for x in resid])
        v2, r2, scales = codec_np.sync_step(v, r)
        for p in range(n_peer):
            assert np.float32(frames[p].scales[i]) == scales[p]
            assert np.array_equal(np.asarray(new_r[p])[sl], r2[p])
            got = values[p]
            for q in range(n_peer):
                if q != p:
                    got = table.apply_table(got, frames[q], spec)
            np.testing.assert_allclose(np.asarray(got)[sl], v2[p], rtol=0, atol=1e-6)


def test_pow2_floor_and_the_undecidable_band():
    x = np.array([1.0, 1.5, 2.0, 0.75, 3e-39], np.float32)
    assert codec_np.pow2_floor(x).tolist() == [1.0, 1.0, 2.0, 0.5, 0.0]
    assert codec_np.leaf_scale(np.zeros(5, np.float32)) == 0.0
    assert codec_np.near_pow2(1.0 + 1e-7) and codec_np.near_pow2(2.0 - 1e-6)
    assert not codec_np.near_pow2(1.5) and not codec_np.near_pow2(0.0)


def test_resnet_reference_agrees_with_the_program_at_a_small_size():
    """float32 'highest' reference against the program's bfloat16
    convolutions on the rehearsal model; the chip run makes the same
    comparison at the paper's widths (configuration's reference_loss_tol)."""
    cfg = _config("resnet18-imagenet")
    model = cfg["rehearsal"]["model"]
    rcfg = resnet.ResNetConfig(
        stages=tuple(model["stages"]), width=model["width"], classes=model["classes"],
        stem_kernel=model["stem_kernel"], stem_stride=model["stem_stride"],
        stem_pool=model["stem_pool"])
    params = resnet.init_params(jax.random.key(1), rcfg)
    params = dict(params, blocks=[dict(b, scale2=jnp.ones_like(b["scale2"]))
                                  for b in params["blocks"]])
    k1, k2 = jax.random.split(jax.random.key(2))
    batch = (jax.random.normal(k1, (8, 32, 32, 3)), jax.random.randint(k2, (8,), 0, 10))
    got = float(resnet.loss_fn(params, batch, rcfg))
    want = float(resnet_ref.loss(params, batch, model))
    assert abs(got - want) <= cfg["checks"]["reference_loss_tol"]
    logits = resnet_ref.forward(params, batch[0], model)
    assert logits.shape == (8, 10) and logits.dtype == jnp.float32


def test_resnet18_flops_match_the_published_count():
    model = _config("resnet18-imagenet")["model"]
    fwd = counts.resnet_forward_flops(model)
    # torchvision's resnet18: 1.814 G multiply-adds at 224x224
    assert fwd == pytest.approx(2 * 1.814e9, rel=0.005)
    train = counts.resnet_train_flops(model)
    stem = 2 * 112 * 112 * 7 * 7 * 3 * 64
    assert train == 3 * fwd - stem
    # XLA counts 320 GFLOP for the fused step at batch 32 (ISSUE 25)
    assert train * 32 == pytest.approx(320e9, rel=0.08)


def test_kernel_and_wire_bytes():
    total = 1024 * 128
    assert counts.quantize_rows_bytes(total) == 8 * total + total // 8 + 8 * 1024
    assert counts.apply_rows_batch_bytes(total, 4) == 8 * total + 4 * (total // 8 + 4096) + 4096
    assert counts.sync_step_kernel_bytes(total, 1) == (
        counts.quantize_rows_bytes(total) + counts.apply_rows_batch_bytes(total, 1))
    from shared_tensor_tpu.parallel.ici import frame_ici_bytes

    spec = table.make_spec({"a": np.zeros((8, 128)), "b": np.zeros((300,))})
    assert counts.frame_ici_bytes(spec.total, spec.num_leaves, 4) == frame_ici_bytes(spec, 4)


def test_olmoe_layer_layout_is_the_checkpoints():
    cfg = _config("olmoe-layer-table")
    layout = table_sync.leaf_layout(cfg, rehearsal=False)
    assert len(layout) == cfg["table"]["expect_leaves"] == 201
    assert sum(int(np.prod(s)) for s in layout.values()) == cfg["table"]["expect_elements"]
    assert layout["mlp.experts.63.down_proj.weight"] == (2048, 1024)
    assert layout["mlp.gate.weight"] == (64, 2048)
    assert len(table_sync.leaf_layout(cfg, rehearsal=True)) == 21


def test_every_seed_streams_the_same_magnitudes_in_another_order():
    ns = [4, 4, 4, 9, 9, 2]
    a = table_sync.leaf_magnitudes(ns, 1e-5, 1e-2, seed=1)
    b = table_sync.leaf_magnitudes(ns, 1e-5, 1e-2, seed=2**31 + 77)
    assert sorted(a[:3]) == sorted(b[:3]) and sorted(a[3:5]) == sorted(b[3:5])
    assert a[5] == b[5] == pytest.approx(np.sqrt(1e-5 * 1e-2))
    assert a.min() == pytest.approx(1e-5) and a.max() == pytest.approx(1e-2)
    assert not np.array_equal(
        table_sync.leaf_magnitudes([7] * 64, 1e-5, 1e-2, 1),
        table_sync.leaf_magnitudes([7] * 64, 1e-5, 1e-2, 2))


def test_host_clock_statistics():
    done = [0.0, 0.1, 0.2, 0.3, 0.6, 0.7, 0.8]
    assert harness.grouped_step_ms(done, 0.25) == pytest.approx([100.0, 300.0])
    assert harness.grouped_step_ms([0.0, 0.1], 0.25) == []
    assert harness.percentile(list(range(1, 101)), 95) == 95
    assert harness.percentile([3.0], 95) == 3.0
    assert harness.percentile([1.0, 2.0], 50) == 1.0
