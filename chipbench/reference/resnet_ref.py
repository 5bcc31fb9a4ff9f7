"""ResNet-18 as the paper and torchvision describe it, in plain float32
``jax.numpy``: the reference the training cells' loss is held to.

He et al., arXiv:1512.03385, Table 1 (18-layer column): a 7x7 stride-2 stem
with BatchNorm, ReLU and a 3x3 stride-2 max-pool, four stages of two basic
blocks (two 3x3 convolutions, each followed by BatchNorm, identity or 1x1
projection shortcut, ReLU after the sum), widths 64/128/256/512, global
average pool, a linear head. Departures, both the program's and stated in
the configuration's ``assumed``: BatchNorm uses the batch's statistics, and a
projection shortcut carries no BatchNorm.

Nothing of ``shared_tensor_tpu`` is imported. Every product runs at
``precision="highest"`` under ``jax.default_matmul_precision("highest")``,
because a float32 product on a TPU is otherwise computed in bfloat16 passes.
The parameter pytree is the program's (``stem``/``blocks``/``head``), read by
key: the layout is the interface, the arithmetic is this file's own.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

EPS = 1e-5


def _conv(x, w, stride):
    return jax.lax.conv_general_dilated(
        x.astype(jnp.float32), w.astype(jnp.float32),
        window_strides=(stride, stride), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST,
    )


def _bn(x, scale, bias):
    mean = jnp.mean(x, axis=(0, 1, 2), keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2), keepdims=True)
    return (x - mean) / jnp.sqrt(var + EPS) * scale + bias


def forward(params, images, model: dict):
    """float32[N, H, W, 3] -> logits float32[N, classes]."""
    with jax.default_matmul_precision("highest"):
        stem = params["stem"]
        x = _conv(images, stem["conv"], model["stem_stride"])
        x = jnp.maximum(_bn(x, stem["scale"], stem["bias"]), 0.0)
        if model["stem_pool"]:
            x = jax.lax.reduce_window(
                x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME"
            )
        i = 0
        for si, depth in enumerate(model["stages"]):
            for b in range(depth):
                blk = params["blocks"][i]
                stride = 2 if (si > 0 and b == 0) else 1
                y = _conv(x, blk["conv1"], stride)
                y = jnp.maximum(_bn(y, blk["scale1"], blk["bias1"]), 0.0)
                y = _bn(_conv(y, blk["conv2"], 1), blk["scale2"], blk["bias2"])
                shortcut = _conv(x, blk["proj"], stride) if "proj" in blk else x
                x = jnp.maximum(shortcut + y, 0.0)
                i += 1
        x = jnp.mean(x, axis=(1, 2))
        return jnp.dot(
            x, params["head"]["w"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        ) + params["head"]["b"]


def loss(params, batch, model: dict):
    """Mean softmax cross-entropy of (images, labels)."""
    images, labels = batch
    logits = forward(params, images, model)
    logits = logits - jnp.max(logits, axis=-1, keepdims=True)
    logp = logits - jnp.log(jnp.sum(jnp.exp(logits), axis=-1, keepdims=True))
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))
