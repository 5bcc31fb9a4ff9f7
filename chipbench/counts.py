"""Operations and bytes computed from shapes: the yardstick's arithmetic.

Kept with the benchmark so that no PR that claims a gain can change what a
share of a peak is a share of. Nothing here touches a device.
"""

from __future__ import annotations

LANES = 128
BITS_PER_WORD = 32


def resnet_forward_flops(model: dict) -> int:
    """Multiply-adds x 2 of one sample's forward pass through
    models/resnet.py::forward for the configuration's ``model`` group: the
    convolutions ('SAME' padding, so the output is ceil(in / stride)) and the
    head's matrix product. BatchNorm, ReLU, pooling and the loss run on the
    VPU and are not counted: a utilization of the MXU's peak counts what the
    MXU must do."""
    flops, _ = _resnet_flops(model)
    return flops


def resnet_train_flops(model: dict) -> int:
    """Forward plus backward of one sample: every convolution costs its
    forward once more for the gradient of its input and once more for the
    gradient of its weights, except the stem, whose input is the image and
    needs no gradient. The head likewise. Recomputation does not count."""
    flops, stem = _resnet_flops(model)
    return 3 * flops - stem


def _resnet_flops(model: dict) -> tuple[int, int]:
    def conv(hw, k, cin, cout, stride):
        out = -(-hw // stride)
        return out, 2 * out * out * k * k * cin * cout

    w = model["width"]
    hw, stem = conv(model["image_size"], model["stem_kernel"],
                    model["channels"], w, model["stem_stride"])
    total = stem
    if model["stem_pool"]:
        hw = -(-hw // 2)
    cin = w
    for si, depth in enumerate(model["stages"]):
        cout = w * 2**si
        for b in range(depth):
            stride = 2 if (si > 0 and b == 0) else 1
            out, f1 = conv(hw, 3, cin, cout, stride)
            _, f2 = conv(out, 3, cout, cout, 1)
            total += f1 + f2
            if stride != 1 or cin != cout:
                total += conv(hw, 1, cin, cout, stride)[1]
            hw, cin = out, cout
    total += 2 * cin * model["classes"]
    return total, stem


def quantize_rows_bytes(total: int) -> int:
    """HBM bytes ops/codec_pallas.py::quantize_rows must move for a flat
    buffer of ``total`` padded elements: the residual read and written once
    in float32, one bit an element written for the words, one float32 scale
    and one int32 live-lane count read per 128-lane row, at their true size
    (the lane-padded operands XLA materialises today are the program's
    choice, not the algorithm's need)."""
    rows = total // LANES
    return 4 * total + 4 * total + total // 8 + 4 * rows + 4 * rows


def apply_rows_batch_bytes(total: int, k_frames: int, n_arrays: int = 1) -> int:
    """HBM bytes ops/codec_pallas.py::apply_rows_batch must move: each target
    array read and written once, ``k_frames`` frames of one bit an element,
    ``k_frames`` float32 scales and one int32 count per row."""
    rows = total // LANES
    return n_arrays * 8 * total + k_frames * (total // 8 + 4 * rows) + 4 * rows


def sync_step_kernel_bytes(total_per_shard: int, n_peer: int) -> int:
    """Both kernels of one fused sync step on one device (parallel/ici.py:
    one quantize of the local residual, one apply of ``n_peer`` gathered
    frames to the local replica)."""
    return quantize_rows_bytes(total_per_shard) + apply_rows_batch_bytes(
        total_per_shard, n_peer, 1
    )


def frame_ici_bytes(total: int, num_leaves: int, n_peer: int) -> int:
    """Bytes one peer receives over the interconnect per compressed sync
    step: one bit an element and one float32 scale a leaf from each other
    peer (the arithmetic of parallel/ici.py::frame_ici_bytes)."""
    return (n_peer - 1) * (total // BITS_PER_WORD * 4 + num_leaves * 4)
