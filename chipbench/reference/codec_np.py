"""The 1-bit error-feedback codec of one sync step, in plain NumPy.

What parallel/ici.py's fused step must equal, written from the reference's
description (SURVEY.md §2.3, reference src/sharedtensor.c:106-174) and not
from the program's code:

- a leaf's scale is the power of two at or under the RMS of its residual
  (mantissa cleared; 0 for an all-zero leaf);
- every element sends one bit: set where the residual is <= 0 (send -scale),
  clear where it is > 0 (send +scale);
- error feedback: the sender's residual moves by what was sent;
- split horizon: a replica applies every OTHER peer's frame, never its own.

Nothing of ``shared_tensor_tpu`` or JAX is imported.
"""

from __future__ import annotations

import numpy as np

SAT = np.float32(3.0e38)


def pow2_floor(x: np.ndarray) -> np.ndarray:
    """2^floor(log2 x) of positive float32, exactly; denormals give 0."""
    bits = np.asarray(x, np.float32).view(np.uint32)
    return (bits & np.uint32(0x7F800000)).view(np.float32)


def leaf_rms(residual: np.ndarray) -> float:
    """The leaf's RMS in float64: the exact value the program's float32
    reduction approximates."""
    r = np.asarray(residual, np.float64)
    return float(np.sqrt(np.mean(r * r)))


def leaf_scale(residual: np.ndarray) -> np.float32:
    rms = np.float32(leaf_rms(residual))
    if not np.isfinite(rms) or rms <= 0:
        return np.float32(0.0)
    return pow2_floor(np.array([rms], np.float32))[0]


def near_pow2(rms: float, rel: float = 1e-5) -> bool:
    """Is the exact RMS so close to a power of two that a float32 reduction
    in another order may land on the other side? Then the scale is
    undecidable to float32 and the comparison skips the leaf."""
    if rms <= 0:
        return False
    frac = np.log2(rms) % 1.0
    return min(frac, 1.0 - frac) < rel / np.log(2.0)


def quantize(residual: np.ndarray, scale: np.float32):
    """(bits, new_residual) of one leaf's rows at ``scale``."""
    r = np.asarray(residual, np.float32)
    bits = r <= 0
    if scale <= 0:
        return bits, r.copy()
    sent = np.where(bits, -scale, scale).astype(np.float32)
    return bits, (r - sent).astype(np.float32)


def apply_others(values: np.ndarray, frames, me: int) -> np.ndarray:
    """``values`` of peer ``me`` after every other peer's frame:
    ``frames[q] = (scale_q, bits_q)``."""
    delta = np.zeros_like(values, dtype=np.float32)
    for q, (scale, bits) in enumerate(frames):
        if q == me or scale <= 0:
            continue
        delta = delta + np.where(bits, -scale, scale).astype(np.float32)
    return np.clip(values.astype(np.float32) + delta, -SAT, SAT)


def sync_step(values: np.ndarray, residual: np.ndarray):
    """One whole step on one leaf for all peers. ``values`` and ``residual``
    are float32[n_peer, n]. Returns (values', residual', scales[n_peer])."""
    n_peer = values.shape[0]
    scales = [leaf_scale(residual[p]) for p in range(n_peer)]
    frames, new_r = [], []
    for p in range(n_peer):
        bits, r2 = quantize(residual[p], scales[p])
        frames.append((scales[p], bits))
        new_r.append(r2)
    new_v = [apply_others(values[p], frames, p) for p in range(n_peer)]
    return np.stack(new_v), np.stack(new_r), np.asarray(scales, np.float32)
