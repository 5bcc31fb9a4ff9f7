"""The approximate-delta codec: 1-bit sign quantization with error feedback.

This is the semantic heart of the framework — a faithful, functional
re-statement of the reference codec (reference src/sharedtensor.c:106-111
receiver, :145-177 sender; SURVEY.md Appendix B):

  sender, per frame over a link with residual ``r``:
    1. ``s = 2^floor(log2(rms(r)))``      (power-of-2 floor; s=0 -> idle)
    2. ``b_i = [r_i <= 0]``; ``r_i -= (1 - 2*b_i) * s``   (error feedback)
    3. transmit ``(s, bits)``
  receiver:  ``x_i += (1 - 2*b_i) * s``  applied to its replica AND to the
  residuals of its other links (per-hop re-quantized flooding).

Where the reference is 5 racy threads doing unsynchronized ``float +=`` over
shared buffers (SURVEY.md §5.2, quirk Q7), these are pure functions over
immutable arrays — the race class is gone by construction while the
approximate/eventually-consistent semantics stay in the codec where they
belong.

Layout: all state is flat float32, zero-padded to a multiple of the (8,128)
float32 TPU tile. Invariant: padding lanes of residuals and values are always
exactly 0 (quantize/apply mask them), so full-array reductions need no mask.

This module is the pure-JAX *golden* implementation; the fused
single-HBM-pass Pallas row kernels (ops/codec_pallas.py, behind ops/table.py's
row codec) must match it bit-for-bit on a single-leaf table.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp

from .packing import pack_bits, padded_len, unpack_bits
from ..config import ScalePolicy


def pow2_floor(x: jnp.ndarray) -> jnp.ndarray:
    """2^floor(log2(x)) computed exactly by clearing the f32 mantissa.

    TPU log2/exp2 are approximate — a scale that is off by 1 ulp from a power
    of two breaks the codec's exact-convergence property (residual
    subtraction no longer cancels), so transcendentals are not an option
    here. Denormal input maps to 0 (idle frame), matching the reference's
    behavior of grinding to scale==0. Shared by the scalar and table codecs,
    which must match bit-for-bit.
    """
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0x7F800000), jnp.float32)


class Frame(NamedTuple):
    """One codec frame: everything that crosses the wire for one link-step.

    ``words`` are the LSB-first packed sign bits (see ops/packing.py for the
    wire-layout contract); ``scale`` is the power-of-2 step size. A set bit
    means ``-scale``, clear means ``+scale`` (reference src/sharedtensor.c:109).
    """

    scale: jnp.ndarray  # f32 scalar
    words: jnp.ndarray  # uint32[n_padded // 32]


def compute_scale(
    residual: jnp.ndarray,
    n: int,
    policy: ScalePolicy = ScalePolicy.POW2_RMS,
) -> jnp.ndarray:
    """Per-frame step size from the residual.

    POW2_RMS is the reference rule ``2^floor(log2(sqrt(mean(r^2))))``
    (reference src/sharedtensor.c:153-159). ``n`` is the true (unpadded)
    element count — the padded tail is all-zero by invariant so it only
    affects the divisor. Returns 0.0 for an all-zero residual (idle link).
    """
    # Overflow-safe RMS: normalize by max|r| before squaring. The reference
    # accumulates raw squares in f32 (src/sharedtensor.c:156-157) and
    # overflows to inf for |r| ~ 1e20+, poisoning every replica via the flood
    # (quirk Q9) — fixed here, not inherited.
    amax = jnp.max(jnp.abs(residual))
    norm = residual / jnp.where(amax > 0, amax, 1.0)
    rms = amax * jnp.sqrt(jnp.sum(norm * norm, dtype=jnp.float32) / jnp.float32(n))
    if policy == ScalePolicy.RMS:
        scale = rms
    elif policy == ScalePolicy.ABS_MEAN:
        # Same amax normalization as rms: a raw f32 |r| sum can overflow.
        scale = amax * (jnp.sum(jnp.abs(norm), dtype=jnp.float32) / jnp.float32(n))
    else:  # POW2_RMS
        scale = pow2_floor(rms)
    # Non-finite rms (residual poisoned despite the accumulate() clamp) maps
    # to 0: the link idles instead of flooding NaN/inf to every replica.
    return jnp.where((rms > 0) & jnp.isfinite(rms), scale, jnp.float32(0.0))


@partial(jax.jit, static_argnames=("n", "policy"))
def quantize(
    residual: jnp.ndarray,
    n: int,
    policy: ScalePolicy = ScalePolicy.POW2_RMS,
) -> tuple[Frame, jnp.ndarray]:
    """One sender step: residual -> (frame, new_residual).

    Sign rule matches the reference exactly: ``r > 0`` sends ``+s`` (bit
    clear), ``r <= 0`` sends ``-s`` (bit set) — zero counts as negative
    (quirk Q3, kept deliberately: converged elements oscillate within
    +/-scale, which is the documented overshoot bound). Padding lanes are
    forced to bit=0 and residual=0.

    With scale == 0 the residual is untouched and the frame is a no-op on
    any receiver — callers may skip sending it (config
    ``suppress_zero_frames``, fixing reference quirk Q2).
    """
    n_pad = residual.shape[0]
    scale = compute_scale(residual, n, policy)
    live = jnp.arange(n_pad, dtype=jnp.int32) < n
    neg = residual <= 0  # bit set => -scale
    bits = jnp.where(live, neg, False)
    sent = jnp.where(neg, -scale, scale)
    new_residual = jnp.where(live, residual - sent, 0.0)
    # scale == 0: keep residual exactly as-is (all-zero stays all-zero).
    new_residual = jnp.where(scale > 0, new_residual, residual)
    return Frame(scale, pack_bits(bits)), new_residual


#: Saturation bound for every state-mutating path (accumulate AND apply, all
#: tiers). Add-side sanitization alone leaves one absorbing state: values
#: legally at +/-SAT plus one max-scale frame (2^127, legal for a residual at
#: the clamp) overflows to inf, and inf - inf = NaN floods tree-wide
#: (reference quirk Q9). Clamping the apply result closes the model: no
#: reachable state is non-finite, by construction. On sane magnitudes the
#: clip is the identity, so cross-tier bit-parity is unaffected.
SAT = 3.0e38


@partial(jax.jit, static_argnames=("n",))
def apply_frame(values: jnp.ndarray, frame: Frame, n: int) -> jnp.ndarray:
    """One receiver step: ``values[i] += scale - bit_i * 2 * scale``
    (reference src/sharedtensor.c:106-111), padding masked to stay 0."""
    n_pad = values.shape[0]
    bits = unpack_bits(frame.words)
    live = jnp.arange(n_pad, dtype=jnp.int32) < n
    delta = frame.scale * (1.0 - 2.0 * bits.astype(jnp.float32))
    return jnp.where(live, jnp.clip(values + delta, -SAT, SAT), 0.0)


@partial(jax.jit, static_argnames=("n",))
def apply_frame_many(
    arrays: tuple[jnp.ndarray, ...], frame: Frame, n: int
) -> tuple[jnp.ndarray, ...]:
    """Apply one frame to several arrays in one traced step — the receive-side
    flood: a frame from link A updates the replica plus the residuals of every
    *other* link (split horizon; reference src/sharedtensor.c:124-127)."""
    n_pad = arrays[0].shape[0]
    bits = unpack_bits(frame.words)
    live = jnp.arange(n_pad, dtype=jnp.int32) < n
    delta = jnp.where(live, frame.scale * (1.0 - 2.0 * bits.astype(jnp.float32)), 0.0)
    return tuple(jnp.clip(a + delta, -SAT, SAT) for a in arrays)


@partial(jax.jit, static_argnames=("n",))
def accumulate(
    arrays: tuple[jnp.ndarray, ...], update: jnp.ndarray, n: int
) -> tuple[jnp.ndarray, ...]:
    """The local additive update: ``values += u`` and every link residual
    ``+= u`` in one step (reference addFromInternal, src/sharedtensor.c:
    334-344). ``update`` is already padded; padding is re-masked for safety.

    Updates are sanitized at this boundary (NaN -> 0, +/-inf clamped): one bad
    delta in the reference NaN-poisons every replica through the flood (quirk
    Q9); here bad values never enter the shared state.
    """
    n_pad = arrays[0].shape[0]
    live = jnp.arange(n_pad, dtype=jnp.int32) < n
    u = jnp.where(live, update, 0.0)
    u = jnp.nan_to_num(u, nan=0.0, posinf=3.0e38, neginf=-3.0e38)
    # Clamp the sum too: a residual near f32 max plus a large update would
    # otherwise overflow to inf and permanently wedge the link.
    return tuple(jnp.clip(a + u, -3.0e38, 3.0e38) for a in arrays)


def pad_flat(x: jnp.ndarray, n_pad: int | None = None) -> jnp.ndarray:
    """Flatten to 1-D float32 and zero-pad to a tile multiple."""
    flat = jnp.ravel(x).astype(jnp.float32)
    n = flat.shape[0]
    n_pad = padded_len(n) if n_pad is None else n_pad
    return jnp.pad(flat, (0, n_pad - n))


def unpad(flat: jnp.ndarray, shape: Sequence[int]) -> jnp.ndarray:
    """Undo :func:`pad_flat` back to the caller's shape."""
    n = 1
    for d in shape:
        n *= d
    return flat[:n].reshape(shape)
