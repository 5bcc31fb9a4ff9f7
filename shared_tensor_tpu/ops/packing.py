"""Sign-bit packing: bool arrays <-> uint32 words <-> reference wire bytes.

Layout contract (load-bearing for wire compatibility): flat bit ``i`` lives in
word ``i // 32`` at bit position ``i % 32`` (LSB-first). Serializing the words
little-endian therefore reproduces the reference's bitmask byte layout exactly
— bit ``i`` at ``byte[i/8]``, position ``i % 8``, LSB-first (reference
src/sharedtensor.c:106-111 receiver, :166-174 sender) — so one packed
representation serves both the TPU-native path and wire-compat interop.

All functions here are pure JAX (jittable) except the ``*_wire_*`` pair, which
are host-side numpy (they touch Python ``bytes``).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

#: float32 TPU tile is (8, 128) sublanes x lanes; pad flat buffers to this so
#: the Pallas kernels see whole tiles.
LANES = 128
SUBLANES = 8
TILE = LANES * SUBLANES  # 1024
BITS_PER_WORD = 32
#: uint32 words that pack one 128-lane row of sign bits.
WORDS_PER_ROW = LANES // BITS_PER_WORD  # 4


def padded_len(n: int, multiple: int = TILE) -> int:
    """Smallest multiple of ``multiple`` >= n (and >= 1 tile)."""
    if n <= 0:
        raise ValueError(f"need a positive element count, got {n}")
    return ((n + multiple - 1) // multiple) * multiple


def pack_bits(bits: jnp.ndarray) -> jnp.ndarray:
    """Pack a flat bool/int array (length divisible by 32) into uint32 words,
    LSB-first: ``word[j] = sum_b bits[32*j+b] << b``."""
    n = bits.shape[-1]
    assert n % BITS_PER_WORD == 0, n
    b = bits.astype(jnp.uint32).reshape(*bits.shape[:-1], -1, BITS_PER_WORD)
    shifts = jnp.arange(BITS_PER_WORD, dtype=jnp.uint32)
    return jnp.sum(b << shifts, axis=-1, dtype=jnp.uint32)


def unpack_bits(words: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`pack_bits`: uint32 words -> flat int32 0/1 array."""
    shifts = jnp.arange(BITS_PER_WORD, dtype=jnp.uint32)
    bits = (words[..., :, None] >> shifts) & jnp.uint32(1)
    return bits.reshape(*words.shape[:-1], -1).astype(jnp.int32)


def words_rows(rows: int) -> int:
    """Rows of the packed words of ``rows`` 128-lane table rows in the form
    the row codec carries them on the device, ``u32[words_rows, 128]``: the
    flat word vector 128 words a row, so the four words of 32 consecutive
    table rows fill one row and the array is dense in HBM (a ``u32[rows, 4]``
    is lane-padded 32x there)."""
    return -(-rows * WORDS_PER_ROW // LANES)


def dense_words(flat: jnp.ndarray, rows: int) -> jnp.ndarray:
    """The flat word vector ``u32[..., rows * 4]`` -> ``u32[...,
    words_rows(rows), 128]``: a bitcast where ``rows`` is a multiple of 32,
    zero pad words behind the last row's else."""
    pad = words_rows(rows) * LANES - flat.shape[-1]
    if pad:
        flat = jnp.pad(flat, [(0, 0)] * (flat.ndim - 1) + [(0, pad)])
    return flat.reshape(*flat.shape[:-1], words_rows(rows), LANES)


def flat_words(words: jnp.ndarray, rows: int) -> jnp.ndarray:
    """Inverse of :func:`dense_words`: ``u32[..., words_rows(rows), 128]`` ->
    the flat word vector ``u32[..., rows * 4]`` of the wire layer."""
    flat = words.reshape(*words.shape[:-2], -1)
    return flat[..., : rows * WORDS_PER_ROW]


def words_to_wire(words: np.ndarray, n: int) -> bytes:
    """Serialize packed words to the reference's bitmask wire bytes:
    little-endian words truncated to ``ceil(n/8)`` bytes."""
    raw = np.asarray(words, dtype="<u4").tobytes()
    return raw[: (n + 7) // 8]


def wire_to_words(payload: bytes, n_padded: int) -> np.ndarray:
    """Parse reference bitmask wire bytes into ``n_padded/32`` uint32 words
    (zero-filled past the wire payload)."""
    nwords = n_padded // BITS_PER_WORD
    buf = np.zeros(nwords * 4, dtype=np.uint8)
    buf[: len(payload)] = np.frombuffer(payload, dtype=np.uint8)
    return buf.view("<u4").astype(np.uint32)
