"""Numpy codec tier: the host-side (CPU) production implementation of the
table codec.

Three codec tiers now exist, one per execution environment:

- ``ops/table.py`` (pure XLA)     — the golden semantics, any backend;
- ``ops/codec_pallas.py``         — fused TPU kernels (the accelerator tier);
- this module (vectorized numpy)  — the HOST tier: CPU peers, whose XLA-CPU
  pack/unpack lowering is many passes and single-digit-MB/s (measured: a CPU
  peer absorbed 16Mi-element frames at ~1.3/s, stalling the whole link via
  TCP backpressure, while the reference's tight C loop does 202M elem/s on
  one core — BASELINE.md). ``np.packbits``/``np.unpackbits`` ARE that tight C
  loop, and the arithmetic is 2-3 memory-bandwidth passes.

Wire compatibility is bit-exact: ``np.packbits(bitorder="little")`` produces
byte ``i`` bit ``j`` = element ``8i+j`` — the LSB-first layout of
ops/packing.py and of the reference (src/sharedtensor.c:106-111,166-174) —
and little-endian bytes viewed as ``<u4`` are exactly the packed words.
Sign bits and error feedback are bit-identical to the XLA tier given the
same scale; the SCALE itself may differ by 1 ulp from XLA's (different f32
summation order in the RMS reduction), which the POW2 floor collapses in all
but boundary cases — and either scale is a valid codec step carried verbatim
on the wire, so cross-tier links interoperate exactly.

All functions take/return host numpy arrays and are synchronous — a CPU
peer's frame path has no device round-trips at all.
"""

from __future__ import annotations

import ctypes
import os
from typing import Optional

import numpy as np

from .. import _build
from ..config import ScalePolicy
from .codec import SAT as _SAT
from .table import TableSpec

# ---- native tier (native/stcodec.c) ---------------------------------------
#
# The per-element loops run as compiled C when native/libstcodec.so is
# available (built on demand, like the transport); numpy remains the
# always-available fallback and the semantic reference. ST_HOST_CODEC=numpy
# additionally pins pure numpy (parity tests).

_NATIVE_DIR = _build.NATIVE_DIR
_LIB: Optional[ctypes.CDLL] = None
_LIB_TRIED = False

# ALIGNED: the C kernels (and their AVX paths) assume natural alignment;
# a misaligned view (e.g. an offset np.frombuffer) must fail loudly here
# rather than reach the library as UB.
_i64p = np.ctypeslib.ndpointer(np.int64, flags="C,ALIGNED")
_f32p = np.ctypeslib.ndpointer(np.float32, flags="C,ALIGNED")
_u32p = np.ctypeslib.ndpointer(np.uint32, flags="C,ALIGNED")


def _native() -> Optional[ctypes.CDLL]:
    global _LIB, _LIB_TRIED
    if _LIB is not None or _LIB_TRIED:
        return _LIB
    _LIB_TRIED = True
    if os.environ.get("ST_HOST_CODEC") == "numpy":
        return None
    path = _NATIVE_DIR / "libstcodec.so"
    try:
        # Always run make (mtime-based no-op when fresh) so edited sources
        # never keep serving a stale .so; flock-serialized across processes
        # (_build.run_make). ISA safety is runtime-dispatched inside the
        # library itself (__builtin_cpu_supports in stcodec.c), so a .so
        # built elsewhere is portable — no -march=native rebuild hazard.
        _build.run_make(target="libstcodec.so")
        lib = ctypes.CDLL(str(path))
        lib.stc_quantize.restype = None
        lib.stc_quantize.argtypes = [
            _f32p, _f32p, _i64p, _i64p, _i64p, ctypes.c_int64, _f32p, _u32p,
        ]
        lib.stc_accumulate_delta.restype = None
        lib.stc_accumulate_delta.argtypes = [_f32p, _i64p, _i64p, _i64p, ctypes.c_int64, _f32p, _u32p]
        lib.stc_add_inplace.restype = None
        lib.stc_add_inplace.argtypes = [_f32p, _f32p, ctypes.c_int64]
        lib.stc_add_to.restype = None
        lib.stc_add_to.argtypes = [_f32p, _f32p, _f32p, ctypes.c_int64]
        lib.stc_apply_frame.restype = None
        lib.stc_apply_frame.argtypes = [
            _f32p, _f32p, _i64p, _i64p, _i64p, ctypes.c_int64, _f32p, _u32p,
        ]
        _f64p = np.ctypeslib.ndpointer(np.float64, flags="C")
        lib.stc_scale_partials.restype = None
        lib.stc_scale_partials.argtypes = [
            _f32p, _i64p, _i64p, ctypes.c_int64, _f64p, _f64p, _f64p,
        ]
        lib.stc_accumulate_update.restype = None
        lib.stc_accumulate_update.argtypes = [_f32p, _f32p, ctypes.c_int64]
        lib.stc_accumulate_update_to.restype = None
        lib.stc_accumulate_update_to.argtypes = [
            _f32p, _f32p, _f32p, _i64p, _i64p, _i64p, ctypes.c_int64,
        ]
        # fused sender pass + next-frame scale partials (the native
        # engine's burst loop; parity-pinned in test_codec_np)
        lib.stc_quantize_ef_partials.restype = None
        lib.stc_quantize_ef_partials.argtypes = [
            _f32p, _f32p, _i64p, _i64p, _i64p, ctypes.c_int64, _f32p, _u32p,
            _f64p, _f64p, _f64p,
        ]
        # k-frame fused apply: one pass over the target regardless of k
        # (replaces the delta-buffer path; bit-identical to it — see
        # stcodec.c). Trailing partials pointers may be None.
        _f64p_opt = ctypes.POINTER(ctypes.c_double)
        lib.stc_apply_frames.restype = None
        lib.stc_apply_frames.argtypes = [
            _f32p, _f32p, _i64p, _i64p, _i64p, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int32, _f32p, _u32p,
            _f64p_opt, _f64p_opt, _f64p_opt,
        ]
        lib.stc_accumulate_update_to_partials.restype = None
        lib.stc_accumulate_update_to_partials.argtypes = [
            _f32p, _f32p, _f32p, _i64p, _i64p, _i64p, ctypes.c_int64,
            _f64p, _f64p, _f64p,
        ]
        # r11 cascade quantize: K halving frames in ONE pass (scales ride
        # the wire, so the sender-chosen schedule is protocol-legal); the
        # sign2 (2-bit) twins carry sign + magnitude planes per frame.
        lib.stc_quantize_ef_cascade.restype = None
        lib.stc_quantize_ef_cascade.argtypes = [
            _f32p, _f32p, _i64p, _i64p, _i64p, ctypes.c_int64,
            ctypes.c_int32, _f32p, _u32p, ctypes.c_int64,
            _f64p, _f64p, _f64p,
        ]
        lib.stc_quantize2_ef_cascade.restype = None
        lib.stc_quantize2_ef_cascade.argtypes = [
            _f32p, _f32p, _i64p, _i64p, _i64p, ctypes.c_int64,
            ctypes.c_int32, _f32p, _u32p, ctypes.c_int64, ctypes.c_int64,
            _f64p, _f64p, _f64p,
        ]
        lib.stc_apply_frames2.restype = None
        lib.stc_apply_frames2.argtypes = [
            _f32p, _f32p, _i64p, _i64p, _i64p, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int32, _f32p, _u32p,
            _f64p_opt, _f64p_opt, _f64p_opt,
        ]
        lib.stc_apply_frame2.restype = None
        lib.stc_apply_frame2.argtypes = [
            _f32p, _f32p, _i64p, _i64p, _i64p, ctypes.c_int64,
            ctypes.c_int64, _f32p, _u32p,
        ]
        _LIB = lib
    except Exception:  # no toolchain / build failure: numpy fallback
        _LIB = None
    return _LIB


_spec_layout_cache: dict = {}


def _layout(spec: TableSpec):
    """(offsets, ns, padded) as int64 arrays, cached per spec. Keyed by the
    spec VALUE (TableSpec is a hashable frozen dataclass — it is already a
    jit static arg): an id() key could alias a garbage-collected spec whose
    id was reused, handing the C kernels another layout's offsets."""
    hit = _spec_layout_cache.get(spec)
    if hit is not None:
        return hit
    out = (
        np.asarray([off for off, _, _ in _leaf_slices(spec)], np.int64),
        np.asarray(spec.ns, np.int64),
        np.asarray(spec.padded, np.int64),
    )
    if len(_spec_layout_cache) > 256:
        _spec_layout_cache.clear()
    _spec_layout_cache[spec] = out
    return out


def _pow2_floor_np(x: np.ndarray) -> np.ndarray:
    """2^floor(log2(x)) by clearing the f32 mantissa (exact, transcendental-
    free — same rationale as ops/codec.pow2_floor)."""
    bits = np.asarray(x, np.float32).view(np.uint32)
    return (bits & np.uint32(0x7F800000)).view(np.float32)


def _leaf_slices(spec: TableSpec):
    off = 0
    for n, p in zip(spec.ns, spec.padded):
        yield off, n, p
        off += p


def flatten_np(tree, spec: TableSpec, *, copy: bool = True) -> np.ndarray:
    """Numpy twin of ops.table.flatten (pytree -> padded flat f32 buffer,
    padding exactly 0). The host tier must never run jax array ops: merely
    creating a jnp array initializes the XLA CPU client, whose thread pool
    contends with the C codec loops (measured 2.7x slower frames on a
    1-vCPU host). jax.tree_util is pure Python and backend-free.

    ``copy=False`` (r11): a caller that only READS the result before
    returning control (the engine add hot path — st_engine_add consumes
    ``u`` synchronously) may receive the caller's own buffer when the
    tree is a single unpadded C-contiguous f32 leaf — at 1 Mi the
    zeros+copy here was two full table passes per add() on the
    production throughput path (the add cadence is what feeds the
    sender's frame rate). Never pass the result anywhere that retains
    it; the default copies as before."""
    import jax

    leaves, treedef = jax.tree.flatten(tree)
    if treedef != spec.treedef:
        raise ValueError(
            f"tree structure {treedef} does not match spec {spec.treedef}"
        )
    if not copy and len(leaves) == 1 and spec.num_leaves == 1:
        flat = np.ravel(np.asarray(leaves[0])).astype(np.float32, copy=False)
        if flat.shape[0] != spec.ns[0]:
            raise ValueError(
                f"leaf has {flat.shape[0]} elements, spec expects "
                f"{spec.ns[0]}"
            )
        if flat.shape[0] == spec.total and flat.flags.c_contiguous:
            return flat
    out = np.zeros(spec.total, np.float32)
    for (off, n, _), leaf in zip(_leaf_slices(spec), leaves):
        flat = np.ravel(np.asarray(leaf)).astype(np.float32, copy=False)
        if flat.shape[0] != n:
            raise ValueError(f"leaf has {flat.shape[0]} elements, spec expects {n}")
        out[off : off + n] = flat
    return out


def unflatten_np(flat: np.ndarray, spec: TableSpec):
    """Numpy twin of ops.table.unflatten. Leaves are COPIES, not views:
    a view would alias the live replica buffer, and an in-place edit on a
    read() snapshot would then mutate the replica behind the codec's back
    (never entering any residual — permanent tree divergence). The device
    tier gets this for free from jnp immutability."""
    import jax

    flat = np.asarray(flat)
    leaves = [
        flat[off : off + n].copy().reshape(shape)
        for (off, n, _), shape in zip(_leaf_slices(spec), spec.shapes)
    ]
    return jax.tree.unflatten(spec.treedef, leaves)


def compute_scales_np(
    residual: np.ndarray,
    spec: TableSpec,
    policy: ScalePolicy = ScalePolicy.POW2_RMS,
    per_leaf: bool = True,
) -> np.ndarray:
    """Per-leaf scales, overflow-safe (normalize by max|r| before squaring —
    quirk Q9 fix, matching ops/table.leaf_scales). With the native tier
    the reductions run as ONE fused C pass with double accumulators
    (overflow-safe without the normalization); scales can differ from the
    f32 tiers by ~1 ulp of rounding, which any tier tolerates — the scale is
    carried on the wire, never recomputed by a receiver."""
    lib = _native()
    if lib is not None:
        r = np.ascontiguousarray(residual, np.float32)
        offs, ns_arr, _ = _layout(spec)
        L = spec.num_leaves
        amax = np.zeros(L, np.float64)
        ss = np.zeros(L, np.float64)
        sabs = np.zeros(L, np.float64)
        lib.stc_scale_partials(r, offs, ns_arr, L, amax, ss, sabs)
        ns = np.asarray(spec.ns, np.float64)
        if not per_leaf:
            amax = np.full(L, amax.max())
            ss = np.full(L, ss.sum())
            sabs = np.full(L, sabs.sum())
            ns = np.full(L, float(spec.total_n))
        if policy == ScalePolicy.ABS_MEAN:
            s = (sabs / ns).astype(np.float32)
        else:
            rms = np.sqrt(ss / ns).astype(np.float32)
            s = _pow2_floor_np(rms) if policy == ScalePolicy.POW2_RMS else rms
        return np.where((amax > 0) & np.isfinite(s), s, 0.0).astype(np.float32)
    if not per_leaf:
        segs = [(0, spec.total_n, None)]
    else:
        segs = list(_leaf_slices(spec))
    out = np.zeros(len(segs), np.float32)
    for i, seg in enumerate(segs):
        if per_leaf:
            off, n, _ = seg
            live = residual[off : off + n]
        else:
            live = residual  # padding is 0 by invariant; only divisor differs
            n = spec.total_n
        amax = np.float32(np.max(np.abs(live))) if live.size else np.float32(0)
        if not (amax > 0) or not np.isfinite(amax):
            continue
        norm = live.astype(np.float32) / amax
        if policy == ScalePolicy.ABS_MEAN:
            s = amax * np.float32(
                np.sum(np.abs(norm), dtype=np.float32) / np.float32(n)
            )
        else:
            rms = amax * np.float32(
                np.sqrt(np.sum(norm * norm, dtype=np.float32) / np.float32(n))
            )
            s = _pow2_floor_np(rms)[()] if policy == ScalePolicy.POW2_RMS else rms
        out[i] = s if np.isfinite(s) else 0.0
    if not per_leaf:
        out = np.full(spec.num_leaves, out[0], np.float32)
    return out


def _scale_per_element(scales: np.ndarray, spec: TableSpec) -> np.ndarray:
    s = np.empty(spec.total, np.float32)
    for i, (off, n, p) in enumerate(_leaf_slices(spec)):
        s[off : off + p] = scales[i]
    return s


_live_cache: dict = {}


def _live_mask_np(spec: TableSpec) -> np.ndarray:
    m = _live_cache.get(spec)  # value key — see _layout
    if m is None:
        m = np.zeros(spec.total, bool)
        for off, n, p in _leaf_slices(spec):
            m[off : off + n] = True
        if len(_live_cache) > 256:
            _live_cache.clear()
        _live_cache[spec] = m
    return m


def quantize_table_np(
    residual: np.ndarray,
    spec: TableSpec,
    policy: ScalePolicy = ScalePolicy.POW2_RMS,
    per_leaf: bool = True,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Sender step: returns (scales f32[L], words u32[total//32],
    new_residual f32[total]). Semantics identical to ops/table.quantize_table
    (bit set iff r <= 0; residual moves by -+leaf scale; scale-0 leaves
    idle; padding stays exactly 0)."""
    r = np.ascontiguousarray(residual, np.float32)
    scales = compute_scales_np(r, spec, policy, per_leaf)
    lib = _native()
    if lib is not None:
        offs, ns, padded = _layout(spec)
        new_r = np.empty(spec.total, np.float32)
        # C writes every word (padding words are emitted as 0), so empty is safe
        words = np.empty(spec.total // 32, np.uint32)
        lib.stc_quantize(
            r, new_r, offs, ns, padded, spec.num_leaves, scales, words
        )
        return scales, words, new_r
    live = _live_mask_np(spec)
    s_el = _scale_per_element(scales, spec)
    neg = r <= 0
    bits = neg & live
    words = np.packbits(bits, bitorder="little").view("<u4").astype(np.uint32)
    sent = np.where(neg, -s_el, s_el)
    new_r = np.where(live & (s_el > 0), r - sent, np.where(live, r, 0.0)).astype(
        np.float32
    )
    return scales, words, new_r


def apply_table_batch_np(
    arrays: tuple[np.ndarray, ...],
    scales: np.ndarray,  # f32[K, L]
    words: np.ndarray,  # u32[K, total//32]
    spec: TableSpec,
) -> tuple[np.ndarray, ...]:
    """Receiver step for K stacked frames applied to every array (replica +
    other links' residuals — the flood), accumulating the summed delta in one
    f32 buffer then adding it once per target."""
    k = scales.shape[0]
    lib = _native()
    if lib is not None:
        offs, ns, padded = _layout(spec)
        if k == 1:
            # Single frame (the common receive case): fully fused
            # out = clip(in + delta) — one memory pass per target, no delta
            # buffer, no copy. At sizes past LLC the host tier is
            # bandwidth-bound and this is ~2x the accumulate+copy+add path.
            row = np.ascontiguousarray(scales[0], np.float32)
            w0 = np.ascontiguousarray(words[0], np.uint32)
            out = []
            for a in arrays:
                src = np.ascontiguousarray(a, np.float32)
                dst = np.empty(spec.total, np.float32)
                lib.stc_apply_frame(
                    src, dst, offs, ns, padded, spec.num_leaves, row, w0
                )
                out.append(dst)
            return tuple(out)
        # k-frame fused apply (stc_apply_frames): one pass over each target
        # regardless of k — reads the k PACKED word rows (total/8 bytes
        # each) instead of building a total*4 delta buffer with k
        # read-modify-write passes. Bit-identical to the delta path by
        # construction (same per-element +/-s summation order, same final
        # clip(a + delta)).
        srows = np.ascontiguousarray(scales, np.float32)
        wrows = np.ascontiguousarray(words, np.uint32)
        out = []
        for a in arrays:
            src = np.ascontiguousarray(a, np.float32)
            dst = np.empty(spec.total, np.float32)
            lib.stc_apply_frames(
                src, dst, offs, ns, padded, spec.num_leaves,
                spec.total // 32, k, srows, wrows, None, None, None,
            )
            out.append(dst)
        return tuple(out)
    delta = np.zeros(spec.total, np.float32)
    live = _live_mask_np(spec)
    for i in range(k):
        row = np.asarray(scales[i], np.float32)
        if not row.any():
            continue  # zero-scale padding frame contributes nothing
        bits = np.unpackbits(
            np.ascontiguousarray(words[i]).view(np.uint8), bitorder="little"
        )[: spec.total]
        s_el = _scale_per_element(row, spec)
        # values[i] += scale - bit*2*scale (reference src/sharedtensor.c:109)
        delta += s_el * (1.0 - 2.0 * bits.astype(np.float32))
    delta[~live] = 0.0
    out = []
    for a in arrays:
        v = np.clip(np.asarray(a, np.float32) + delta, -_SAT, _SAT)
        v[~live] = 0.0
        out.append(v)
    return tuple(out)


def apply_table_many_np(
    arrays: tuple[np.ndarray, ...],
    scales: np.ndarray,  # f32[L]
    words: np.ndarray,  # u32[total//32]
    spec: TableSpec,
) -> tuple[np.ndarray, ...]:
    return apply_table_batch_np(
        arrays, scales.reshape(1, -1), words.reshape(1, -1), spec
    )


def accumulate_table_np(
    arrays: tuple[np.ndarray, ...], update: np.ndarray, spec: TableSpec
) -> tuple[np.ndarray, ...]:
    """values += u and each link residual += u, sanitized (quirk Q9 fix,
    matching ops/table.accumulate_table)."""
    lib = _native()
    if lib is not None:
        # one fused pass per target: dst = clip(a + sanitize(u)) on live
        # lanes, padding copied from a — no update copy, no target copy
        offs, ns, padded = _layout(spec)
        u_src = np.ascontiguousarray(update, np.float32)
        out = []
        for a in arrays:
            src = np.ascontiguousarray(a, np.float32)
            dst = np.empty(spec.total, np.float32)
            lib.stc_accumulate_update_to(
                dst, src, u_src, offs, ns, padded, spec.num_leaves
            )
            out.append(dst)
        return tuple(out)
    live = _live_mask_np(spec)
    u = np.asarray(update, np.float32).copy()
    u[~live] = 0.0
    np.nan_to_num(u, copy=False, nan=0.0, posinf=3.0e38, neginf=-3.0e38)
    return tuple(
        np.clip(np.asarray(a, np.float32) + u, -3.0e38, 3.0e38) for a in arrays
    )


# ---- r11 sign2 (2-bit sign/magnitude) reference twins -----------------------
#
# PURE-numpy semantic references for the engine tier's sign2 kernels
# (stc_quantize2_ef_cascade / stc_apply_frames2) — deliberately NO native
# fast path: these exist so the parity tests can pin the C loops (and the
# JAX lab step, parallel/ici_lab.build_sign2_sync_step) against an
# independent implementation of the codec-lab Sign2 rule:
#   neg = r <= 0 (zero-negative, quirk Q3), big = |r| > 2s,
#   sent = +/- (3s if big else s), r' = r - sent on live lanes with s > 0.
# Wire layout per frame: [scales L*4][sign words W*4][mag words W*4].


def quantize2_table_np(
    residual: np.ndarray,
    spec: TableSpec,
    policy: ScalePolicy = ScalePolicy.POW2_RMS,
    per_leaf: bool = True,
    scales: "np.ndarray | None" = None,
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """One sign2 sender frame: returns (scales f32[L], sign_words
    u32[total//32], mag_words u32[total//32], new_residual). Pass
    ``scales`` to quantize at given scales (the cross-tier parity
    discipline: bit-identical GIVEN the same scales)."""
    r = np.ascontiguousarray(residual, np.float32)
    if scales is None:
        scales = compute_scales_np(r, spec, policy, per_leaf)
    live = _live_mask_np(spec)
    s_el = _scale_per_element(np.asarray(scales, np.float32), spec)
    neg = r <= 0
    big = np.abs(r) > np.float32(2.0) * s_el
    sign_words = (
        np.packbits(neg & live, bitorder="little").view("<u4").astype(np.uint32)
    )
    mag_words = (
        np.packbits(big & live, bitorder="little").view("<u4").astype(np.uint32)
    )
    mag = np.where(big, np.float32(3.0) * s_el, s_el)
    sent = np.where(neg, -mag, mag)
    new_r = np.where(
        live & (s_el > 0), r - sent, np.where(live, r, 0.0)
    ).astype(np.float32)
    return np.asarray(scales, np.float32), sign_words, mag_words, new_r


def apply2_table_np(
    arrays: tuple[np.ndarray, ...],
    scales: np.ndarray,  # f32[K, L]
    words: np.ndarray,  # u32[K, 2 * total//32]: sign plane then mag plane
    spec: TableSpec,
) -> tuple[np.ndarray, ...]:
    """Receiver reference for K sign2 frames: delta = s * (1-2*neg) *
    (1+2*big) summed across frames, clip once (the fused-apply summation
    order)."""
    k = np.asarray(scales).shape[0]
    w = spec.total // 32
    live = _live_mask_np(spec)
    delta = np.zeros(spec.total, np.float32)
    for i in range(k):
        row = np.asarray(scales[i], np.float32)
        if not row.any():
            continue
        wrow = np.ascontiguousarray(words[i]).view(np.uint32)
        neg = np.unpackbits(
            np.ascontiguousarray(wrow[:w]).view(np.uint8), bitorder="little"
        )[: spec.total].astype(np.float32)
        big = np.unpackbits(
            np.ascontiguousarray(wrow[w:]).view(np.uint8), bitorder="little"
        )[: spec.total].astype(np.float32)
        s_el = _scale_per_element(row, spec)
        delta += s_el * (1.0 - 2.0 * neg) * (1.0 + 2.0 * big)
    delta[~live] = 0.0
    out = []
    for a in arrays:
        v = np.clip(np.asarray(a, np.float32) + delta, -_SAT, _SAT)
        v[~live] = 0.0
        out.append(v)
    return tuple(out)
