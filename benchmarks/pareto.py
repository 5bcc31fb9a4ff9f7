"""BASELINE config 5: dense-tensor sweep — approximation-error vs
sync-bandwidth Pareto.

For each table size, measures (a) the fused codec roundtrip rate on the chip
(long-chain device-side timing, utils/timing.py) giving equivalent-fp32-delta
GB/s per link at 1 bit/element/frame wire cost, and (b) the measured residual-RMS
decay per frame on uniform data — the matched-approximation-error yardstick
(the reference halves residual RMS each frame on homogeneous data,
BASELINE.md convergence table; the codec here is bit-identical, and this
sweep re-measures rather than assumes it).

Prints one JSON line per size. The reference crashes past ~60 Mi elements
(stack VLA, SURVEY.md quirk Q6); sizes here are bounded only by HBM.

Usage: python benchmarks/pareto.py [--sizes 20,22,24,26]
"""

from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp

BASELINE_GBPS = {  # reference E2E loopback equiv-delta GB/s (BASELINE.md)
    1 << 12: 1.28,
    1 << 20: 1.01,
    1 << 24: 0.52,
}


def measure_size(codec, n: int, policy) -> dict:
    from shared_tensor_tpu.utils.timing import codec_frame_time

    uniform = lambda seed: jax.random.uniform(
        jax.random.key(seed), (n,), jnp.float32, -1.0, 1.0
    )
    t_frame = codec_frame_time(codec, n, policy, make_residual=uniform)
    equiv_gbps = n * 4 / t_frame / 1e9

    # Error curve: residual RMS per frame on U(-1,1) (matched-error check).
    @jax.jit
    def rms_curve(resid):
        def body(r, _):
            frame, r = codec.quantize(r, n, policy)
            return r, jnp.sqrt(jnp.mean(r * r))
        _, curve = jax.lax.scan(body, resid, None, length=8)
        return curve

    r0 = jax.random.uniform(jax.random.key(7), (n,), jnp.float32, -1.0, 1.0)
    rms0 = float(jnp.sqrt(jnp.mean(r0 * r0)))
    curve = [float(x) for x in jax.device_get(rms_curve(r0))]
    halving = (curve[-1] / rms0) ** (1 / len(curve)) if rms0 else 0.0

    base = BASELINE_GBPS.get(n)
    return {
        "n_elements": n,
        "mbytes": round(n * 4 / 1e6, 1),
        "equiv_gbps": round(equiv_gbps, 2),
        "wire_gbps": round(equiv_gbps / 32, 3),
        "frame_us": round(t_frame * 1e6, 1),
        "rms_decay_per_frame": round(halving, 4),  # reference: 0.5
        "vs_baseline": round(equiv_gbps / base, 1) if base else None,
        "backend": jax.default_backend(),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", default="12,16,20,24,26")
    ap.add_argument("--policy", default="POW2_RMS")
    args = ap.parse_args()

    from shared_tensor_tpu.config import ScalePolicy
    from shared_tensor_tpu.ops import codec
    from shared_tensor_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    policy = ScalePolicy[args.policy]
    for log2n in (int(s) for s in args.sizes.split(",")):
        print(json.dumps(measure_size(codec, 1 << log2n, policy)), flush=True)


if __name__ == "__main__":
    main()
