"""Device-side timing for codec work (shared by bench.py and
benchmarks/pareto.py).

One codec frame on a 1 Mi table is tens of microseconds of device work and
one dispatch from the host costs twenty times that (on a v5e, r22: 36 us a
frame, a call returning after ~0.7 ms), so a host loop of single frames
times the dispatch. Each measurement therefore chains L codec frames
device-side in ONE program, waits for it by fetching a scalar that depends
on the final frame of both the residual and values chains, and sizes L so
the chain runs for seconds: the per-call overhead becomes a small bias that
only UNDERSTATES the reported rate. ``block_until_ready`` on the outputs
waits just as long as the fetch (same run: a 65 536-frame chain took 2.373 s
against 2.370 s, three repeats each within 0.2 %).

The chain length is a *dynamic* ``lax.fori_loop`` trip count, so every
length reuses ONE compiled program (a ``lax.scan`` with a static length
would compile once per length step).
"""

from __future__ import annotations

import time
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp


def codec_frame_time(
    codec,
    n: int,
    policy,
    make_residual: Callable[[int], jnp.ndarray] | None = None,
    target_seconds: float = 3.0,
    reps: int = 2,
    budget_s: float | None = None,
) -> float:
    """Seconds per fused codec roundtrip frame (sender quantize + receiver
    apply) at table size ``n``. ``make_residual(seed)`` supplies the starting
    residual (default: standard normal — nonzero scale throughout, so every
    frame does the full non-idle work). ``budget_s`` is a hard wall-clock
    budget for the whole measurement including compile: the best estimate so
    far is returned when it trips (never raises for budget reasons)."""
    deadline = None if budget_s is None else time.monotonic() + budget_s
    if make_residual is None:
        make_residual = lambda seed: jax.random.normal(
            jax.random.key(seed), (n,), jnp.float32
        )

    @partial(jax.jit, donate_argnums=(0, 1))
    def group(resid, values, length):
        def body(_, carry):
            r, v = carry
            frame, r = codec.quantize(r, n, policy)
            v = codec.apply_frame(v, frame, n)
            return (r, v)

        r, v = jax.lax.fori_loop(0, length, body, (resid, values))
        # The fetched scalar depends on both chains (each frame's error
        # feedback feeds r, each apply feeds v), so neither half can be
        # dead-code-eliminated and the fetch waits for the whole program.
        return r, v, r[0] + v[0]

    def timed(length: int) -> float:
        best = float("inf")
        for rep in range(reps):
            r = make_residual(rep)
            v = jnp.zeros((n,), jnp.float32)
            jax.block_until_ready((r, v))
            t0 = time.perf_counter()
            _, _, probe = group(r, v, jnp.int32(length))
            float(probe)  # the fetch waits for the whole chain
            best = min(best, time.perf_counter() - t0)
            if deadline is not None and time.monotonic() > deadline:
                break
        return best

    # Grow the chain until the measured run itself is target-length: a pilot
    # estimate alone UNDERSHOOTS (its per-frame time over-counts the fixed
    # overhead, so the projected length lands short and the long run would
    # still be overhead-dominated). Dynamic trip count = no recompiles, so
    # growth can jump straight to the projected length.
    length = 256
    timed(length)  # warmup/compile (the one compile)
    t = timed(length)
    max_length = 4_000_000
    while t < target_seconds and length < max_length:
        if deadline is not None and time.monotonic() > deadline:
            break
        est = max(t / length, 1e-9)
        length = min(max_length, max(length * 2, int(target_seconds / est)))
        t = timed(length)
    return t / length
