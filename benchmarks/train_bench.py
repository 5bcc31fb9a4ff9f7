"""Training-throughput benchmark: char-rnn async-DP step time, tokens/s, MFU,
and sync overhead (BASELINE config 2 workload).

Four arms of the SAME fused training step (train/async_sgd.py), differing
only in the sync tail:

- ``sync_off``   — pure local SGD, no communication (isolation baseline);
- ``compressed`` — the framework's 1-bit error-feedback codec sync (the
  reference's semantics, reference README.md:13-19);
- ``compressed_overlap`` — same codec, collective scheduled under the
  backward pass (async overlap mode, train/async_sgd.py ``overlap=True``);
- ``exact``      — uncompressed delta exchange (the allreduce comparison arm,
  BASELINE config 4).

Sync overhead = (t_arm - t_sync_off) / t_sync_off: what fraction of a
training step the parameter sync costs, the in-step analog of the
reference's codec-CPU bottleneck (SURVEY.md §6: one core fully saturated).

MFU uses analytic matmul FLOPs (fwd 2N, bwd 4N per token, N = matmul
params/token) against the chip's published peak (PEAK_FLOPS, keyed by
``device_kind``; a TPU that is not in the table is an error, the CPU gives
``mfu: null``).

Steps are chained device-side with a dynamic-trip-count fori_loop (one
compile per arm, dispatch cost amortized — utils/timing.py rationale).
Prints ONE JSON line with all arms. An arm that raises ends the run; an arm
the wall-clock budget (ST_TRAIN_BENCH_BUDGET_S, default 600 s) left no room
for is recorded as an error and the exit code is nonzero.
"""

from __future__ import annotations

import json
import os
import sys
import time
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BUDGET_S = float(os.environ.get("ST_TRAIN_BENCH_BUDGET_S", "600"))
_T0 = time.monotonic()

#: Peak dense FLOP/s of one chip by ``jax.devices()[0].device_kind``, with
#: the source of each figure.
PEAK_FLOPS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16 per chip
    "TPU v5 lite": 197e12,
}


def peak_flops(platform: str, device_kind: str) -> float | None:
    """The table's peak for this device; None on the CPU (no MFU there). A
    TPU the table does not know is an error, not a default."""
    if platform == "cpu":
        return None
    if device_kind not in PEAK_FLOPS:
        raise SystemExit(
            f"train_bench: no published peak for device_kind "
            f"{device_kind!r} (platform {platform!r}); add it to PEAK_FLOPS "
            "with its source"
        )
    return PEAK_FLOPS[device_kind]


def _remaining() -> float:
    return BUDGET_S - (time.monotonic() - _T0)


def flops_per_token(cfg) -> int:
    """Analytic matmul FLOPs per token for one training step (fwd+bwd).

    Matmul params N/token: per layer (d*4H input proj + H*4H recurrent),
    plus H*V output proj; embedding lookup is a gather (no FLOPs). Forward
    = 2N, backward = 4N (standard approximation), total 6N.
    """
    n = 0
    d = cfg.embed
    for _ in range(cfg.layers):
        n += d * 4 * cfg.hidden + cfg.hidden * 4 * cfg.hidden
        d = cfg.hidden
    n += cfg.hidden * cfg.vocab
    return 6 * n


def bench_arm(
    jnp,
    jax,
    trainer,
    batch,
    lr: float,
    target_seconds: float,
    budget_s: float,
) -> float:
    """Seconds per training step, measured on a device-side chain of steps
    (same batch every step — throughput, not convergence)."""
    deadline = time.monotonic() + budget_s
    step_fn = trainer._step  # the compiled fused step

    losses0 = jnp.zeros((trainer.n_peer,), jnp.float32)

    @partial(jax.jit, donate_argnums=(0,))
    def chain(state, k):
        def body(_, carry):
            st, losses = carry
            st, _, losses, _ = step_fn(st, trainer.opt_state, batch, lr)
            return (st, losses)

        st, losses = jax.lax.fori_loop(0, k, body, (state, losses0))
        return st, losses, losses[0]

    def timed(k: int) -> float:
        state = trainer.state
        t0 = time.perf_counter()
        state, _, probe = chain(state, jnp.int32(k))
        float(probe)  # the fetch waits for the whole chain
        trainer.state = state  # keep ownership after donation
        return time.perf_counter() - t0

    k = 2
    timed(k)  # warmup/compile
    t = timed(k)
    while t < target_seconds and k < 100_000:
        if time.monotonic() > deadline:
            break
        est = max(t / k, 1e-9)
        k = min(100_000, max(k * 2, int(target_seconds / est)))
        t = timed(k)
    return t / k


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--platform", default=None, help="force a jax platform (e.g. cpu)")
    ap.add_argument("--peers", type=int, default=None, help="peer-axis size (default: all devices)")
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--tiny", action="store_true", help="tiny model (CI smoke)")
    args = ap.parse_args()

    import jax

    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    import jax.numpy as jnp

    from shared_tensor_tpu.models import char_rnn as m
    from shared_tensor_tpu.parallel.mesh import make_mesh
    from shared_tensor_tpu.train.async_sgd import PodTrainer
    from shared_tensor_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    dev = jax.devices()[0]
    peak = peak_flops(dev.platform, dev.device_kind)

    if args.tiny:
        cfg = m.CharRNNConfig(vocab=64, embed=32, hidden=64, layers=2)
    else:
        cfg = m.CharRNNConfig()  # flagship: 2-layer LSTM 512, byte vocab
    n_peer = args.peers or len(jax.devices())
    mesh = make_mesh(n_peer, 1)
    params = m.init_params(jax.random.key(0), cfg)
    loss = lambda p, b: m.loss_fn(p, b, cfg)

    text = (b"the quick brown fox jumps over the lazy dog. " * 200)
    batch = m.make_batches(
        text, batch=args.batch, seq=args.seq, key=jax.random.key(1),
        n_peer=n_peer, vocab=cfg.vocab,
    )

    arms = [
        ("sync_off", dict(sync=False)),
        ("compressed", dict(sync=True, compressed=True)),
        # collective scheduled under the backward pass (async overlap mode,
        # train/async_sgd.py overlap=True) — the arm that should drive
        # sync_overhead_pct toward zero on hardware with real ICI latency
        ("compressed_overlap", dict(sync=True, compressed=True, overlap=True)),
        ("exact", dict(sync=True, compressed=False)),
    ]
    tokens_per_step = n_peer * args.batch * args.seq
    fpt = flops_per_token(cfg)
    out: dict = {
        "metric": "train_step_bench",
        "model": "char_rnn",
        "config": {
            "vocab": cfg.vocab, "embed": cfg.embed, "hidden": cfg.hidden,
            "layers": cfg.layers, "params": cfg.param_count,
            "n_peer": n_peer, "batch": args.batch, "seq": args.seq,
        },
        "backend": jax.default_backend(),
        "device_kind": dev.device_kind,
        "on_tpu": dev.platform == "tpu",
        "flops_per_token": fpt,
        "arms": {},
    }
    t_base = None
    for name, kw in arms:
        slice_budget = _remaining() / max(1, len(arms) - len(out["arms"]))
        if slice_budget < 20:
            out["arms"][name] = {"error": "budget exhausted"}
            continue
        trainer = PodTrainer(mesh, params, loss, **kw)
        batch_sh = trainer.shard_batch(batch)
        t_step = bench_arm(
            jnp, jax, trainer, batch_sh, 0.05,
            target_seconds=2.0, budget_s=slice_budget,
        )
        tok_s = tokens_per_step / t_step
        arm: dict = {
            "step_ms": round(t_step * 1e3, 3),
            "tokens_per_s": round(tok_s, 1),
            "mfu": round(fpt * tok_s / peak, 4) if peak else None,
        }
        if name == "sync_off":
            t_base = t_step
        elif t_base:
            arm["sync_overhead_pct"] = round((t_step - t_base) / t_base * 100, 1)
        out["arms"][name] = arm
    print(json.dumps(out), flush=True)
    if any("error" in arm for arm in out["arms"].values()):
        sys.exit(1)


if __name__ == "__main__":
    main()
