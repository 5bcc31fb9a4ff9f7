"""chip_smoke.py: the quickest proof that the system still starts on the chip.

One process drives the main path once, through the entry points a user
calls (``make_mesh``, ``PodTrainer``, ``build_sync_step``, ``SharedTensor``),
at the full width of the models the repo has, with random weights from a
seed:

1. reports the device, the versions and the compile-cache directory, and
   stops unless the platform is ``tpu``;
2. on ``make_mesh(n, 1)`` over every device (and, with four or more devices,
   on ``make_mesh(4, 1)`` and ``make_mesh(2, 2)``): the state lies on the
   devices it should; the compiled train step contains the Mosaic kernels;
   the Pallas sync step agrees with the XLA one on the same state; distinct
   updates on each peer reach every replica; flagship char-rnn trains
   (compressed, then ``overlap=True`` and ``compressed=False``);
3. ResNet-18 (many leaves) takes a few compressed steps;
4. two ``SharedTensor``s on the chip exchange the device tier's default
   16-frame burst until the replica matches.

Any failed check raises; nothing is caught. Times are printed as
information only — they are not a baseline and belong to no metric.

Last line of stdout on success::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

``--rehearse-cpu`` is the one way to run without a chip: tiny sizes, four
virtual CPU devices, Pallas in interpret mode. It exists so that chip time is
not spent on typos, and its last line says ``"rehearsal": true``.
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import gc
import importlib.metadata
import json
import os
import re
import sys
import time
import typing

SEED = 22
TEXT = b"the quick brown fox jumps over the lazy dog. " * 200
#: Plain SGD step for one peer (examples/train_char_rnn.py's default). The
#: peers' updates are summed, not averaged, so each peer steps by
#: LR / n_peer: at LR itself four peers overshoot (loss 5.5 -> 7.5 -> 3.2
#: over 30 steps) and the arms are then compared inside a transient.
LR = 0.5
RESNET_LR = 0.05

#: Sync-step parity, Pallas against XLA on the same state: scales and
#: residuals bit-equal, values to 1e-6 (the sum over peers may run in another
#: order) — what tests/test_table_pallas.py pins on the CPU.
PARITY_TOL = 1e-6
#: Converged replicas against seed + sum of every peer's update. Each applied
#: frame rounds once in f32 (half an ulp of |value| <= 4 is 2.4e-7) and about
#: 30 frames from each other peer land on an element; a missing peer would be
#: off by ~1.
SYNC_ATOL = 1e-4
#: The sync counts as idle once no leaf's scale exceeds half an ulp of a
#: value of magnitude 1. Exact zero takes hundreds of frames more: a lone
#: outlier in a small leaf moves by +/-scale a frame while the scale follows
#: the leaf's RMS (README "Known behaviors").
IDLE_SCALE = 2.0**-24
MAX_SYNC_STEPS = 200
#: Final mean loss of the overlap and exact arms against the compressed arm
#: after the same number of steps, same seed and batch, in nats. This asks
#: whether the arms train at all, not whether they train equally well: the
#: loss band belongs to the benchmark (ROADMAP S1). One peer makes the three
#: arms the same arithmetic up to fusion order. On several peers the arms
#: reach the same plateau near 3.1 and still bounce by ~0.3 between steps:
#: the widest gap at step 60 seen on the CPU at full size was 0.18 ((2,2)),
#: while an arm that does not train stays 2.4 away.
ARM_LOSS_TOL = 0.75
#: replica_spread() while training, never quiesced: a bound on "bounded".
#: Seen on the CPU: up to 0.008 at full size on (4,1) and (2,2), 0.02 at the
#: rehearsal's size.
SPREAD_BOUND = 0.1
#: Device-tier replica against the sender's target: ~30 frames, each adding
#: +/-scale with one f32 rounding (README "Known behaviors": ~1 ulp a frame).
BURST_ATOL = 1e-5


class SmokeFailure(Exception):
    """A check of the smoke run did not hold."""


def require(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


@dataclasses.dataclass(frozen=True)
class Sizes:
    char: object  # CharRNNConfig
    batch: int
    seq: int
    train_steps: int
    resnet: object  # ResNetConfig
    resnet_batch: int
    resnet_hw: int
    resnet_steps: int


def _bytes_in_use(devices):
    """Per-device ``bytes_in_use``, or None where the backend reports no
    memory statistics (the CPU)."""
    stats = [d.memory_stats() for d in devices]
    if any(s is None for s in stats):
        return None
    return [int(s["bytes_in_use"]) for s in stats]


def _peer_trees(template, n_peer: int, seed: int, sample):
    """One pytree shaped like ``template`` per peer, leaves drawn by
    ``sample(key, shape)`` from a key that differs per peer and leaf."""
    import jax

    leaves, treedef = jax.tree.flatten(template)
    trees = []
    for p in range(n_peer):
        keys = jax.random.split(
            jax.random.fold_in(jax.random.key(seed), p), len(leaves)
        )
        trees.append(
            jax.tree.unflatten(
                treedef, [sample(k, l.shape) for k, l in zip(keys, leaves)]
            )
        )
    return trees


def check_placement(trainer, mesh, before) -> None:
    """The state lies where its sharding says, and nowhere else."""
    n_peer, n_shard = mesh.devices.shape
    devices = list(mesh.devices.flat)
    total = trainer.spec.total
    for name, arr in (("values", trainer.state.values),
                      ("residual", trainer.state.residual)):
        shards = arr.addressable_shards
        require(
            len({s.device for s in shards}) == len(devices) == len(shards),
            f"{name}: {len(shards)} shards on "
            f"{len({s.device for s in shards})} devices, want {len(devices)}",
        )
        want = (1, total // n_shard)
        got = {s.data.shape for s in shards}
        require(got == {want}, f"{name}: shard shapes {got}, want {want}")
    after = _bytes_in_use(devices)
    if after is None:
        say("  memory_stats(): not reported by this backend, check skipped")
        return
    share = 2 * (total // n_shard) * 4  # one block of values + residual
    delta = [a - b for a, b in zip(after, before)]
    say(f"  bytes_in_use per device {after}; added by the state {delta}; "
        f"one device's share {share}")
    for d, a, grew in zip(devices, after, delta):
        require(a > 0, f"{d}: bytes_in_use is 0")
        require(
            share <= grew < 2 * share,
            f"{d}: the state added {grew} bytes, its share is {share}",
        )


def check_sync_parity(mesh, spec, params) -> None:
    """build_sync_step(impl="pallas") against impl="xla", same state in."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from shared_tensor_tpu.ops.table import flatten
    from shared_tensor_tpu.parallel import (
        add_updates, build_sync_step, init_state, state_sharding,
    )

    n_peer = mesh.devices.shape[0]
    trees = _peer_trees(
        params, n_peer, SEED + 2,
        lambda k, s: 0.05 * jax.random.normal(k, s, jnp.float32),
    )
    ups = jax.device_put(
        jnp.stack([flatten(t, spec) for t in trees]), state_sharding(mesh)
    )

    def run(impl):
        state = add_updates(init_state(mesh, spec, params), ups)
        step = build_sync_step(mesh, spec, impl=impl)
        for _ in range(3):
            state, scales = step(state)
        return [np.asarray(x) for x in (state.values, state.residual, scales)]

    v_x, r_x, s_x = run("xla")
    v_p, r_p, s_p = run("pallas")
    require(s_p.any(), "sync parity: every scale is 0, nothing was compared")
    require(np.array_equal(s_p, s_x), "sync parity: scales differ")
    require(np.array_equal(r_p, r_x), "sync parity: residuals differ")
    require(
        np.allclose(v_p, v_x, rtol=PARITY_TOL, atol=PARITY_TOL),
        f"sync parity: values differ by {np.abs(v_p - v_x).max()}",
    )
    say("  sync step pallas == xla: scales and residuals bit-equal, "
        f"values within {PARITY_TOL}")


def check_sync_converges(mesh, spec, params) -> None:
    """A distinct update on each peer reaches every replica: the all-gather
    really crossed the peer axis."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from shared_tensor_tpu.ops.table import flatten
    from shared_tensor_tpu.parallel import (
        add_updates, build_sync_step, init_state, state_sharding,
    )

    n_peer = mesh.devices.shape[0]
    trees = _peer_trees(
        params, n_peer, SEED + 3,
        lambda k, s: jax.random.uniform(k, s, jnp.float32, -1.0, 1.0),
    )
    ups = jnp.stack([flatten(t, spec) for t in trees])
    expect = np.asarray(flatten(params, spec) + ups.sum(0))
    state = add_updates(
        init_state(mesh, spec, params),
        jax.device_put(ups, state_sharding(mesh)),
    )
    step = build_sync_step(mesh, spec)
    frames, top = 0, float("inf")
    while top > IDLE_SCALE and frames < MAX_SYNC_STEPS:
        state, scales = step(state)
        top = float(np.asarray(scales).max())
        frames += 1
    require(
        top <= IDLE_SCALE,
        f"sync: largest scale still {top} after {frames} steps",
    )
    values = np.asarray(state.values)
    err = max(float(np.abs(values[p] - expect).max()) for p in range(n_peer))
    left = float(np.abs(np.asarray(state.residual)).max())
    require(err <= SYNC_ATOL, f"sync: a replica is {err} from the sum")
    require(left <= SYNC_ATOL, f"sync: {left} left in a residual at idle")
    say(f"  {n_peer} distinct updates reached every replica in {frames} "
        f"sync steps (max error {err:.2e}, tolerance {SYNC_ATOL})")


class Run(typing.NamedTuple):
    """What :func:`train` saw. The two times are informational."""

    losses: list  # mean over peers, one per step
    scales: object  # the last step's, f32[n_peer, leaves]
    spreads: list  # replica_spread() every ``spread_every`` steps
    first_s: float  # the first step's wall time, compile included
    step_s: float  # the median of the others

    def times(self) -> dict:
        return {"first_step_s": round(self.first_s, 2),
                "step_ms": round(self.step_s * 1e3, 3)}

    def __str__(self) -> str:
        # every tenth loss and the last
        shown = self.losses[:-1:10] + [self.losses[-1]]
        return (
            "loss " + " ".join(f"{x:.3f}" for x in shown)
            + f" ({len(self.losses)} steps); first step {self.first_s:.1f} s, "
            f"then {self.step_s * 1e3:.2f} ms/step (informational)"
        )


def train(trainer, batch, steps: int, lr: float, spread_every=0) -> Run:
    """``steps`` fused steps, each waited for and checked for finite losses."""
    import jax
    import numpy as np

    losses, times, spreads = [], [], []
    scales = None
    for i in range(steps):
        t0 = time.perf_counter()
        step_losses, scales = trainer.step(batch, lr=lr)
        jax.block_until_ready((trainer.state, step_losses, scales))
        times.append(time.perf_counter() - t0)
        step_losses = np.asarray(step_losses)
        require(
            step_losses.shape == (trainer.n_peer,)
            and np.isfinite(step_losses).all(),
            f"step {i}: losses {step_losses}",
        )
        losses.append(float(step_losses.mean()))
        if spread_every and (i + 1) % spread_every == 0:
            spreads.append(trainer.replica_spread())
    require(
        np.isfinite(np.asarray(trainer.state.values)).all(),
        "non-finite values after training",
    )
    rest = sorted(times[1:])
    return Run(losses, scales, spreads, times[0], rest[len(rest) // 2])


def run_mesh(n_peer: int, n_shard: int, sizes: Sizes):
    """Everything the smoke run asks of one mesh, on flagship char-rnn."""
    import jax

    from shared_tensor_tpu.models import char_rnn as m
    from shared_tensor_tpu.parallel.mesh import make_mesh
    from shared_tensor_tpu.train import PodTrainer

    say(f"mesh ({n_peer} peer x {n_shard} shard)")
    cfg = sizes.char
    mesh = make_mesh(n_peer, n_shard)
    devices = list(mesh.devices.flat)
    params = m.init_params(jax.random.key(SEED), cfg)
    loss = lambda p, b: m.loss_fn(p, b, cfg)

    gc.collect()
    before = _bytes_in_use(devices)
    trainer = PodTrainer(mesh, params, loss)
    jax.block_until_ready(trainer.state)
    say(f"  char-rnn {cfg.param_count} params, {trainer.spec.num_leaves} "
        f"leaves, table of {trainer.spec.total} elements")
    check_placement(trainer, mesh, before)
    batch = trainer.shard_batch(
        m.make_batches(
            TEXT, sizes.batch, sizes.seq, jax.random.key(SEED + 1),
            n_peer=n_peer, vocab=cfg.vocab,
        )
    )

    t0 = time.perf_counter()
    lr = LR / n_peer
    text = trainer.lower(batch, lr).compile().as_text()
    calls = text.count("tpu_custom_call")
    say(f"  compressed step compiled in {time.perf_counter() - t0:.1f} s "
        f"(informational): {calls} tpu_custom_call")
    if jax.default_backend() == "tpu":
        require(
            calls >= 2,
            f"the compiled step holds {calls} Mosaic custom calls, want the "
            "quantize and the apply kernel",
        )
    else:
        say("  rehearsal: kernels interpreted, custom-call check skipped")

    check_sync_parity(mesh, trainer.spec, params)
    if n_peer > 1:
        check_sync_converges(mesh, trainer.spec, params)

    run = train(
        trainer, batch, sizes.train_steps, lr,
        spread_every=5 if n_peer > 1 else 0,
    )
    info = {"compressed": run.times()}
    say(f"  compressed: {run}")
    ref = run.losses[-1]
    require(
        ref < run.losses[0],
        f"compressed: loss did not fall ({run.losses[0]} -> {ref})",
    )
    if run.spreads:
        say(f"  replica_spread every 5 steps: "
            f"{[float(f'{s:.3g}') for s in run.spreads]} "
            f"(bound {SPREAD_BOUND})")
        require(
            max(run.spreads) <= SPREAD_BOUND,
            f"replica_spread reached {max(run.spreads)}, bound {SPREAD_BOUND}",
        )
    del trainer

    for arm, kw in (("overlap", dict(overlap=True)),
                    ("exact", dict(compressed=False))):
        run = train(
            PodTrainer(mesh, params, loss, **kw), batch, sizes.train_steps, lr
        )
        info[arm] = run.times()
        say(f"  {arm}: {run}; compressed ended at {ref:.4f}, tolerance "
            f"{ARM_LOSS_TOL}")
        require(
            abs(run.losses[-1] - ref) <= ARM_LOSS_TOL,
            f"{arm}: final loss {run.losses[-1]} against compressed {ref}",
        )
    after = _bytes_in_use(devices)
    if after is not None:
        peak = [int(d.memory_stats()["peak_bytes_in_use"]) for d in devices]
        say(f"  after training: bytes_in_use {after}, peak {peak}")
    return info


def run_resnet(n_peer: int, sizes: Sizes):
    """ResNet-18 — the model with many leaves for the per-leaf scales — takes
    a few steps of the compressed program."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from shared_tensor_tpu.models import resnet as r
    from shared_tensor_tpu.parallel.mesh import make_mesh
    from shared_tensor_tpu.train import PodTrainer

    cfg = sizes.resnet
    mesh = make_mesh(n_peer, 1)
    params = r.init_params(jax.random.key(SEED + 4), cfg)
    trainer = PodTrainer(mesh, params, lambda p, b: r.loss_fn(p, b, cfg))
    k_img, k_lab = jax.random.split(jax.random.key(SEED + 5))
    shape = (n_peer, sizes.resnet_batch, sizes.resnet_hw, sizes.resnet_hw, 3)
    batch = trainer.shard_batch((
        jax.random.normal(k_img, shape, jnp.float32),
        jax.random.randint(k_lab, shape[:2], 0, cfg.classes),
    ))
    run = train(trainer, batch, sizes.resnet_steps, RESNET_LR)
    scales = np.asarray(run.scales)
    leaves = trainer.spec.num_leaves
    say(f"resnet on ({n_peer} x 1): {trainer.spec.total_n} params, {leaves} "
        f"leaves, {int((scales > 0).sum())}/{scales.size} leaf scales "
        f"nonzero; {run}")
    require(
        scales.shape == (n_peer, leaves) and np.isfinite(scales).all(),
        f"resnet: scales of shape {scales.shape}, want {(n_peer, leaves)}",
    )
    require((scales > 0).any(), "resnet: every leaf scale is 0")
    return run.times()


def run_device_tier(sizes: Sizes) -> None:
    """core.py's device tier, in process: the sender quantizes the default
    burst in one dispatch, the receiver applies it in one."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from shared_tensor_tpu import SharedTensor
    from shared_tensor_tpu.comm import wire
    from shared_tensor_tpu.models import char_rnn as m
    from shared_tensor_tpu.ops.table import flatten

    template = m.init_params(jax.random.key(SEED), sizes.char)
    a, b = SharedTensor(template), SharedTensor(template)
    require(not a.host_tier, "SharedTensor chose the host (numpy) tier")
    # what comm/peer.py sends when Config.device_frame_burst is left at 0
    k = min(16, wire.burst_frames_cap(a.spec))
    require(k == 16, f"default device burst is {k} frames here, want 16")
    a.new_link(1, seed=False)
    b.new_link(1, seed=False)
    b.new_link(2, seed=False)  # a second link: the flood has two targets
    (delta,) = _peer_trees(
        template, 1, SEED + 6,
        lambda key, s: jax.random.uniform(key, s, jnp.float32, -1.0, 1.0),
    )
    a.add(delta)
    target = np.asarray(flatten(delta, a.spec))
    t0 = time.perf_counter()
    bursts = 0
    frames = []
    while frames is not None and bursts < 20:
        seq, stacked = a.begin_frame_burst_device(1, k)
        frames = a.finish_frame_burst(stacked)
        a.ack_frame(1, seq)
        if frames is not None:
            b.receive_frames(1, frames)
            bursts += 1
    require(frames is None, f"sender not idle after {bursts} bursts of {k}")
    err = float(np.abs(np.asarray(b.snapshot_flat()) - target).max())
    flood = float(np.abs(np.asarray(b.drop_link(2)) - target).max())
    say(f"device tier: {a.frames_out} frames in {bursts} bursts of up to {k} "
        f"({time.perf_counter() - t0:.1f} s with compiles, informational); "
        f"replica off by {err:.2e}, flooded residual by {flood:.2e} "
        f"(tolerance {BURST_ATOL})")
    require(a.frames_out == b.frames_in, "frames sent != frames applied")
    require(err <= BURST_ATOL, f"device tier: replica off by {err}")
    require(flood <= BURST_ATOL, f"device tier: flood off by {flood}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--rehearse-cpu", action="store_true",
        help="tiny sizes on four virtual CPU devices, Pallas interpreted; "
        "never chosen by the script itself",
    )
    args = ap.parse_args(argv)
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        count = "--xla_force_host_platform_device_count"
        flags = re.sub(rf"{count}=\d+", "", os.environ.get("XLA_FLAGS", ""))
        os.environ["XLA_FLAGS"] = f"{flags} {count}=4".strip()
        os.environ["ST_CODEC"] = "pallas"  # the kernels, interpreted
        os.environ["ST_HOST_CODEC"] = "xla"  # core.py's device tier

    import jax
    import jaxlib

    from shared_tensor_tpu.models.char_rnn import CharRNNConfig
    from shared_tensor_tpu.models.resnet import ResNetConfig
    from shared_tensor_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    events = collections.Counter()  # JAX's, the compile cache's among them
    jax.monitoring.register_event_listener(
        lambda name, **_: events.update([name])
    )
    warm = os.path.isdir(cache_dir) and len(os.listdir(cache_dir))

    t_start = time.perf_counter()
    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    say(f"platform={device['platform']} device_kind={device['kind']!r} "
        f"count={device['count']} default_backend={jax.default_backend()}")
    say(f"jax {jax.__version__}, jaxlib {jaxlib.__version__}, libtpu "
        f"{importlib.metadata.version('libtpu')}, python "
        f"{sys.version.split()[0]}")
    say(f"compile cache: {cache_dir} ({warm or 'no'} entries at start)")
    want = "cpu" if args.rehearse_cpu else "tpu"
    if device["platform"] != want:
        print(
            f"chip_smoke: jax.devices()[0].platform is "
            f"{device['platform']!r}, need {want!r}; there is no CPU "
            "fallback (--rehearse-cpu is the tiny CPU rehearsal)",
            file=sys.stderr,
        )
        return 1

    if args.rehearse_cpu:
        sizes = Sizes(
            char=CharRNNConfig(vocab=64, embed=32, hidden=64, layers=2),
            batch=2, seq=32, train_steps=10,
            resnet=ResNetConfig(stages=(1, 1), width=8, classes=4),
            resnet_batch=4, resnet_hw=16, resnet_steps=2,
        )
    else:
        # full width; batch 16 x seq 256 a peer is benchmarks/train_bench.py
        sizes = Sizes(
            char=CharRNNConfig(), batch=16, seq=256, train_steps=60,
            resnet=ResNetConfig(), resnet_batch=32, resnet_hw=32,
            resnet_steps=3,
        )

    n = len(devices)
    meshes = [(n, 1)]
    if n >= 4:
        meshes += [s for s in ((4, 1), (2, 2)) if s not in meshes]
    info = {}
    for n_peer, n_shard in meshes:
        info[f"char_rnn_{n_peer}x{n_shard}"] = run_mesh(n_peer, n_shard, sizes)
    info[f"resnet_{n}x1"] = run_resnet(n, sizes)
    run_device_tier(sizes)

    wall = time.perf_counter() - t_start
    say(f"meshes run: {meshes}")
    hits, misses = (
        events[f"/jax/compilation_cache/cache_{kind}"]
        for kind in ("hits", "misses")
    )
    say(f"compile cache: {hits} hits, {misses} misses this run"
        + (" (warm start)" if warm else " (cold start)"))
    say("informational times, not a baseline: "
        + json.dumps({"wall_s": round(wall, 1), **info}))
    result = {"ok": True, "device": device}
    if args.rehearse_cpu:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
