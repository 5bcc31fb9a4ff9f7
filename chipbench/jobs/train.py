"""Job kind ``train``: a model of ``shared_tensor_tpu.models`` trained through
``PodTrainer`` (the default compressed fused program), closed loop.

Set-up (all of it counted in ``setup_s``): parameters, images and labels are
made on the device from ``--seed``; the program's loss is held to the plain
float32 reference on a seeded sample of the first batch; a few ``PodTrainer``
steps are held to bare ``jax.value_and_grad`` + SGD on the same pytree and
batches (those steps are also the warm-up of the one program the window
runs). The window then runs steps back to back with one step in flight.

A traced run (``--trace 1``) times the default program and a
``PodTrainer(sync=False)`` of the same model through the same entry point
for ``arm_seconds`` each (the outside timing ``sync_share_of_step`` rests on
until the program has scopes), then traces ``trace_steps`` steps of the
default program.
"""

from __future__ import annotations

import statistics
import time

import numpy as np

from chipbench import counts, harness, trace_reduce
from chipbench.reference import resnet_ref


def run(ctx: harness.Context) -> dict:
    import jax
    import jax.numpy as jnp

    from shared_tensor_tpu.models import resnet
    from shared_tensor_tpu.parallel import make_mesh
    from shared_tensor_tpu.train import PodTrainer

    cfg = ctx.config
    model = cfg["rehearsal"]["model"] if ctx.rehearsal else cfg["model"]
    chk = cfg["checks"]
    rcfg = resnet.ResNetConfig(
        stages=tuple(model["stages"]), width=model["width"],
        classes=model["classes"], stem_kernel=model["stem_kernel"],
        stem_stride=model["stem_stride"], stem_pool=model["stem_pool"],
    )
    n_peer, n_shard = ctx.cell["mesh"]
    mesh = make_mesh(n_peer, n_shard)
    batch = int(ctx.sized("per_peer_batch"))
    n_set = int(cfg["resident_batches"])
    lr = float(cfg["learning_rate"])
    hw, ch = model["image_size"], model["channels"]

    def loss_fn(p, b):
        return resnet.loss_fn(p, b, rcfg)

    # --- inputs and weights, on the device, from the seed -------------------
    k_params, k_data, k_shift, k_sample = jax.random.split(jax.random.key(ctx.seed), 4)
    params = jax.jit(lambda k: resnet.init_params(k, rcfg))(k_params)

    @jax.jit
    def make_batch(k, i):
        k_img, k_lab = jax.random.split(jax.random.fold_in(k, i))
        labels = jax.random.randint(k_lab, (n_peer, batch), 0, model["classes"])
        shift = 0.5 * jax.random.normal(k_shift, (model["classes"], ch))
        images = jax.random.normal(k_img, (n_peer, batch, hw, hw, ch), jnp.float32)
        return images + shift[labels][:, :, None, None, :], labels

    trainer = PodTrainer(mesh, params, loss_fn)
    batches = [trainer.shard_batch(make_batch(k_data, i)) for i in range(n_set)]
    checks: dict = {"params": int(trainer.spec.total_n), "leaves": trainer.spec.num_leaves}

    # --- check: the program's loss against the plain reference ----------------
    n_ref = min(batch, int(chk["reference_loss_samples"]))
    pick = jax.random.permutation(k_sample, batch)[:n_ref]
    sample = (batches[0][0][0][pick], batches[0][1][0][pick])
    point = dict(params, blocks=[dict(b, scale2=jnp.ones_like(b["scale2"]))
                                 for b in params["blocks"]])
    loss_prog = float(jax.jit(loss_fn)(point, sample))
    loss_ref = float(jax.jit(lambda p, b: resnet_ref.loss(p, b, model))(point, sample))
    checks["reference_loss"] = {
        "program": loss_prog, "reference": loss_ref,
        "diff": abs(loss_prog - loss_ref), "tol": chk["reference_loss_tol"],
    }
    ok = abs(loss_prog - loss_ref) <= chk["reference_loss_tol"]

    # --- check: PodTrainer's steps against bare value_and_grad + SGD ----------
    @jax.jit
    def sgd_step(p, b, step_lr):
        loss, g = jax.value_and_grad(loss_fn)(p, b)
        return loss, jax.tree.map(lambda a, d: a - step_lr * d, p, g)

    n_sgd = min(int(chk["sgd_steps"]), n_set)
    losses_dev = []  # every trainer loss since the seed, in order
    for i in range(n_sgd):
        losses_dev.append(trainer.step(batches[i], lr)[0])
    bare, p_ref = [], params
    for i in range(n_sgd):
        loss, p_ref = sgd_step(
            p_ref, (batches[i][0][0], batches[i][1][0]), jnp.float32(lr)
        )
        bare.append(float(loss))
    del p_ref, point, sample
    got = [float(l[0]) for l in jax.device_get(losses_dev)]
    sgd_diff = max(abs(a - b) for a, b in zip(got, bare))
    checks["sgd_losses"] = {
        "trainer": got, "bare": bare, "diff": sgd_diff, "tol": chk["sgd_loss_tol"],
    }
    ok = ok and sgd_diff <= chk["sgd_loss_tol"]

    # --- warm: the rest of the resident set once -------------------------------
    for i in range(n_sgd, n_set):
        losses_dev.append(trainer.step(batches[i], lr)[0])
    jax.block_until_ready(losses_dev[-1])

    group_s = float(ctx.sized("group_ms")) / 1e3

    def closed_loop(tr, seconds=None, steps=None, annotate=False, keep=None):
        """Steps back to back, one in flight. Returns (completions,
        dispatch seconds, steps): completions[0] is the origin, then one
        host-clock time per step seen complete."""
        span = harness.annotate if annotate else harness.no_span
        dispatch = 0.0
        pending = None
        i = 0
        t0 = time.perf_counter()
        done = [t0]
        while True:
            with span("dispatch"):
                a = time.perf_counter()
                loss, _ = tr.step(batches[i % n_set], lr)
                dispatch += time.perf_counter() - a
            if keep is not None:
                keep.append(loss)
            if pending is not None:
                with span("wait_step"):
                    pending.block_until_ready()
                done.append(time.perf_counter())
            pending = loss
            i += 1
            if (steps is not None and i >= steps) or (
                seconds is not None and time.perf_counter() - t0 >= seconds
            ):
                break
        with span("wait_last"):
            pending.block_until_ready()
        done.append(time.perf_counter())
        return done, dispatch, i

    out: dict = {"checks": checks}
    if not ctx.trace:
        ctx.setup_done()
        compiled_before = ctx.compiles.count
        done, _, steps = closed_loop(trainer, seconds=ctx.seconds, keep=losses_dev)
        compiled_inside = ctx.compiles.count - compiled_before
        window_s = done[-1] - done[0]
        groups = harness.grouped_step_ms(done, group_s)
        out["end_to_end"] = {
            "train_samples_per_s": steps * batch * n_peer / window_s / ctx.cell["chips"],
            "train_step_p95_ms": harness.percentile(groups, 95) if groups else float("nan"),
        }
        checks["window"] = {
            "steps": steps, "window_s": window_s, "groups": len(groups),
            "step_ms_median": statistics.median(groups) if groups else None,
        }
    else:
        # the sync=False arm: the same entry point, no exchange
        nosync = PodTrainer(mesh, params, loss_fn, sync=False)
        for i in range(2):
            jax.block_until_ready(nosync.step(batches[i], lr)[0])
        ctx.setup_done()
        compiled_before = ctx.compiles.count
        arm_s = float(ctx.sized("arm_seconds"))
        done, dispatch, steps = closed_loop(trainer, seconds=arm_s, keep=losses_dev)
        done_ns, _, steps_ns = closed_loop(nosync, seconds=arm_s)
        g, g_ns = (harness.grouped_step_ms(d, group_s) for d in (done, done_ns))
        trace_steps = int(ctx.sized("trace_steps"))
        with harness.TraceWindow(ctx) as tw:
            closed_loop(trainer, steps=trace_steps, annotate=True, keep=losses_dev)
        compiled_inside = ctx.compiles.count - compiled_before
        summary = trace_reduce.reduce(
            tw.trace, trace_steps, harness.kernel_patterns()
        )
        steps += trace_steps
        out["observations"] = {
            "job": "train",
            "host": {
                "dispatch_s": dispatch, "dispatch_calls": steps - trace_steps,
                "step_ms": statistics.median(g) if g else None,
                "step_ms_nosync": statistics.median(g_ns) if g_ns else None,
                "samples_per_s_per_chip": (
                    (steps - trace_steps) * batch * n_peer
                    / (done[-1] - done[0]) / ctx.cell["chips"]
                ),
            },
            "trace": summary,
            "counts": {"train_flops_per_sample": counts.resnet_train_flops(model)},
            "peaks": ctx.peaks,
            "memory": {"peak_bytes": harness.memory_peak_bytes(ctx.cell["chips"])},
        }
        checks["arms"] = {"steps": steps - trace_steps, "steps_nosync": steps_ns,
                          "groups": len(g), "groups_nosync": len(g_ns)}
        ok = ok and summary is not None

    # --- after the window: every loss finite, the loss fell, nothing compiled --
    losses = np.asarray([float(l[0]) for l in jax.device_get(losses_dev)])
    failed = int(np.sum(~np.isfinite(losses)))
    first, last = float(np.mean(losses[:n_set])), float(np.mean(losses[-n_set:]))
    checks["loss"] = {"first_cycle": first, "last_cycle": last,
                      "margin": chk["loss_fall_margin"], "steps_since_seed": len(losses)}
    checks["compiled_inside_window"] = compiled_inside
    ok = ok and failed == 0 and compiled_inside == 0
    ok = ok and last < first - chk["loss_fall_margin"]
    out.update(correct=ok, attempted=steps, failed=failed)
    return out

