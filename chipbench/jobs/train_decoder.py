"""Job kind ``train_decoder``: a decoder language model trained through
``PodTrainer`` (the default compressed fused program, plain SGD), closed
loop, one sequence a peer a step. What ``train_lm`` does for ``mla_moe``, for
any model module the configuration names: the file's ``model_module``
(``Config``, ``init_params``, ``loss_fn(params, batch, cfg, positions=)``),
``reference_module`` (``loss_and_outputs(params, batch, file, choices, at)``)
and ``counts_module`` (``train_flops_per_sequence``,
``attention_kernel_flops``). A ``benchmark`` PR may merge the two jobs.

Set-up (all of it counted in ``setup_s``): weights and token ids are made on
the device from ``--seed``; then, at the timed sizes and before the window,

(a) the program's ``CE`` and logits at seeded positions of the first resident
    batch's sequences (from the loss's own path: the ``aux`` of the first
    bare step of (c)) are held to the plain float32 reference forced to the
    program's expert choices, and the reference's own choices along that
    path to the program's;
(b) the table after one ``PodTrainer`` step from the seed is held, leaf by
    leaf (the worst leaf and the median leaf each to its limit), to seed -
    lr x the reference's gradient; that step is taken at the file's
    ``update_check_lr`` (at the cell's own rate a weight's change is a few
    float32 spacings of the weight and the comparison would read rounding),
    and the state is then seeded again;
(c) three ``PodTrainer`` steps are held to bare ``jax.value_and_grad`` + SGD
    on the same pytree and batches (the bare steps first and the trainer's
    last, after the reference: the trainer holds no state while the bare
    steps or the reference run, which take 10 and 11 GB of their own);
(d) after the window: every loss finite, the last cycle of the resident set
    under the first by the file's margin, nothing compiled inside the window.

A cold set-up is compilation, of three large programs side by side: the fused
step by the main thread, the bare step (which also hands out what (a)
compares) and the reference's one program (its loss, logits, choices and
gradient) by two more.

One trainer is all that fits beside a table of this size: a traced run times
the default program, then traces ``trace_steps`` steps of it, and reads
device time by scope from the trace and the compiled step's text
(``chipbench/scope_reduce.py``) and the attention kernels' device time from
every ``st_attn_fwd`` / ``st_attn_bwd`` event of the traced window, by name.
Where the model module is missing (an older program) the job says so and
exits at once.
"""

from __future__ import annotations

import dataclasses
import importlib
import os
import re
import statistics
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from chipbench import harness, scope_reduce, trace_reduce
from chipbench.jobs.train_lm import choice_agreement

ATTN_KERNEL = re.compile(r"^st_attn_(fwd|bwd)\b")


def modules(cfg: dict):
    """``(model, reference, counts)``: the three modules the configuration
    file names."""
    return tuple(importlib.import_module(cfg[key])
                 for key in ("model_module", "reference_module", "counts_module"))


def model_config(model, m: dict, **over):
    """``model.Config`` from the configuration file's keys (or from its
    ``rehearsal.model`` group): the published names as they stand, lists as
    tuples. Of the keys the file cuts, ``published`` has the whole model's
    value and the ``Config`` takes that (the router's width, the whole
    vocabulary), but for ``num_hidden_layers``, where the file's count of
    layers is what runs; ``experts_held`` and the file's ``vocab_size`` say
    what is held here."""
    fields = {f.name for f in dataclasses.fields(model.Config)}
    keys = {k: tuple(v) if isinstance(v, list) else v for k, v in m.items() if k in fields}
    keys.update({k: v for k, v in m["published"].items()
                 if k in fields and k != "num_hidden_layers"})
    keys.update(experts_held=tuple(m["experts_held"]), vocab_held=m["vocab_size"])
    keys.update(over)
    return model.Config(**keys)


def attention_kernel_seconds(trace: dict, steps: int) -> dict | None:
    """Device seconds a step of the attention kernels, from every event of
    the traced window whose instruction is named ``st_attn_fwd`` or
    ``st_attn_bwd`` (the ``pallas_call`` names), the mean over devices:
    ``{"fwd": s, "bwd": s, "calls": n a step}``. None where the program has
    no such kernel."""
    if not trace or not trace.get("devices") or not steps:
        return None
    lo, hi = trace_reduce.window_of(trace)
    total, calls = {"fwd": 0.0, "bwd": 0.0}, 0
    for events in trace["devices"].values():
        for label, start, dur, _ in events:
            hit = ATTN_KERNEL.match(label)
            if hit and start + dur > lo and start < hi:
                total[hit.group(1)] += dur / 1e9
                calls += 1
    if not calls:
        return None
    n = len(trace["devices"]) * steps
    return {"fwd": total["fwd"] / n, "bwd": total["bwd"] / n, "calls": calls / n}


def check_programs(model, reference, mcfg, m: dict, margin: float):
    """The two programs the checks compile beside the fused step, unjitted:
    the program's bare step and the reference's side."""
    import jax

    def sgd_step(p, b, at, step_lr):
        """Bare ``value_and_grad`` + SGD on ``b [batch, seq]``; ``aux`` has
        what check (a) compares."""
        (loss, aux), g = jax.value_and_grad(
            lambda p: model.loss_fn(p, b, mcfg, positions=at), has_aux=True)(p)
        return loss, aux, jax.tree.map(lambda a, d: a - step_lr * d, p, g)

    def reference_checked(p, b, choices, at):
        """The reference's gradient on ``b [batch, seq]`` forced to
        ``choices``, and a sequence each: its loss, its logits at ``at``,
        and its own choices along that path held to the forced ones."""
        (_, outs), grads = jax.value_and_grad(
            lambda p: reference.loss_and_outputs(p, b, m, choices, at), has_aux=True)(p)
        return grads, [
            (ce, logits, choice_agreement(c, routed, margin))
            for c, (ce, logits, routed) in zip(choices, outs)]

    return sgd_step, reference_checked


def run(ctx: harness.Context) -> dict:
    import jax
    import jax.numpy as jnp

    try:
        model, reference, counts = modules(ctx.config)
    except ImportError as e:
        raise SystemExit(f"chipbench: this program cannot run {ctx.workload}: {e}")
    from shared_tensor_tpu.ops.table import unflatten
    from shared_tensor_tpu.parallel import make_mesh
    from shared_tensor_tpu.parallel.ici import init_state
    from shared_tensor_tpu.train import PodTrainer

    cfg = ctx.config
    m = cfg["rehearsal"]["model"] if ctx.rehearsal else cfg
    chk = dict(cfg["checks"], **(cfg["rehearsal"].get("checks", {}) if ctx.rehearsal else {}))
    lr = float(cfg["rehearsal"]["learning_rate"] if ctx.rehearsal else cfg["learning_rate"])
    mcfg = model_config(model, m)
    n_peer, n_shard = ctx.cell["mesh"]
    mesh = make_mesh(n_peer, n_shard)
    seq = int(ctx.sized("sequence_length"))
    batch = int(ctx.sized("per_peer_batch"))
    n_set = int(cfg["resident_batches"])
    vocab = mcfg.vocab_held

    def loss_fn(p, b):
        return model.loss_fn(p, b, mcfg)

    phase_s: dict = {}

    def phase(name, since):
        """Host seconds of one part of the set-up (compilation included), into
        ``checks``: what a cold or a warm ``setup_s`` is made of."""
        phase_s[name] = round(time.perf_counter() - since, 3)
        return time.perf_counter()

    t0 = time.perf_counter()
    k_params, k_data, k_perm, k_pos = jax.random.split(jax.random.key(ctx.seed), 4)
    seeded = jax.jit(lambda: model.init_params(k_params, mcfg))
    n_pos = min(seq, int(chk["reference_positions"]))
    n_sgd = min(int(chk["sgd_steps"]), n_set)

    # --- the checks' two programs, compiled beside the fused step --------------
    # from their shapes, by two threads, first of all (train_lm: a cold set-up is
    # 263 s so and 391 s with the three compiled one after the other; my chip
    # runs, PR 29). (The positions are an argument, not a constant: every seed then
    # finds the same programs in the compile cache.)
    sgd_step, reference_checked = check_programs(
        model, reference, mcfg, m, chk["choices_margin"])
    one = jax.sharding.SingleDeviceSharding(mesh.devices.flat[0])
    like = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one)
    args = (jax.tree.map(lambda a: like(a.shape, a.dtype), jax.eval_shape(seeded)),
            like((batch, seq), jnp.int32), like((n_pos,), jnp.int32), like((), jnp.float32))
    choices_like = [[like((seq, mcfg.num_experts_per_tok), jnp.int32)] * mcfg.expert_layers] * batch
    compiling = ThreadPoolExecutor(max_workers=2)
    reference_compiling = compiling.submit(
        lambda: jax.jit(reference_checked).lower(*args[:2], choices_like, args[2]).compile())
    sgd_compiling = compiling.submit(
        lambda: jax.jit(sgd_step, donate_argnums=0).lower(*args).compile())
    compiling.shutdown(wait=False)

    # --- inputs and weights, on the device, from the seed -------------------
    @jax.jit
    def make_batch(i):
        """Zipf ranks by the inverse CDF, permuted onto the held ids."""
        rank = jnp.arange(1, vocab + 1, dtype=jnp.float32)
        cdf = jnp.cumsum(rank ** -float(cfg["zipf_exponent"]))
        u = jax.random.uniform(jax.random.fold_in(k_data, i), (n_peer, batch, seq))
        ranks = jnp.minimum(jnp.searchsorted(cdf, u * cdf[-1]), vocab - 1)
        return jax.random.permutation(k_perm, vocab)[ranks].astype(jnp.int32)

    trainer = PodTrainer(mesh, seeded(), loss_fn)
    # the fused step leaves no room for a second copy of the seed (2.63 GB):
    # none is kept while a step runs
    trainer.template = None
    jax.block_until_ready(trainer.state.values)
    checks: dict = {
        "params": int(trainer.spec.total_n), "leaves": trainer.spec.num_leaves,
        "phase_s": phase_s,
    }
    t0 = phase("pod_trainer_init", t0)
    batches = [trainer.shard_batch(make_batch(i)) for i in range(n_set)]
    tokens0 = batches[0][0]  # peer 0's sequences of the first batch, [batch, seq]
    positions = jnp.sort(jax.random.permutation(k_pos, seq)[:n_pos])

    # --- (b), its step: one PodTrainer step from the seed ------------------------
    # at the file's check rate, at which every leaf's change stands far above
    # the float32 spacing of its parameters; the state is then seeded again
    text = None
    if ctx.trace:
        # compiled once: the text for the join of trace events with scopes,
        # and the program the steps run
        trainer._step = trainer.lower(batches[0], lr).compile()
        text = trainer._step.as_text()
    lr_b = float(chk["update_check_lr"])
    jax.block_until_ready(trainer.step(batches[0], lr_b)[0])
    # peer 0's table after it waits on the host for the reference's gradient
    # and the trainer holds no state (5.25 GB at the cell's size) until the
    # bare steps and the reference, 10 and 11 GB of their own, are done
    stepped, trainer.state = jax.device_get(trainer.state.values[0]), None
    t0 = phase("first_step", t0)

    # --- (c), its bare half: value_and_grad + SGD ------------------------------
    # the first step's ``aux`` is the seed's forward on tokens0, for (a)
    sgd_step = sgd_compiling.result()
    t0 = phase("sgd_compiled", t0)
    bare, got, p_ref = [], None, seeded()
    for i in range(n_sgd):
        loss, aux, p_ref = sgd_step(p_ref, batches[i][0], positions, jnp.float32(lr))
        bare.append(float(loss))
        got = got or aux
    del p_ref, aux
    t0 = phase("sgd_steps", t0)

    # --- (a) forward against the reference, forced to the program's choices ----
    params = seeded()
    reference_checked = reference_compiling.result()
    t0 = phase("reference_compiled", t0)
    ref_grads, ref = reference_checked(
        params, tokens0, [list(c) for c in got["choices"]], positions)
    checks["reference_forward"] = []
    ok = True
    for b, (ce, ref_logits, (agree, outside)) in enumerate(ref):
        logits_err = float(jnp.linalg.norm(got["logits"][b] - ref_logits)
                           / jnp.linalg.norm(ref_logits))
        mine = float(got["ce_main_of"][b])
        checks["reference_forward"].append({
            "ce_main": [mine, float(ce)], "ce_tol": chk["ce_tol"],
            "logits_rel_err": logits_err, "logits_rel_tol": chk["logits_rel_tol"],
            "choices_agree": float(agree), "choices_agree_min": chk["choices_agree_min"],
            "choices_outside_margin": int(outside), "choices_margin": chk["choices_margin"],
        })
        ok = ok and (
            abs(mine - float(ce)) <= chk["ce_tol"]
            and logits_err <= chk["logits_rel_tol"]
            and float(agree) >= chk["choices_agree_min"] and int(outside) == 0
        )
    del got, ref, ref_logits
    t0 = phase("reference", t0)

    # --- (b) the table after that step against seed - lr x reference gradient ---
    names = sorted(params)

    @jax.jit
    def update_errors(values_row, p, g):
        """Per leaf ``||change - expected|| / ||expected||`` (every leaf of
        this model takes a gradient; one that took none would read inf)."""
        table = unflatten(values_row, trainer.spec)
        return jnp.stack([
            jnp.linalg.norm(table[n] - p[n] + lr_b * g[n]) / jnp.linalg.norm(lr_b * g[n])
            for n in names])

    errs = jax.device_get(update_errors(stepped, params, ref_grads))
    del ref_grads, stepped, params
    checks["reference_update"] = {
        "rel_err_max": float(errs.max()), "rel_err_median": float(np.median(errs)),
        "worst_leaf": names[int(np.argmax(errs))], "tol": chk["update_rel_tol"],
        "median_tol": chk["update_rel_median_tol"], "lr": lr_b,
    }
    ok = ok and float(errs.max()) <= chk["update_rel_tol"]
    ok = ok and float(np.median(errs)) <= chk["update_rel_median_tol"]
    t0 = phase("reference_update", t0)

    # --- (c) PodTrainer's steps, seeded again, against the bare ones ------------
    trainer.state = init_state(mesh, trainer.spec, seeded())
    losses_dev = [trainer.step(batches[i], lr)[0] for i in range(n_sgd)]  # every loss since the seed
    trained = [float(l[0]) for l in jax.device_get(losses_dev)]
    sgd_diff = max(abs(a - b) for a, b in zip(trained, bare))
    checks["sgd_losses"] = {
        "trainer": trained, "bare": bare, "diff": sgd_diff, "tol": chk["sgd_loss_tol"],
    }
    ok = ok and sgd_diff <= chk["sgd_loss_tol"]
    # warm: the rest of the resident set once
    for i in range(n_sgd, n_set):
        losses_dev.append(trainer.step(batches[i], lr)[0])
    jax.block_until_ready(losses_dev[-1])
    t0 = phase("trainer_steps", t0)

    group_s = float(ctx.sized("group_ms")) / 1e3

    def closed_loop(seconds=None, steps=None, annotate=False):
        """Steps back to back, one in flight. Returns (completions, dispatch
        seconds, steps): completions[0] is the origin, then one host-clock
        time per step seen complete."""
        span = harness.annotate if annotate else harness.no_span
        dispatch = 0.0
        pending = None
        i = 0
        t0 = time.perf_counter()
        done = [t0]
        while True:
            with span("dispatch"):
                a = time.perf_counter()
                loss, _ = trainer.step(batches[i % n_set], lr)
                dispatch += time.perf_counter() - a
            losses_dev.append(loss)
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
    per_step = batch * n_peer / ctx.cell["chips"]  # sequences a step a chip
    if not ctx.trace:
        ctx.setup_done()
        compiled_before = ctx.compiles.count
        done, _, steps = closed_loop(seconds=ctx.seconds)
        compiled_inside = ctx.compiles.count - compiled_before
        window_s = done[-1] - done[0]
        groups = harness.grouped_step_ms(done, group_s)
        out["end_to_end"] = {
            "train_samples_per_s": steps * per_step / window_s,
            "train_step_p95_ms": harness.percentile(groups, 95) if groups else float("nan"),
        }
        checks["window"] = {
            "steps": steps, "window_s": window_s, "groups": len(groups),
            "step_ms_median": statistics.median(groups) if groups else None,
        }
    else:
        ctx.setup_done()
        compiled_before = ctx.compiles.count
        done, dispatch, steps = closed_loop(seconds=float(ctx.sized("arm_seconds")))
        g = harness.grouped_step_ms(done, group_s)
        trace_steps = int(ctx.sized("trace_steps"))
        with harness.TraceWindow(ctx) as tw:
            closed_loop(steps=trace_steps, annotate=True)
        compiled_inside = ctx.compiles.count - compiled_before
        if ctx.keep_trace:
            with open(os.path.join(ctx.keep_trace, ctx.workload, "step_hlo.txt"), "w") as f:
                f.write(text)
        summary = trace_reduce.reduce(tw.trace, trace_steps, harness.kernel_patterns())
        scopes = scope_reduce.by_scope(tw.trace, text, trace_steps)
        attn = attention_kernel_seconds(tw.trace, trace_steps)
        out["observations"] = {
            "job": "train_decoder",
            "host": {
                "dispatch_s": dispatch, "dispatch_calls": steps,
                "step_ms": statistics.median(g) if g else None,
                "samples_per_s_per_chip": steps * per_step / (done[-1] - done[0]),
            },
            "trace": summary,
            "scopes": scopes,
            "attn_kernels": attn,
            "counts": {
                "train_flops_per_sample": counts.train_flops_per_sequence(m, seq),
                "attn_kernel_flops_per_step": counts.attention_kernel_flops(m, seq) * batch,
            },
            "peaks": ctx.peaks,
            "memory": {"peak_bytes": harness.memory_peak_bytes(ctx.cell["chips"])},
        }
        checks["arm"] = {"steps": steps, "groups": len(g),
                         "step_ms_median": statistics.median(g) if g else None}
        checks["scopes_ms_per_step"] = scopes
        checks["attn_kernels_s_per_step"] = attn
        steps += trace_steps
        ok = ok and summary is not None

    # --- (d) after the window: losses finite, the loss fell, nothing compiled --
    losses = np.asarray([float(l[0]) for l in jax.device_get(losses_dev)])
    failed = int(np.sum(~np.isfinite(losses)))
    first, last = float(np.mean(losses[:n_set])), float(np.mean(losses[-n_set:]))
    checks["loss"] = {"first_cycle": first, "last_cycle": last,
                      "margin": chk["loss_fall_margin"], "steps_since_seed": len(losses)}
    checks["compiled_inside_window"] = compiled_inside
    # the newest step's expert-layer counters, over peers: printed, not judged
    aux = jax.device_get(trainer.aux)
    if ctx.trace:
        out["observations"]["aux"] = {k: np.asarray(v).tolist() for k, v in aux.items()}
    pairs = np.asarray(aux["moe_pairs_held"], np.float64)
    checks["aux"] = {
        "ce_main": float(np.mean(aux["ce_main"])),
        "moe_pairs_held": pairs.sum(axis=0).tolist(),
        "moe_load_max_over_mean": np.max(aux["moe_load_max_over_mean"], axis=0).tolist(),
        "moe_tokens_unrouted_share": np.mean(aux["moe_tokens_unrouted_share"], axis=0).tolist(),
        "moe_rows_executed_over_pairs": float(
            np.sum(aux["moe_rows_executed"]) / max(1.0, pairs.sum())),
    }
    ok = ok and failed == 0 and compiled_inside == 0
    ok = ok and last < first - chk["loss_fall_margin"]
    out.update(correct=bool(ok), attempted=steps, failed=failed)
    return out
