"""Job kind ``table_sync``: replicas of a parameter table kept consistent by
the pod tier's public entry points, with no model: ``make_spec`` ->
``init_state`` -> ``add_updates_raw`` (inside one jitted call of the
benchmark's, state donated) -> ``build_sync_step``.

Set-up: the table's values and one update per peer are made on the device
from ``--seed``; every program is warmed on the fresh table in a way that
leaves it bit for bit fresh (an add with coefficient 0, a sync step of an
all-zero residual). Then, timed apart, the catch-up: every peer adds its
update once and sync steps run back to back, each followed by a poll of the
residual that is read one frame later (a reader does not stall the syncing
side), until a poll is under the configuration's threshold. Then the window:
the stream.

``correct`` (all outside the window): conservation on sampled rows of every
leaf with no drain, bounded lag, the catch-up's frame bound, and one more
sync step held to the plain NumPy codec of chipbench/reference/codec_np.py.
Reductions run on the device; nothing of table size is fetched.
"""

from __future__ import annotations

import collections
import time

import numpy as np

from chipbench import counts, harness, trace_reduce
from chipbench.reference import codec_np

LANES = 128


def leaf_layout(cfg: dict, rehearsal: bool) -> dict[str, tuple[int, ...]]:
    """One decoder layer's leaves in the checkpoint's layout, by name."""
    sizes = dict(cfg, **cfg["rehearsal"]) if rehearsal else cfg
    h, inter, e = sizes["hidden_size"], sizes["intermediate_size"], sizes["num_experts"]
    kv = h * sizes["num_key_value_heads"] // sizes["num_attention_heads"]
    leaves = {
        "self_attn.q_proj.weight": (h, h),
        "self_attn.k_proj.weight": (kv, h),
        "self_attn.v_proj.weight": (kv, h),
        "self_attn.o_proj.weight": (h, h),
        "self_attn.q_norm.weight": (h,),
        "self_attn.k_norm.weight": (kv,),
        "input_layernorm.weight": (h,),
        "post_attention_layernorm.weight": (h,),
        "mlp.gate.weight": (e, h),
    }
    for i in range(e):
        leaves[f"mlp.experts.{i:02d}.gate_proj.weight"] = (inter, h)
        leaves[f"mlp.experts.{i:02d}.up_proj.weight"] = (inter, h)
        leaves[f"mlp.experts.{i:02d}.down_proj.weight"] = (h, inter)
    return leaves


def leaf_magnitudes(ns, lo: float, hi: float, seed: int) -> np.ndarray:
    """A fixed geometric ladder over [lo, hi] for each group of equally sized
    leaves, permuted within the group by the seed: every seed streams the
    same set of magnitudes in another order."""
    rng = np.random.default_rng(seed)
    mags = np.zeros(len(ns), np.float64)
    groups = collections.defaultdict(list)
    for i, n in enumerate(ns):
        groups[n].append(i)
    for n in sorted(groups):
        idx = groups[n]
        ladder = (
            np.geomspace(lo, hi, len(idx)) if len(idx) > 1
            else np.array([np.sqrt(lo * hi)])
        )
        mags[idx] = rng.permutation(ladder)
    return mags.astype(np.float32)


def _row_geometry(spec):
    """(first row of each leaf, live rows of each leaf)."""
    row_off = np.concatenate([[0], np.cumsum([p // LANES for p in spec.padded])])
    return row_off, [-(-n // LANES) for n in spec.ns]


def codec_check(state, sync_step, spec, names, rng, rows_per_leaf: int, ulps: float):
    """One sync step held to chipbench/reference/codec_np.py on the five
    smallest leaves and one expert's gate_proj and another's down_proj: each
    peer's scale from the leaf's whole residual (bit for bit, unless the exact
    RMS is within float32's reach of a power of two), then residuals (bit for
    bit) and applied values (to ``ulps``) on ``rows_per_leaf`` rows of each.
    Returns (state after the step, report)."""
    import jax

    n_peer = state.values.shape[0]
    row_off, live_rows = _row_geometry(spec)
    small = sorted(range(spec.num_leaves), key=lambda i: (spec.ns[i], i))[:5]
    experts = [i for i, k in enumerate(names) if ".experts." in k]
    pick = [
        int(next(i for i in rng.permutation(experts) if names[i].endswith(suffix)))
        for suffix in ("gate_proj.weight", "down_proj.weight")
    ]
    leaves = small + pick
    spans = [(int(row_off[i]) * LANES, spec.padded[i], spec.ns[i]) for i in leaves]
    sub_rows = [
        np.sort(rng.choice(live_rows[i], min(live_rows[i], rows_per_leaf), replace=False))
        for i in leaves
    ]

    @jax.jit
    def whole_residuals(st):
        return [st.residual[:, o:o + p] for o, p, _ in spans]

    @jax.jit
    def sampled_rows(st):
        return [(st.values[:, o:o + p].reshape(n_peer, -1, LANES)[:, r],
                 st.residual[:, o:o + p].reshape(n_peer, -1, LANES)[:, r])
                for (o, p, _), r in zip(spans, sub_rows)]

    pre_whole, pre = jax.device_get((whole_residuals(state), sampled_rows(state)))
    state, scales = sync_step(state)
    post = jax.device_get(sampled_rows(state))
    dev_scales = np.asarray(jax.device_get(scales))
    lane = np.arange(LANES)[None, :]
    report = {"leaves": [names[i] for i in leaves], "scales_equal": 0,
              "scales_skipped_near_pow2": 0, "scales_differ": 0,
              "residual_rows_differ": 0, "values_rows_off": 0}
    for j, leaf in enumerate(leaves):
        n = spans[j][2]
        # padding lanes are held at 0 by the program and belong to no leaf
        live_lanes = lane < np.clip(n - sub_rows[j] * LANES, 0, LANES)[:, None]
        frames = []
        for p in range(n_peer):
            live = pre_whole[j][p][:n]
            s_np = codec_np.leaf_scale(live)
            s_dev = np.float32(dev_scales[p, leaf])
            if s_np == s_dev:
                report["scales_equal"] += 1
            elif codec_np.near_pow2(codec_np.leaf_rms(live)):
                report["scales_skipped_near_pow2"] += 1
                s_np = s_dev
            else:
                report["scales_differ"] += 1
            bits, r2 = codec_np.quantize(pre[j][1][p], s_np)
            frames.append((s_np, bits))
            if not np.array_equal(r2[live_lanes], post[j][1][p][live_lanes]):
                report["residual_rows_differ"] += 1
        for p in range(n_peer):
            want = codec_np.apply_others(pre[j][0][p], frames, p)
            got = post[j][0][p]
            ulp = np.spacing(np.maximum(np.abs(want), np.abs(got)).astype(np.float32))
            if not np.all((np.abs(want - got) <= ulps * ulp)[live_lanes]):
                report["values_rows_off"] += 1
    report["ok"] = not (
        report["scales_differ"] or report["residual_rows_differ"] or report["values_rows_off"]
    )
    return state, report


def run(ctx: harness.Context) -> dict:
    import jax
    import jax.numpy as jnp

    from shared_tensor_tpu.ops.table import make_spec, unflatten
    from shared_tensor_tpu.parallel import (
        build_sync_step, init_state, make_mesh, state_sharding,
    )
    from shared_tensor_tpu.parallel.ici import add_updates_raw

    cfg, tab, chk = ctx.config, ctx.config["table"], ctx.config["checks"]
    n_peer, n_shard = ctx.cell["mesh"]
    mesh = make_mesh(n_peer, n_shard)
    layout = leaf_layout(cfg, ctx.rehearsal)
    names = sorted(layout)  # the order jax flattens a dict in
    spec = make_spec(
        {k: jax.ShapeDtypeStruct(v, jnp.float32) for k, v in layout.items()}
    )
    if not ctx.rehearsal and (
        spec.num_leaves != tab["expect_leaves"] or spec.total_n != tab["expect_elements"]
    ):
        raise SystemExit(
            f"chipbench: the table has {spec.num_leaves} leaves and "
            f"{spec.total_n} elements, the configuration expects "
            f"{tab['expect_leaves']} and {tab['expect_elements']}"
        )
    rows = spec.total // LANES
    n_leaves = spec.num_leaves
    row_leaf_np = spec.row_leaf()
    rng = np.random.default_rng(ctx.seed)
    mags = leaf_magnitudes(spec.ns, tab["update_mag_lo"], tab["update_mag_hi"], ctx.seed)
    is_norm = np.array([len(layout[k]) == 1 for k in names])
    leaf_std = np.where(is_norm, 0.0, tab["value_std"]).astype(np.float32)
    leaf_mean = np.where(is_norm, 1.0, 0.0).astype(np.float32)
    coeffs = rng.standard_normal(int(tab["coefficients"])).astype(np.float32)

    # a few sampled rows of every leaf, for conservation
    row_off, live_rows = _row_geometry(spec)
    per_leaf = int(chk["sample_rows_per_leaf"])
    idx_c = np.concatenate([
        row_off[i] + np.sort(rng.choice(live_rows[i], min(live_rows[i], per_leaf), replace=False))
        for i in range(n_leaves)
    ])
    sh = state_sharding(mesh)
    row_leaf, rowcount = jnp.asarray(row_leaf_np), jnp.asarray(spec.live_rowcount())
    d_idx_c = jnp.asarray(idx_c, jnp.int32)
    # 1 / update magnitude of every row's leaf, spread on the host: on this
    # chip a gather over 3.3 M rows costs more than the pass it feeds
    d_inv_rows = jnp.asarray(1.0 / mags[row_leaf_np], jnp.float32)
    d_leaf_c = jnp.asarray(row_leaf_np[idx_c])

    # --- the table and the updates, on the device, from the seed -------------
    k_values, k_updates = jax.random.split(jax.random.key(ctx.seed))

    def _live(x, rowcount):  # padding lanes are exactly 0; x is [..., rows, 128]
        lane = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1)
        return jnp.where(lane < rowcount[:, None], x, 0.0)

    @jax.jit
    def make_values(k, std, mean, row_leaf, rowcount):
        x = jax.random.normal(k, (rows, LANES), jnp.float32)
        x = x * std[row_leaf][:, None] + mean[row_leaf][:, None]
        return unflatten(_live(x, rowcount).reshape(-1), spec)

    def _make_updates(k, mag, row_leaf, rowcount):
        x = jax.random.normal(k, (n_peer, rows, LANES), jnp.float32)
        x = x * mag[row_leaf][None, :, None]
        return _live(x, rowcount).reshape(n_peer, -1)

    make_updates = jax.jit(_make_updates, out_shardings=sh)

    template = make_values(
        k_values, jnp.asarray(leaf_std), jnp.asarray(leaf_mean), row_leaf, rowcount
    )
    state = init_state(mesh, spec, template)
    del template
    u = make_updates(k_updates, jnp.asarray(mags), row_leaf, rowcount)

    # --- the programs ----------------------------------------------------------
    add_scaled = jax.jit(
        lambda st, upd, c: add_updates_raw(st, c * upd), donate_argnums=(0,)
    )
    sync_step = build_sync_step(mesh, spec)

    def _rows(a, idx):  # [n_peer, total] -> [n_peer, len(idx), 128]
        return a.reshape(a.shape[0], rows, LANES)[:, idx]

    @jax.jit
    def lag(residual, inv_rows):
        """Worst peer's RMS of residual / leaf update magnitude over the whole
        table: one pass on the device, nothing fetched but the scalar. Not a
        sample: what is left after 25 frames is the Gaussian tail, a few
        elements in ten thousand, and a million sampled elements read it
        2 % off from seed to seed."""
        x = residual.reshape(n_peer, rows, LANES) * inv_rows[None, :, None]
        return jnp.max(jnp.sqrt(jnp.sum(x * x, axis=(1, 2)) / spec.total_n))

    @jax.jit
    def seed_rows(values, idx):
        return _rows(values, idx)[0]

    @jax.jit
    def conservation(st, upd, seed_c, idx, leaf, sum_c):
        """Per leaf, over the sampled rows and every peer p: the largest
        |values[p] + sum_{q != p} residual[q] - (seed + sum_c * sum_q u_q)|,
        and the magnitudes the tolerance is derived from."""
        v, r, uu = _rows(st.values, idx), _rows(st.residual, idx), _rows(upd, idx)
        lhs = v + (jnp.sum(r, axis=0, keepdims=True) - r)
        rhs = seed_c[None] + sum_c * jnp.sum(uu, axis=0)[None]
        seg = lambda x: jax.ops.segment_max(
            jnp.max(jnp.abs(x), axis=(0, 2)), leaf, num_segments=n_leaves
        )
        return seg(lhs - rhs), seg(v), seg(r), seg(uu)

    # --- warm every program on the fresh table, leaving it fresh ---------------
    seed_c = seed_rows(state.values, d_idx_c)
    state = add_scaled(state, u, np.float32(0.0))
    state, scales = sync_step(state)
    warm_lag = float(lag(state.residual, d_inv_rows))
    err0 = jax.device_get(
        conservation(state, u, seed_c, d_idx_c, d_leaf_c, np.float32(0.0))
    )[0]
    checks: dict = {
        "leaves": n_leaves, "elements": int(spec.total_n),
        "fresh_after_warm": {"lag": warm_lag, "max_err": float(np.max(err0)),
                             "max_scale": float(jnp.max(scales))},
    }
    ok = warm_lag == 0.0 and float(np.max(err0)) == 0.0
    jax.block_until_ready(state)
    ctx.setup_done()

    # --- the catch-up, timed apart ----------------------------------------------
    thr, cap = float(chk["catchup_threshold"]), int(chk["catchup_frames_bound"])
    t0 = time.perf_counter()
    state = add_scaled(state, u, np.float32(1.0))
    state, scales = sync_step(state)
    polls = collections.deque([lag(state.residual, d_inv_rows)])
    ratios = []
    while True:
        # the syncing side does not wait for its reader: the next frame is
        # dispatched before the last one's poll is read, so the device never
        # idles on the host and the time is the device's, not the poll's
        state, scales = sync_step(state)
        polls.append(lag(state.residual, d_inv_rows))
        ratios.append(float(polls.popleft()))
        if ratios[-1] < thr or len(ratios) >= 4 * cap:
            break
    catchup_ms = 1e3 * (time.perf_counter() - t0)
    frames = len(ratios)  # frames until the criterion held; one more is in flight
    jax.block_until_ready(state)
    # adds, sync steps and coefficients since the fresh table
    n_add, n_sync, sum_c = 1, frames + 1, 1.0
    checks["catchup"] = {"frames": frames, "bound": cap, "threshold": thr,
                         "last_ratios": ratios[-3:], "ms": catchup_ms}
    ok = ok and frames <= cap

    # --- the stream ---------------------------------------------------------------
    in_flight = int(tab["in_flight"])

    def stream(st, seconds=None, steps=None, annotate=False, start=0):
        span = harness.annotate if annotate else harness.no_span
        pend = collections.deque()
        dispatch, i, total_c, last = 0.0, 0, 0.0, None
        t0 = time.perf_counter()
        while True:
            c = coeffs[(start + i) % len(coeffs)]
            with span("dispatch"):
                a = time.perf_counter()
                st = add_scaled(st, u, c)
                st, last = sync_step(st)
                dispatch += time.perf_counter() - a
            total_c += float(c)
            pend.append(last)
            if len(pend) > in_flight:
                with span("wait_step"):
                    pend.popleft().block_until_ready()
            i += 1
            if (steps is not None and i >= steps) or (
                seconds is not None and time.perf_counter() - t0 >= seconds
            ):
                break
        with span("wait_last"):
            jax.block_until_ready(list(pend))
        return st, last, time.perf_counter() - t0, dispatch, i, total_c

    compiled_before = ctx.compiles.count
    out: dict = {"checks": checks}
    if not ctx.trace:
        state, scales, window_s, _, steps, dc = stream(state, seconds=ctx.seconds)
        out["end_to_end"] = {
            "sync_equiv_rate": 4.0 * spec.total_n * steps / window_s / 1e9,
            "sync_catchup_ms": catchup_ms,
        }
        checks["window"] = {"steps": steps, "window_s": window_s,
                            "step_ms": 1e3 * window_s / steps}
    else:
        arm_s = float(ctx.sized("arm_seconds"))
        state, scales, arm_window, dispatch, steps, dc = stream(state, seconds=arm_s)
        trace_steps = int(ctx.sized("trace_steps"))
        with harness.TraceWindow(ctx) as tw:
            state, scales, _, _, _, dc2 = stream(
                state, steps=trace_steps, annotate=True, start=steps
            )
        summary = trace_reduce.reduce(tw.trace, trace_steps, harness.kernel_patterns())
        dc += dc2
        out["observations"] = {
            "job": "table_sync",
            "host": {"dispatch_s": dispatch, "dispatch_calls": steps,
                     "step_ms": 1e3 * arm_window / steps},
            "trace": summary,
            "counts": {
                "kernel_bytes_per_step": counts.sync_step_kernel_bytes(
                    spec.total // n_shard, n_peer),
                "ici_bytes_per_step": counts.frame_ici_bytes(
                    spec.total // n_shard, n_leaves, n_peer),
            },
            "peaks": ctx.peaks,
            "memory": {"peak_bytes": harness.memory_peak_bytes(ctx.cell["chips"])},
        }
        steps += trace_steps
        ok = ok and summary is not None
    compiled_inside = ctx.compiles.count - compiled_before
    n_add, n_sync, sum_c = n_add + steps, n_sync + steps, sum_c + dc
    checks["compiled_inside_window"] = compiled_inside
    ok = ok and compiled_inside == 0

    # --- after the window: conservation with no drain, bounded lag --------------
    err, vmax, rmax, umax = (np.asarray(a, np.float64) for a in jax.device_get(
        conservation(state, u, seed_c, d_idx_c, d_leaf_c, np.float32(sum_c))
    ))
    cmax = max(1.0, float(np.max(np.abs(coeffs))))
    tol = (
        chk["conservation_sigmas"] * np.sqrt(n_add + n_sync) * 2.0**-24
        * (vmax + (n_peer - 1) * rmax + n_peer * cmax * umax)
    )
    worst = int(np.argmax(err / tol))
    checks["conservation"] = {
        "rounding_events": n_add + n_sync, "sum_c": sum_c,
        "worst_err_over_tol": float(err[worst] / tol[worst]),
        "worst_leaf": names[worst], "max_err": float(np.max(err)),
        "max_tol": float(np.max(tol)),
    }
    finite = bool(np.all(np.isfinite(err)))
    ok = ok and finite and bool(np.all(err <= tol))
    last_scales = np.asarray(jax.device_get(scales))
    lag_ratio = last_scales / mags[None, :]
    checks["bounded_lag"] = {"max_scale_over_update": float(np.max(lag_ratio)),
                             "min": float(np.min(lag_ratio)), "bound": chk["scale_bound"]}
    ok = ok and float(np.max(lag_ratio)) <= chk["scale_bound"]

    # --- one more sync step against the plain NumPy codec ------------------------
    state, checks["codec_vs_numpy"] = codec_check(
        state, sync_step, spec, names, rng, per_leaf, chk["values_ulps"]
    )
    ok = ok and checks["codec_vs_numpy"]["ok"]
    out.update(correct=ok, attempted=steps, failed=0 if finite else steps)
    return out

