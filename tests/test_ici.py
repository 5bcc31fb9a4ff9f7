"""Pod-tier (ICI) sync tests on the 8-device virtual CPU mesh.

SURVEY.md §4.2 tier 2: the sharded/collective path runs on
``--xla_force_host_platform_device_count=8`` CPU devices; identical code runs
on a real v5e-8. The semantic yardsticks come from the reference codec
(SURVEY.md §6.2 convergence table, Appendix B).
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shared_tensor_tpu.config import ScalePolicy
from shared_tensor_tpu.ops.table import (
    apply_table,
    make_spec,
    flatten,
    live_lanes,
    quantize_table,
)
from shared_tensor_tpu.ops.packing import words_rows
from shared_tensor_tpu.parallel import (
    add_updates,
    build_sync_step,
    frame_ici_bytes,
    init_state,
    read_peer,
    rows_per_shard,
)
from shared_tensor_tpu.parallel.mesh import make_mesh as make_mesh_strict
from tests._mesh import make_mesh


def template(key=0, shape=(40, 64)):
    k1, k2 = jax.random.split(jax.random.PRNGKey(key))
    return {
        "w": jax.random.normal(k1, shape, jnp.float32),
        "b": jax.random.normal(k2, (shape[1],), jnp.float32) * 1e-3,
    }


def _live_mask(spec):
    """f32[total]: 1 on live lanes, 0 on padding (what a real flatten leaves)."""
    live = live_lanes(jnp.asarray(spec.live_rowcount()))
    return live.reshape(-1).astype(jnp.float32)


def test_mesh_shapes():
    assert rows_per_shard(2048, 4) == 4
    with pytest.raises(ValueError):
        rows_per_shard(1024, 3)  # 8 rows not divisible by 3
    with pytest.raises(ValueError):
        make_mesh_strict(16, 1)  # more devices than exist
    mesh = make_mesh(4, 2)  # skips here on a <8-device backend
    assert mesh.shape == {"peer": 4, "shard": 2}


@pytest.mark.parametrize(
    "impl,shape", [("xla", (40, 64)), ("pallas", (40, 64)), ("pallas", (1290, 128))]
)
def test_parity_with_golden_codec(impl, shape):
    """One pod step == per-peer golden quantize + cross-apply of every other
    peer's frame, bit-for-bit (n_shard=1), on either tier of the row codec:
    the words a peer gathers (128 a row, as the kernels write and read them)
    are the golden codec's flat vector. (1290, 128) is 1304 rows, 24 (mod
    32) and (mod 256): two grid blocks, the last words row 24 table rows."""
    mesh = make_mesh(2, 1)
    tpl = template(shape=shape)
    spec = make_spec(tpl)
    state = init_state(mesh, spec, tpl)
    # give each peer a distinct pending update
    ups = jnp.stack(
        [flatten(jax.tree.map(lambda x: 0.1 * x, tpl), spec),
         flatten(jax.tree.map(lambda x: -0.3 * x, tpl), spec)]
    )
    state = add_updates(state, ups)
    v0 = np.asarray(state.values)
    r0 = np.asarray(state.residual)

    step = build_sync_step(mesh, spec, impl=impl)
    state2, scales = jax.block_until_ready(step(state))
    # golden: quantize each peer's residual, apply to the *other* peer
    frames, resids = [], []
    for p in range(2):
        f, r2 = quantize_table(jnp.asarray(r0[p]), spec)
        frames.append(f)
        resids.append(r2)
    for p in range(2):
        expect_v = apply_table(jnp.asarray(v0[p]), frames[1 - p], spec)
        np.testing.assert_array_equal(np.asarray(state2.values[p]), np.asarray(expect_v))
        np.testing.assert_array_equal(
            np.asarray(state2.residual[p]), np.asarray(resids[p])
        )
        np.testing.assert_array_equal(np.asarray(scales[p]), np.asarray(frames[p].scales))


@pytest.mark.parametrize("n_shard", [2, 4])
def test_sharded_matches_unsharded(n_shard):
    """Sharding the table over the shard axis must not change the math."""
    tpl = template(3)
    spec = make_spec(tpl)
    ups = jnp.stack(
        [flatten(jax.tree.map(lambda x: (0.05 * (p + 1)) * x, tpl), spec) for p in range(2)]
    )
    results = []
    for ns in (1, n_shard):
        mesh = make_mesh(2, ns)
        state = add_updates(init_state(mesh, spec, tpl), ups)
        step = build_sync_step(mesh, spec)
        state2, scales = jax.block_until_ready(step(state))
        results.append((np.asarray(state2.values), np.asarray(state2.residual), np.asarray(scales)))
    (v1, r1, s1), (v2, r2, s2) = results
    # partial-sum order differs across shards; pow2 flooring absorbs it
    np.testing.assert_array_equal(s1, s2)
    np.testing.assert_array_equal(v1, v2)
    np.testing.assert_array_equal(r1, r2)


def test_conservation_invariant():
    """values_p + sum_{q != p} residual_q is invariant under sync steps:
    nothing is lost or double-counted on the way to eventual consistency
    (the reference cannot even promise this — quirk Q7 races lose updates)."""
    mesh = make_mesh(4, 2)
    tpl = template(1)
    spec = make_spec(tpl)
    state = init_state(mesh, spec, tpl)
    key = jax.random.PRNGKey(7)
    ups = jax.random.normal(key, (4, spec.total)) * (
        jnp.arange(1, 5)[:, None].astype(jnp.float32)
    )
    ups = ups * _live_mask(spec)
    state = add_updates(state, ups)

    def ledger(st):
        v = np.asarray(st.values)
        r = np.asarray(st.residual)
        return np.stack([v[p] + r.sum(0) - r[p] for p in range(4)])

    before = ledger(state)
    step = build_sync_step(mesh, spec)
    for _ in range(3):
        state, _ = step(state)
    after = ledger(jax.block_until_ready(state))
    np.testing.assert_allclose(after, before, rtol=0, atol=1e-4)


def test_eventual_consistency_convergence():
    """After updates quiesce, every replica converges to seed + sum of all
    peers' updates — the README.md:24 contract, at the reference's measured
    rate (~1 bit/elem/frame ⇒ exact fp32 in a few dozen frames, BASELINE.md)."""
    mesh = make_mesh(4, 1)
    tpl = template(2)
    spec = make_spec(tpl)
    state = init_state(mesh, spec, tpl)
    key = jax.random.PRNGKey(11)
    ups = jax.random.uniform(key, (4, spec.total), minval=-1.0, maxval=1.0)
    ups = ups * _live_mask(spec)
    state = add_updates(state, ups)
    expect = flatten(tpl, spec) + ups.sum(0)
    step = build_sync_step(mesh, spec)
    for _ in range(64):
        state, scales = step(state)
    state = jax.block_until_ready(state)
    v = np.asarray(state.values)
    for p in range(4):
        np.testing.assert_allclose(v[p], np.asarray(expect), rtol=0, atol=1e-5)
    # converged peers idle at scale 0 (no wasted ICI traffic; quirk Q2 fixed)
    assert float(np.abs(np.asarray(state.residual)).max()) < 1e-6


def test_exact_allreduce_arm():
    """compressed=False delivers every pending residual exactly in one step
    (BASELINE config 4's comparison arm)."""
    mesh = make_mesh(4, 2)
    tpl = template(4)
    spec = make_spec(tpl)
    state = init_state(mesh, spec, tpl)
    ups = jnp.stack(
        [flatten(jax.tree.map(lambda x: (0.2 * (p + 1)) * x, tpl), spec) for p in range(4)]
    )
    state = add_updates(state, ups)
    expect = flatten(tpl, spec) + ups.sum(0)
    step = build_sync_step(mesh, spec, compressed=False)
    state, scales = jax.block_until_ready(step(state))
    v = np.asarray(state.values)
    for p in range(4):
        np.testing.assert_allclose(v[p], np.asarray(expect), rtol=1e-6, atol=1e-5)
    assert np.all(np.asarray(state.residual) == 0)


def test_idle_peers_send_nothing():
    mesh = make_mesh(2, 1)
    tpl = template(5)
    spec = make_spec(tpl)
    state = init_state(mesh, spec, tpl)
    v0 = np.asarray(state.values)  # snapshot: step() donates its input
    step = build_sync_step(mesh, spec)
    state2, scales = jax.block_until_ready(step(state))
    assert np.all(np.asarray(scales) == 0)
    np.testing.assert_array_equal(np.asarray(state2.values), v0)


def test_add_updates_sanitizes():
    """NaN/inf updates must not poison the pod (quirk Q9 fixed)."""
    mesh = make_mesh(2, 1)
    tpl = template(6)
    spec = make_spec(tpl)
    state = init_state(mesh, spec, tpl)
    bad = jnp.full((2, spec.total), jnp.nan)
    state = add_updates(state, bad)
    assert np.isfinite(np.asarray(state.values)).all()
    step = build_sync_step(mesh, spec)
    state, scales = jax.block_until_ready(step(state))
    assert np.isfinite(np.asarray(state.values)).all()


def test_read_peer_roundtrip():
    mesh = make_mesh(2, 2)
    tpl = template(8)
    spec = make_spec(tpl)
    state = init_state(mesh, spec, tpl)
    out = read_peer(state, spec, 1)
    for ka in tpl:
        np.testing.assert_array_equal(np.asarray(out[ka]), np.asarray(tpl[ka]))


def test_frame_ici_bytes_model():
    tpl = template(9)
    spec = make_spec(tpl)
    comp = frame_ici_bytes(spec, 8, compressed=True)
    exact = frame_ici_bytes(spec, 8, compressed=False)
    # ~1 bit/elem vs fp32 wire: the >=10x headroom (BASELINE.md)
    assert exact / comp > 8


def test_global_scale_mode():
    """per_leaf=False reproduces the reference's single-global-scale frames."""
    mesh = make_mesh(2, 1)
    tpl = template(10)
    spec = make_spec(tpl)
    ups = jnp.stack([flatten(tpl, spec) * 0.1, flatten(tpl, spec) * 0.2])
    state = add_updates(init_state(mesh, spec, tpl), ups)
    r0 = np.asarray(state.residual)
    step = build_sync_step(mesh, spec, per_leaf=False)
    state2, scales = jax.block_until_ready(step(state))
    for p in range(2):
        f, _ = quantize_table(jnp.asarray(r0[p]), spec, ScalePolicy.POW2_RMS, False)
        np.testing.assert_array_equal(np.asarray(scales[p]), np.asarray(f.scales)[:1])


@pytest.mark.parametrize(
    "n_peer,n_shard,impl",
    [(1, 1, "auto"), (4, 1, "auto"), (4, 2, "auto"), (4, 1, "pallas"), (2, 2, "pallas")],
)
def test_sync_phases_compose_to_sync_step(n_peer, n_shard, impl):
    """build_sync_phases is the fused step split in two: composing
    apply_gathered(values, *send(residual)[1:]) immediately must be
    bit-for-bit build_sync_step (the overlap training mode's correctness
    anchor, train/async_sgd.py overlap=True). The (1, 1) case fits a
    single chip (ST_TEST_PLATFORM=tpu), compiling the shard_map + Pallas
    phase path on hardware."""
    from shared_tensor_tpu.parallel import build_sync_phases

    tpl = template(11)
    spec = make_spec(tpl)
    mesh = make_mesh(n_peer, n_shard)
    ups = jnp.stack(
        [
            flatten(jax.tree.map(lambda x: (0.07 * (p + 1)) * x, tpl), spec)
            for p in range(n_peer)
        ]
    )
    state = add_updates(init_state(mesh, spec, tpl), ups)
    fused, scales_f = jax.block_until_ready(build_sync_step(mesh, spec, impl=impl)(state))

    state2 = add_updates(init_state(make_mesh(n_peer, n_shard), spec, tpl), ups)
    send, apply_gathered = build_sync_phases(mesh, spec, impl=impl)
    rows_local = spec.total // 128 // n_shard

    @jax.jit
    def composed(st):
        r2, words_all, scales_all = send(st.residual)
        # every peer's packed words, 128 a row, a shard's rows after another's
        assert words_all.shape == (n_peer, n_shard * words_rows(rows_local), 128)
        v2 = apply_gathered(st.values, words_all, scales_all)
        return v2, r2, scales_all

    v2, r2, scales = jax.block_until_ready(composed(state2))
    np.testing.assert_array_equal(np.asarray(v2), np.asarray(fused.values))
    np.testing.assert_array_equal(np.asarray(r2), np.asarray(fused.residual))
    np.testing.assert_array_equal(np.asarray(scales), np.asarray(scales_f))


@pytest.mark.parametrize("compressed", [True, False], ids=["compressed", "exact"])
@pytest.mark.parametrize("n_shard", [1, 2])
def test_sync_step_takes_no_index_operand(n_shard, compressed):
    """The row <-> leaf maps are static row ranges (TableSpec.leaf_rows), so a
    many-leaf sync step lowers, and compiles, with no gather and no scatter:
    the guard against a per-row index vector coming back (on the v5e a
    3.28 M-row one was 108 ms of a 158 ms step, PERF.md section 6). With
    several shards the ranges are picked by a switch on the shard index."""
    mesh = make_mesh(2, n_shard)
    tpl = {f"leaf{i:02d}": jnp.zeros(n) for i, n in enumerate([3000, 70] + [2048] * 9 + [5, 1100])}
    spec = make_spec(tpl)
    step = build_sync_step(mesh, spec, compressed=compressed, impl="xla")
    lowered = step.lower(init_state(mesh, spec, tpl))
    stablehlo = lowered.as_text()
    assert not re.findall(r"stablehlo\.(?:dynamic_)?(?:gather|scatter)\b", stablehlo)
    assert ("stablehlo.case" in stablehlo) == (n_shard > 1)
    hlo = lowered.compile().as_text()
    assert not re.findall(r"= \S+ (?:gather|scatter)\(", hlo)
