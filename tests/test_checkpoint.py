"""Checkpoint/resume tests (SURVEY.md §5.4 — capability the reference
lacks entirely)."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shared_tensor_tpu.core import SharedTensor
from shared_tensor_tpu.models import char_rnn as m
from shared_tensor_tpu.parallel.ici import init_state
from tests._mesh import make_mesh
from shared_tensor_tpu.train import PodTrainer
from shared_tensor_tpu.utils import checkpoint as ckpt


def _template():
    return {
        "a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
        "b": jnp.ones((4,), jnp.float32),
    }


def test_shared_roundtrip(tmp_path):
    st = SharedTensor(_template(), seed_values=True)
    st.new_link(1)
    st.add({"a": jnp.full((2, 3), 0.5), "b": jnp.zeros((4,))})
    path = str(tmp_path / "st.npz")
    ckpt.save_shared(st, path)

    st2 = SharedTensor(_template())
    st2.new_link(1, seed=False)
    ckpt.load_shared(st2, path)
    np.testing.assert_array_equal(
        np.asarray(st2.snapshot_flat()), np.asarray(st.snapshot_flat())
    )
    np.testing.assert_array_equal(
        np.asarray(st2._links[1]), np.asarray(st._links[1])
    )
    # restored replica unflattens to the right pytree values
    got = st2.read()
    np.testing.assert_allclose(np.asarray(got["a"]), np.arange(6).reshape(2, 3) + 0.5)


def test_shared_layout_mismatch_rejected(tmp_path):
    st = SharedTensor(_template(), seed_values=True)
    path = str(tmp_path / "st.npz")
    ckpt.save_shared(st, path)
    other = SharedTensor({"x": jnp.zeros((5,))})
    with pytest.raises(ValueError, match="layout"):
        ckpt.load_shared(other, path)


def test_engine_snapshot_roundtrip_sign2_cascade_inflight(monkeypatch):
    """r12 satellite: the r04-era checkpoint path predates the r11 state —
    sign2 (2-bit) wire frames, cascade quantize, the per-link precision
    governor. Pin a pair with sign2 forced on (ST_SIGN2=2), stall the
    joiner's uplink so 32 cascade-quantized sign2 messages sit LEDGERED
    (in flight, error feedback already debited from the residual) and the
    send window closes, then require snapshot_ex → restore_ex →
    snapshot_ex to round-trip values, every residual, and the per-link
    aux (seqs, precision capability) BYTE-EXACT — the one-mutex capture
    must be atomic against all of it. Also pins restore_ex's governor
    restore: a crafted prec/gov_prev survives into the next snapshot."""
    from shared_tensor_tpu.comm import faults
    from shared_tensor_tpu.comm.peer import create_or_fetch
    from shared_tensor_tpu.config import Config, FaultConfig, TransportConfig
    from tests._ports import free_port

    monkeypatch.setenv("ST_SIGN2", "2")
    env = faults.to_env(
        FaultConfig(enabled=True, seed=3, stall_after_frames=0, only_link=1)
    )
    port = free_port()
    seed = jnp.zeros((2048,), jnp.float32)
    # ack_timeout 0: the stalled link must KEEP its ledger (a go-back-N
    # teardown would roll the in-flight state away mid-test)
    cfg = Config(transport=TransportConfig(ack_timeout_sec=0.0))
    master = create_or_fetch("127.0.0.1", port, seed, cfg, timeout=30.0)
    monkeypatch.setenv("ST_FAULT_PLAN", env["ST_FAULT_PLAN"])
    child = create_or_fetch("127.0.0.1", port, seed, cfg, timeout=30.0)
    monkeypatch.delenv("ST_FAULT_PLAN")
    try:
        if child._engine is None:
            pytest.skip("native engine unavailable")
        rng = np.random.default_rng(2)
        for _ in range(40):
            child.add(jnp.asarray(rng.uniform(-1, 1, 2048).astype(np.float32)))
        # the stalled uplink ledgers every message unacked; production
        # stops when either the 32-deep window closes or the cascade
        # drains the residual — both leave a deep in-flight ledger
        deadline = time.time() + 20.0
        while time.time() < deadline and child.st.inflight_total() < 8:
            time.sleep(0.05)
        # wait for production to STOP (window closed or residual drained):
        # the byte-exact round trip below needs the sender quiescent, or a
        # post-restore quantize would legitimately mutate the residual
        last = -1
        while time.time() < deadline:
            cur = child.st.frames_out
            if cur == last:
                break
            last = cur
            time.sleep(0.3)
        inflight = child.st.inflight_total()
        assert inflight >= 8, f"no in-flight ledger built up ({inflight})"
        eng = child._engine
        v1, l1, a1 = eng.snapshot_ex()
        assert a1[1]["sign2"], "peer sign2 capability missing from aux"
        assert a1[1]["tx_seq"] >= inflight and a1[1]["rx_count"] == 0
        eng.restore_ex(v1, l1, a1)
        v2, l2, a2 = eng.snapshot_ex()
        np.testing.assert_array_equal(v1, v2)
        assert set(l1) == set(l2)
        for lid in l1:
            np.testing.assert_array_equal(l1[lid], l2[lid])
        assert a1 == a2
        # governor restore: crafted precision + previous-RMS sample survive
        crafted = {1: dict(a1[1], prec=2, gov_prev=0.25)}
        eng.restore_ex(v1, l1, crafted)
        assert eng.link_precision(1) == 2
        _, _, a3 = eng.snapshot_ex()
        assert a3[1]["prec"] == 2
        assert a3[1]["gov_prev"] == pytest.approx(0.25)
    finally:
        child.close()
        master.close()


def test_pod_roundtrip_resumes_training(tmp_path):
    """Save mid-training, restore onto a fresh mesh state, continue — the
    loss trajectory must continue from the checkpoint, not restart."""
    cfg = m.CharRNNConfig(vocab=64, embed=16, hidden=32, layers=1)
    text = b"abcdefgh" * 200
    mesh = make_mesh(4, 1)
    params = m.init_params(jax.random.key(0), cfg)
    loss = lambda p, b: m.loss_fn(p, b, cfg)
    tr = PodTrainer(mesh, params, loss)
    for i in range(10):
        batch = tr.shard_batch(
            m.make_batches(text, 4, 16, jax.random.key(i), n_peer=4, vocab=64)
        )
        l1, _ = tr.step(batch, lr=0.3)
    path = str(tmp_path / "pod.npz")
    ckpt.save_pod(tr.state, tr.spec, path)

    tr2 = PodTrainer(mesh, params, loss)
    tr2.state = ckpt.load_pod(path, mesh, tr2.spec)
    np.testing.assert_array_equal(
        np.asarray(tr2.state.values), np.asarray(tr.state.values)
    )
    batch = tr2.shard_batch(
        m.make_batches(text, 4, 16, jax.random.key(99), n_peer=4, vocab=64)
    )
    l2, _ = tr2.step(batch, lr=0.3)
    # resumed loss is near the trained loss, far below a fresh model's
    fresh = PodTrainer(mesh, params, loss)
    l0, _ = fresh.step(batch, lr=0.0)
    assert float(jnp.mean(l2)) < float(jnp.mean(l0)) * 0.8


def test_pod_checkpoint_in_row_major_order_is_refused(tmp_path):
    """A trainer holds its ``[16, 256]`` leaf in tile order; a checkpoint of
    the same table written row-major (as every pod checkpoint was before
    tile order) carries that layout's digest, so the trainer refuses it
    rather than read it in the wrong order, and takes its own back."""
    mesh = make_mesh(2, 1)
    tpl = {"b": jnp.ones((4,), jnp.float32),
           "w": jnp.arange(16 * 256, dtype=jnp.float32).reshape(16, 256)}
    tr = PodTrainer(mesh, tpl, lambda p, b: 0.0)
    assert tr.spec.tiled == (False, True)
    path = str(tmp_path / "pod.npz")
    row_major = tr.spec.row_major()
    ckpt.save_pod(init_state(mesh, row_major, tpl), row_major, path)
    with pytest.raises(ValueError, match="layout"):
        ckpt.load_pod(path, mesh, tr.spec)
    with pytest.raises(ValueError, match="layout"):
        ckpt.load_trainer(tr, path)
    ckpt.save_trainer(tr, path)
    ckpt.load_trainer(tr, path)
    np.testing.assert_array_equal(np.asarray(tr.read(1)["w"]), np.asarray(tpl["w"]))


def test_pod_peer_count_mismatch_rejected(tmp_path):
    mesh = make_mesh(4, 1)
    st = init_state(mesh, PodTrainer(mesh, _template(), lambda p, b: 0.0).spec)
    spec = PodTrainer(mesh, _template(), lambda p, b: 0.0).spec
    path = str(tmp_path / "pod.npz")
    ckpt.save_pod(st, spec, path)
    mesh2 = make_mesh(2, 1)
    with pytest.raises(ValueError, match="peers"):
        ckpt.load_pod(path, mesh2, spec)


def test_trainer_adam_resume_bit_equal(tmp_path):
    """Train 2k steps straight vs train k, save_trainer, restore into a FRESH
    trainer, train k more: state, optimizer moments, and every subsequent
    step must be bit-equal (round-2 verdict Weak #5 — dropping opt_state made
    Adam resume with reset moments, silently changing training)."""
    import optax

    cfg = m.CharRNNConfig(vocab=64, embed=16, hidden=32, layers=1)
    text = b"abcdefgh" * 200
    mesh = make_mesh(4, 1)
    params = m.init_params(jax.random.key(0), cfg)
    loss = lambda p, b: m.loss_fn(p, b, cfg)
    opt = optax.adam(3e-3)

    def batches(i):
        return m.make_batches(text, 4, 16, jax.random.key(i), n_peer=4, vocab=64)

    k = 5
    ref = PodTrainer(mesh, params, loss, optimizer=opt)
    for i in range(2 * k):
        ref.step(ref.shard_batch(batches(i)))

    tr = PodTrainer(mesh, params, loss, optimizer=opt)
    for i in range(k):
        tr.step(tr.shard_batch(batches(i)))
    path = str(tmp_path / "trainer.npz")
    ckpt.save_trainer(tr, path)

    tr2 = PodTrainer(mesh, params, loss, optimizer=opt)
    ckpt.load_trainer(tr2, path)
    assert tr2.steps == k
    for i in range(k, 2 * k):
        tr2.step(tr2.shard_batch(batches(i)))

    np.testing.assert_array_equal(
        np.asarray(tr2.state.values), np.asarray(ref.state.values)
    )
    np.testing.assert_array_equal(
        np.asarray(tr2.state.residual), np.asarray(ref.state.residual)
    )
    for a, b in zip(jax.tree.leaves(tr2.opt_state), jax.tree.leaves(ref.opt_state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_trainer_optimizer_mismatch_rejected(tmp_path):
    import optax

    mesh = make_mesh(2, 1)
    tr = PodTrainer(mesh, _template(), lambda p, b: 0.0, optimizer=optax.adam(1e-3))
    path = str(tmp_path / "trainer.npz")
    ckpt.save_trainer(tr, path)
    plain = PodTrainer(mesh, _template(), lambda p, b: 0.0)
    with pytest.raises(ValueError, match="optimizer"):
        ckpt.load_trainer(plain, path)


def test_pod_sharded_roundtrip_per_shard_io(tmp_path):
    """save_pod_sharded / load_pod_sharded (round-3 verdict item 6): the
    sharded pod state checkpoints WITHOUT materializing the full table on
    one host — each shard is its own file sized ~total/n_shards, and the
    restore callback reads one shard at a time. Table sized so the full
    buffer (4 MiB x 2 arrays x 4 peers) exceeds a deliberately tiny 'host
    budget' of one shard."""
    import os

    from shared_tensor_tpu.ops.table import make_spec
    from shared_tensor_tpu.parallel.ici import add_updates

    mesh = make_mesh(4, 2)
    template = {"w": jnp.zeros((1 << 20,), jnp.float32)}  # 4 MiB/peer
    spec = make_spec(template)
    state = init_state(mesh, spec, template)
    upd = (
        jax.random.normal(jax.random.key(0), state.values.shape)
        .astype(jnp.float32)
    )
    state = add_updates(state, upd)

    path = str(tmp_path / "pod_ckpt")
    ckpt.save_pod_sharded(state, spec, path)

    files = [f for f in os.listdir(path) if f.startswith("shard_")]
    assert len(files) == 8, files  # one per device of the 4x2 mesh
    full_bytes = state.values.size * 4
    for f in files:
        sz = os.path.getsize(os.path.join(path, f))
        # each file holds 2 arrays of total/8 f32s (plus npz framing): far
        # under the full table — the per-shard-I/O claim, falsifiable here
        assert sz < full_bytes // 2, (f, sz, full_bytes)
    restored = ckpt.load_pod_sharded(path, mesh, spec)
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(restored.values)),
        np.asarray(jax.device_get(state.values)),
    )
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(restored.residual)),
        np.asarray(jax.device_get(state.residual)),
    )
    # restored arrays carry the mesh sharding (not single-device commits)
    assert restored.values.sharding == state.values.sharding

    # stale-shard immunity: re-save DIFFERENT state on a coarser mesh into
    # the same directory (the old 4x2 shard files linger — save never
    # deletes other layouts' files); load must serve only the manifested
    # files, never a stale one. Also covers the n_shard=1 filename case
    # (slice(None) bounds must normalize, not embed 'None').
    mesh2 = make_mesh(4, 1)
    state2 = init_state(mesh2, spec, template)
    state2 = add_updates(
        state2,
        jax.random.normal(jax.random.key(1), state2.values.shape).astype(
            jnp.float32
        ),
    )
    ckpt.save_pod_sharded(state2, spec, path)
    assert len([f for f in os.listdir(path) if f.startswith("shard_")]) > 4
    restored2 = ckpt.load_pod_sharded(path, mesh2, spec)
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(restored2.values)),
        np.asarray(jax.device_get(state2.values)),
    )


def test_pod_sharded_rejects_wrong_layout(tmp_path):
    from shared_tensor_tpu.ops.table import make_spec

    mesh = make_mesh(4, 2)
    template = {"w": jnp.zeros((1 << 14,), jnp.float32)}
    spec = make_spec(template)
    state = init_state(mesh, spec, template)
    path = str(tmp_path / "pod_ckpt")
    ckpt.save_pod_sharded(state, spec, path)
    other = make_spec({"w": jnp.zeros((1 << 13,), jnp.float32)})
    with pytest.raises(ValueError):
        ckpt.load_pod_sharded(path, mesh, other)


def test_pod_sharded_training_resume_bit_equal(tmp_path):
    """Resume from a sharded checkpoint mid-training and continue: the
    continued run must match an uninterrupted one bit-for-bit (same data
    stream, deterministic step)."""
    cfg = m.CharRNNConfig(vocab=64, embed=16, hidden=32, layers=1)
    text = b"a quick brown fox jumps over the lazy dog. " * 40
    mesh = make_mesh(4, 2)
    params = m.init_params(jax.random.key(0), cfg)
    loss = lambda p, b: m.loss_fn(p, b, cfg)

    def batches(i):
        return m.make_batches(
            text, 2, 8, jax.random.key(i), n_peer=4, vocab=cfg.vocab,
        )

    tr = PodTrainer(mesh, params, loss)
    for i in range(3):
        tr.step(tr.shard_batch(batches(i)), lr=0.2)
    path = str(tmp_path / "mid")
    ckpt.save_pod_sharded(tr.state, tr.spec, path)
    # continue the original
    for i in range(3, 6):
        tr.step(tr.shard_batch(batches(i)), lr=0.2)
    # resume a fresh trainer from the sharded checkpoint
    tr2 = PodTrainer(mesh, params, loss)
    tr2.state = ckpt.load_pod_sharded(path, mesh, tr2.spec)
    for i in range(3, 6):
        tr2.step(tr2.shard_batch(batches(i)), lr=0.2)
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(tr.state.values)),
        np.asarray(jax.device_get(tr2.state.values)),
    )
