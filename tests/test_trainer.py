"""Pod async-DP trainer tests on the 8-device virtual CPU mesh
(SURVEY.md §4.2 tier 2): the full fused grads + add_updates + compressed
sync step — BASELINE config 2's shape (char-rnn, 4 peers, compression on)
at test scale."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shared_tensor_tpu.models import char_rnn as m
from tests._mesh import make_mesh
from shared_tensor_tpu.train import PodTrainer

CFG = m.CharRNNConfig(vocab=64, embed=16, hidden=32, layers=1)
TEXT = b"the quick brown fox jumps over the lazy dog. " * 60


def _trainer(n_peer=4, n_shard=1, **kw):
    mesh = make_mesh(n_peer, n_shard)
    params = m.init_params(jax.random.key(0), CFG)
    loss = lambda p, b: m.loss_fn(p, b, CFG)
    return PodTrainer(mesh, params, loss, **kw)


def _batches(key, n_peer, batch=4, seq=16):
    return m.make_batches(
        TEXT, batch=batch, seq=seq, key=key, n_peer=n_peer, vocab=CFG.vocab
    )


def test_read_gives_the_template_back_bit_for_bit():
    """The device table holds the leaves of whole 8 x 128 tiles in tile
    order (the ``[8, 384]`` and ``[2, 16, 128]`` ones, not the vector or the
    ``[3, 5]``): every peer reads the template back as it was given."""
    rng = np.random.default_rng(7)
    tpl = {"v": rng.standard_normal(300), "s": rng.standard_normal((3, 5)),
           "m": rng.standard_normal((8, 384)), "t": rng.standard_normal((2, 16, 128))}
    tpl = {k: v.astype(np.float32) for k, v in tpl.items()}
    tr = PodTrainer(make_mesh(2, 1), tpl, lambda p, b: 0.0)
    assert tr.spec.tiled == (True, False, True, False)  # m, s, t, v
    for peer in range(2):
        got = tr.read(peer)
        for k in tpl:
            np.testing.assert_array_equal(np.asarray(got[k]), tpl[k])


def test_train_step_runs_and_loss_decreases():
    tr = _trainer(n_peer=4)
    first = last = None
    for i in range(80):
        batch = tr.shard_batch(_batches(jax.random.key(i), 4))
        losses, scales = tr.step(batch, lr=0.3)
        mean = float(jnp.mean(losses))
        first = mean if first is None else first
        last = mean
    assert losses.shape == (4,)
    assert scales.shape[0] == 4
    assert last < first * 0.7, (first, last)


def test_peers_stay_consistent_under_compression():
    """Replicas drift only within the codec's bounded overshoot — after
    training quiesces (no more updates), pure sync steps pull all replicas
    together to within a few final-frame scales (reference README.md:24's
    eventual consistency; quirk Q3's +/-scale oscillation is the floor —
    converged elements keep bouncing within +/-scale, so spread is bounded,
    not zero)."""
    tr = _trainer(n_peer=4)
    for i in range(10):
        batch = tr.shard_batch(_batches(jax.random.key(i), 4))
        tr.step(batch, lr=0.3)
    # Quiesce: no new grads, keep syncing via zero-lr steps on a fixed batch.
    batch = tr.shard_batch(_batches(jax.random.key(99), 4))
    for _ in range(60):
        _, scales = tr.step(batch, lr=0.0)
    floor = float(jnp.max(scales))
    spread = tr.replica_spread()
    # 8 scales per other-peer link: the +/-scale oscillation of quirk Q3
    # superposes across links and is trajectory-dependent — fp drift between
    # XLA versions moves which elements sit mid-oscillation at the final
    # step (12x to 17x the floor has been seen); the scale-PROPORTIONAL
    # shape of the bound is the claim
    assert spread <= max(8 * (4 - 1) * floor, 1e-6), (spread, floor)
    assert spread < 0.02, spread


def test_exact_arm_keeps_replicas_identical():
    """compressed=False is the exact-allreduce comparison arm (BASELINE
    config 4): replicas must agree to float rounding after every step
    (exactly equal is impossible: peer p computes (v+u_p)+(S-u_p), whose
    rounding differs per peer)."""
    tr_exact = _trainer(n_peer=4, compressed=False)
    for i in range(5):
        batch = tr_exact.shard_batch(_batches(jax.random.key(i), 4))
        tr_exact.step(batch, lr=0.3)
    v = np.asarray(tr_exact.state.values)
    np.testing.assert_allclose(v[0], v[1], atol=1e-5)
    np.testing.assert_allclose(v[0], v[3], atol=1e-5)
    # and residuals fully drain every step
    assert float(jnp.max(jnp.abs(tr_exact.state.residual))) == 0.0


def test_compressed_tracks_exact_training():
    """Compression must not wreck optimization: compressed-arm loss stays
    within a modest factor of the exact arm on the same data stream."""
    tr_c = _trainer(n_peer=4, compressed=True)
    tr_e = _trainer(n_peer=4, compressed=False)
    for i in range(25):
        b = _batches(jax.random.key(i), 4)
        lc, _ = tr_c.step(tr_c.shard_batch(b), lr=0.3)
        le, _ = tr_e.step(tr_e.shard_batch(b), lr=0.3)
    assert float(jnp.mean(lc)) < float(jnp.mean(le)) * 1.35 + 0.1


def test_sharded_table_trains():
    """peer x shard mesh: the replica buffer itself is sharded (quirk Q6
    fix); training must still run and learn."""
    tr = _trainer(n_peer=4, n_shard=2)
    first = last = None
    for i in range(15):
        batch = tr.shard_batch(_batches(jax.random.key(i), 4))
        losses, _ = tr.step(batch, lr=0.3)
        mean = float(jnp.mean(losses))
        first = mean if first is None else first
        last = mean
    assert last < first, (first, last)


def test_read_returns_template_structure():
    tr = _trainer(n_peer=2)
    params = tr.read(0)
    assert set(params.keys()) == {"embed", "lstm", "proj"}
    assert params["embed"].shape == (CFG.vocab, CFG.embed)


def test_no_sync_arm_diverges_replicas():
    """sync=False isolation baseline: peers training on different data must
    drift apart (sanity check that sync is what keeps them together)."""
    tr = _trainer(n_peer=4, sync=False)
    for i in range(5):
        batch = tr.shard_batch(_batches(jax.random.key(i), 4))
        tr.step(batch, lr=0.3)
    assert tr.replica_spread() > 1e-4


def test_optax_optimizer_trains():
    """optax momentum per peer: loss decreases and per-peer optimizer state
    is carried across steps."""
    import optax

    tr = _trainer(n_peer=4, optimizer=optax.sgd(0.3, momentum=0.9))
    first = last = None
    for i in range(40):
        batch = tr.shard_batch(_batches(jax.random.key(i), 4))
        losses, _ = tr.step(batch)
        mean = float(jnp.mean(losses))
        first = mean if first is None else first
        last = mean
    assert last < first * 0.8, (first, last)
    assert tr.opt_state is not None


def test_overlap_trainer_trains_and_stays_consistent():
    """overlap=True (collective under the backward pass): loss decreases,
    replicas stay mutually consistent, and after training stops the extra
    sync steps drain every replica to the same point (the one-step-later
    delivery must not strand any mass)."""
    tr = _trainer(n_peer=4, overlap=True)
    first = last = None
    for i in range(80):
        batch = tr.shard_batch(_batches(jax.random.key(i), 4))
        losses, scales = tr.step(batch, lr=0.3)
        mean = float(jnp.mean(losses))
        first = mean if first is None else first
        last = mean
    assert last < first * 0.9, (first, last)
    # drain: sync-only steps deliver the in-flight tail. Heavy-tailed grad
    # residuals drain their outliers only +/-scale per frame (same as the C
    # reference), so the bar is "shrinks like the fused trainer does", not
    # exact zero: measured fused-mode spread after the same 40 drains is
    # ~0.017 on this config.
    from shared_tensor_tpu.parallel.ici import build_sync_step

    spread0 = tr.replica_spread()
    drain = build_sync_step(tr.mesh, tr.spec)
    for _ in range(40):
        tr.state, _ = drain(tr.state)
    spread = tr.replica_spread()
    assert spread < 0.05 and spread < spread0, (spread0, spread)
    assert np.isfinite(np.asarray(tr.state.values)).all()


def test_overlap_vs_fused_convergence_ab():
    """Convergence A/B (round-3 verdict item 4): the overlap arm's one-step-
    delayed delivery must be *statistically* indistinguishable from fused —
    not just compose-parity (bit-identical composition is pinned elsewhere;
    this trains both arms on the SAME pinned data stream to comparable
    loss). Bars: tail losses within 10% of each other, and both arms
    actually learned (tail well under the initial loss)."""
    steps = 240
    tail = 40
    curves = {}
    for overlap in (False, True):
        tr = _trainer(n_peer=4, overlap=overlap)
        losses = []
        for i in range(steps):
            batch = tr.shard_batch(_batches(jax.random.key(i), 4))
            l, _ = tr.step(batch, lr=0.3)
            losses.append(float(jnp.mean(l)))
        curves[overlap] = losses
        assert np.isfinite(np.asarray(tr.state.values)).all()
    fused_tail = float(np.mean(curves[False][-tail:]))
    over_tail = float(np.mean(curves[True][-tail:]))
    first = curves[False][0]
    # both arms learned
    assert fused_tail < first * 0.5, (first, fused_tail)
    assert over_tail < first * 0.5, (first, over_tail)
    # and to statistically comparable loss: the inter-arm gap must be small
    # relative to the loss scale AND small relative to within-arm noise
    gap = abs(fused_tail - over_tail)
    noise = max(
        float(np.std(curves[False][-tail:])),
        float(np.std(curves[True][-tail:])),
        1e-9,
    )
    assert gap <= 0.1 * fused_tail + 1e-6, (fused_tail, over_tail)
    assert gap <= 3.0 * noise, (gap, noise)


def test_overlap_requires_compressed_sync():
    import pytest

    with pytest.raises(ValueError):
        _trainer(n_peer=2, overlap=True, compressed=False)


def test_sync_every_paces_exchanges():
    """sync_every=2: off-beat steps run the no-sync program (scales all 0,
    updates pile into the residual); the beat step delivers the accumulated
    sum as one frame. Training still converges and replicas stay bounded."""
    tr = _trainer(n_peer=4, sync_every=2)
    first = last = None
    beat_scales, off_scales = [], []
    for i in range(60):
        batch = tr.shard_batch(_batches(jax.random.key(i), 4))
        losses, scales = tr.step(batch, lr=0.3)
        mean = float(jnp.mean(losses))
        first = mean if first is None else first
        last = mean
        (beat_scales if tr.steps % 2 == 0 else off_scales).append(
            float(jnp.max(scales))
        )
    assert last < first * 0.9, (first, last)
    assert all(s == 0.0 for s in off_scales)  # off-beats exchange nothing
    assert any(s > 0.0 for s in beat_scales)  # beats carry the frames
    assert np.isfinite(np.asarray(tr.state.values)).all()
