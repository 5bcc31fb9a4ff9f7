"""models/swa_moe.py against the plain reference (chipbench/reference/
smallthinker_ref.py) on seeded random weights at a small preset, its shares
of the experts against the uncut layer, and through PodTrainer."""

import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench.jobs import train_decoder  # noqa: E402
from chipbench.reference import smallthinker_ref as R  # noqa: E402
from shared_tensor_tpu.models import mla_moe  # noqa: E402
from shared_tensor_tpu.models import swa_moe as M  # noqa: E402
from shared_tensor_tpu.ops.table import make_spec  # noqa: E402
from shared_tensor_tpu.parallel import make_mesh  # noqa: E402
from shared_tensor_tpu.train import PodTrainer  # noqa: E402

with open(os.path.join(ROOT, "chipbench", "configs", "smallthinker-21b-a3b.json")) as _f:
    FILE = json.load(_f)
# hidden 64, 4 query heads on 2 K/V heads of 16, 16 experts top-3, window 48,
# layers [full, window, window, window]
PRESET = FILE["rehearsal"]["model"]
T = 128  # 2.7 windows: the band's far edge lies inside the sequence


def model(held=(0, 16), vocab=512):
    """The preset as the configuration file writes it, holding ``held`` of
    its 16 experts and ``vocab`` of its 512 rows."""
    return dict(PRESET, experts_held=list(held), moe_num_primary_experts=held[1],
                vocab_size=vocab)


def config(held=(0, 16), vocab=512, dtype="float32", **over):
    return train_decoder.model_config(M, model(held, vocab), compute_dtype=dtype, **over)


@functools.lru_cache(maxsize=None)
def inputs(held, vocab):
    params = M.init_params(jax.random.key(0), config(held, vocab))
    return params, jax.random.randint(jax.random.key(1), (1, T), 0, vocab)


POSITIONS = jnp.arange(0, T, 8)


@functools.lru_cache(maxsize=None)
def program(held, vocab, dtype):
    """loss, aux (with what a comparison needs) and gradients of the program."""
    cfg = config(held, vocab, dtype)
    (loss, aux), grads = jax.jit(jax.value_and_grad(
        lambda p, b: M.loss_fn(p, b, cfg, positions=POSITIONS), has_aux=True))(
            *inputs(held, vocab))
    return loss, aux, grads


@functools.lru_cache(maxsize=None)
def reference(held, vocab, forced=None):
    """loss, outputs and gradients of the reference: its experts its own, or
    forced to the ``forced`` program's choices."""
    m = model(held, vocab)
    choices = None if forced is None else [list(program(held, vocab, forced)[1]["choices"][0])]
    (loss, outs), grads = jax.jit(jax.value_and_grad(
        lambda p, b: R.loss_and_outputs(p, b, m, choices, POSITIONS), has_aux=True))(
            *inputs(held, vocab))
    return loss, outs[0], grads


def leaf_errors(got, want):
    """Relative error in the L2 norm, leaf by leaf."""
    return {k: float(jnp.linalg.norm(got[k] - want[k]) / (jnp.linalg.norm(want[k]) + 1e-30))
            for k in want}


@pytest.mark.parametrize("held,vocab", [((0, 16), 512), ((4, 4), 512), ((4, 4), 128)])
def test_float32_program_equals_the_reference_in_loss_logits_and_every_gradient(held, vocab):
    """All experts held and a share of them; the vocabulary whole and sliced.
    The window (48) is no multiple of the scan's tile (32) and the sequence
    is 2.7 windows long."""
    loss, aux, grads = program(held, vocab, "float32")
    ref_loss, (ce, logits, routed), ref_grads = reference(held, vocab)
    # 1e-5 relative: the two differ by reduction order alone
    assert abs(float(loss - ref_loss)) <= 1e-5 * float(ref_loss)
    assert float(aux["ce_main_of"][0]) == pytest.approx(float(ce), rel=1e-5)
    assert float(jnp.linalg.norm(aux["logits"][0] - logits) / jnp.linalg.norm(logits)) <= 1e-5
    assert set(grads) == set(ref_grads)
    worst = max(leaf_errors(grads, ref_grads).items(), key=lambda kv: kv[1])
    assert worst[1] <= 1e-5, worst
    for mine, (own, _) in zip(aux["choices"][0], routed):
        assert np.array_equal(np.sort(mine, axis=-1), np.sort(own, axis=-1))


def test_bfloat16_program_is_near_the_reference_forced_to_its_choices():
    """The chip's precision at the preset: bfloat16 products move the loss,
    the logits and the gradient by the rounding of a product, and the
    reference along the program's path chooses nearly the program's experts.
    Limits of the preset (widths of 64 read noisier than the chip's): CE 2e-3
    nat, logits 2 % in L2, the median leaf's gradient 2 %, the worst leaf's
    50 %, choices 99 % (the file's ``rehearsal.checks``)."""
    chk = dict(FILE["checks"], **FILE["rehearsal"]["checks"])
    held, vocab = (4, 4), 128
    loss, aux, grads = program(held, vocab, "bfloat16")
    _, (ce, logits, routed), ref_grads = reference(held, vocab, "bfloat16")
    assert abs(float(aux["ce_main_of"][0] - ce)) <= chk["ce_tol"]
    err = float(jnp.linalg.norm(aux["logits"][0] - logits) / jnp.linalg.norm(logits))
    assert 0 < err <= chk["logits_rel_tol"]
    agree, outside = train_decoder.choice_agreement(
        list(aux["choices"][0]), routed, chk["choices_margin"])
    assert float(agree) >= chk["choices_agree_min"] and int(outside) == 0
    errs = sorted(leaf_errors(grads, ref_grads).values())
    assert errs[-1] <= chk["update_rel_tol"]
    assert errs[len(errs) // 2] <= chk["update_rel_median_tol"]


def test_the_reference_in_blocks_equals_the_reference_whole(monkeypatch):
    """At the chip's sizes the reference runs its row-wise parts and its
    queries in blocks of ``ROWS``; here 32 of the preset's 128."""
    whole = reference((4, 4), 128)
    monkeypatch.setattr(R, "ROWS", 32)
    m = model((4, 4), 128)
    (loss, outs), grads = jax.jit(jax.value_and_grad(
        lambda p, b: R.loss_and_outputs(p, b, m, None, POSITIONS), has_aux=True))(
            *inputs((4, 4), 128))
    assert float(loss) == pytest.approx(float(whole[0]), rel=1e-6)
    np.testing.assert_allclose(outs[0][1], whole[1][1], rtol=1e-4, atol=1e-5)
    assert max(leaf_errors(grads, whole[2]).values()) <= 1e-5


def test_the_shares_add_up_to_the_uncut_layer():
    """The deployment's claim: the routed parts the four shares of the
    experts give (the program, each told what it holds) sum to what the
    uncut reference gives for the whole layer, router and attention counted
    once. A window layer, so the band and RoPE are in it."""
    whole = model((0, 16))
    params, _ = inputs((0, 16), 512)
    x = jax.random.normal(jax.random.key(5), (T, PRESET["hidden_size"]))
    layer = 1
    pre = f"model.layers.{layer}."
    with jax.default_matmul_precision("highest"):
        want, (own, _) = R.block(params, layer, x, whole)
        h = x + R.self_attention(
            params, pre + "self_attn.",
            R._norm(x, params[pre + "input_layernorm.weight"], whole["rms_norm_eps"]),
            whole, layer)
    rope = mla_moe.rope_tables(T, PRESET["head_dim"], PRESET["rope_theta"])
    routed, pairs = 0.0, 0
    for first in range(0, 16, 4):
        cfg = config((first, 4))
        y, aux = jax.jit(lambda p, x, cfg=cfg: M.block(
            mla_moe._sub(p, pre), x, rope, cfg, cfg.window(layer)))(params, x)
        routed = routed + (y - h)  # this share's experts alone: h is everybody's
        pairs += int(aux["moe_pairs_held"])
        assert np.array_equal(np.sort(aux["choices"], axis=-1), np.sort(own, axis=-1))
    assert pairs == T * PRESET["moe_num_active_primary_experts"]  # every pair held once
    np.testing.assert_allclose(h + routed, want, rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("hidden,tile", [(128, 8), (256, 16)])
def test_reglu_experts_on_the_token_major_accumulator_equal_the_two_dimensional_to_the_bit(
        monkeypatch, hidden, tile):
    """``mla_moe``'s expert loop as this decoder calls it (ReGLU, top-3 of 16,
    experts 4-8 held, the leaves' own names) carries its sums ``[T, hidden /
    128, 128]``: forward and the five cotangents equal the ``[T, hidden]``
    carry's element for element. Choices at random, so experts' last tiles
    are part padding; token 0 has all its pairs held, expert 8 has none."""
    t, k, f = 64, 3, 24
    cfg = M.Config(hidden_size=hidden, moe_ffn_hidden_size=f, moe_num_primary_experts=16,
                   moe_num_active_primary_experts=k, experts_held=(4, 5), expert_tile=tile,
                   compute_dtype="float32")
    keys = jax.random.split(jax.random.key(hidden), 5)
    idx = jnp.argsort(jax.random.uniform(keys[0], (t, 16)), axis=1)[:, :k]
    idx = jnp.where(idx == 8, 9, idx).at[0].set(jnp.arange(4, 4 + k)).astype(jnp.int32)
    p = {f"experts.{e}.{name}.weight": 0.3 * jax.random.normal(
        jax.random.fold_in(keys[1], 3 * e + i), (hidden, f) if name == "down" else (f, hidden))
        for e in range(4, 9) for i, name in enumerate(("gate", "up", "down"))}
    u, g = (jax.random.normal(key, (t, hidden)) for key in keys[2:4])
    w = jax.nn.softmax(jax.random.normal(keys[4], (t, k)), axis=-1)

    def both(p, u, w):
        out, vjp = jax.vjp(lambda p, u, w: mla_moe.held_experts(
            p, u, idx, w, cfg, "relu", names=("gate", "up", "down"))[0], p, u, w)
        return out, vjp(g)

    assert mla_moe._token_major(u.shape) == (t, hidden // 128, 128)
    got = jax.jit(both)(p, u, w)
    monkeypatch.setattr(mla_moe, "_token_major", lambda shape: tuple(shape))
    want = jax.jit(lambda *a: both(*a))(p, u, w)  # a function of its own: traced again
    assert float(jnp.max(jnp.abs(want[0]))) > 0
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        assert a.shape == b.shape and np.array_equal(np.asarray(a), np.asarray(b))


def test_router_weights_are_a_softmax_over_the_chosen_logits_of_the_raw_input():
    cfg = config()
    w_r = jax.random.normal(jax.random.key(2), (16, 64))
    x = 3.0 * jax.random.normal(jax.random.key(3), (T, 64))
    idx, w = M.route(w_r, x, cfg)
    logits = np.asarray(x, np.float64) @ np.asarray(w_r, np.float64).T
    order = np.argsort(-logits, axis=-1)[:, :3]
    assert np.array_equal(np.sort(idx, axis=-1), np.sort(order, axis=-1))
    chosen = np.take_along_axis(logits, np.asarray(idx), axis=-1)
    e = np.exp(chosen - chosen.max(-1, keepdims=True))
    np.testing.assert_allclose(w, e / e.sum(-1, keepdims=True), rtol=1e-4)
    np.testing.assert_allclose(np.sum(w, axis=-1), 1.0, rtol=1e-6)


def test_rope_is_the_half_split_layout():
    """Dimension i pairs with i + d/2, by complex arithmetic in float64, and
    the reference's own rope agrees."""
    n, d, theta = 16, 16, 1.5e6
    x = np.random.default_rng(3).normal(size=(n, 2, d)).astype(np.float32)
    got = M.rope_half(jnp.asarray(x), *mla_moe.rope_tables(n, d, theta))
    ang = np.arange(n)[:, None] * theta ** (-np.arange(0, d, 2) / d)[None, :]
    z = (x[..., : d // 2] + 1j * x[..., d // 2:]) * np.exp(1j * ang)[:, None, :]
    want = np.concatenate([z.real, z.imag], -1)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        R.rope(jnp.asarray(x), theta, jnp.arange(n, dtype=jnp.float32)), want,
        rtol=1e-5, atol=1e-6)


def test_layer_zero_has_no_position_encoding_and_the_others_a_window():
    """Layer 0 (layout 0) is blind to order among the keys a query sees: a
    permutation of the earlier tokens leaves the last position's output
    where it was. A window layer sees only its window: changing a token
    older than the window leaves the last position where it was."""
    cfg = config(num_hidden_layers=1)
    params, tokens = inputs((0, 16), 512)
    t = tokens[0]
    fwd = jax.jit(lambda tok: M.trunk(params, tok, cfg)[0][-1])
    shuffled = jnp.concatenate([jax.random.permutation(jax.random.key(9), t[:-1]), t[-1:]])
    np.testing.assert_allclose(fwd(t), fwd(shuffled), rtol=2e-4, atol=2e-5)

    window_only = config(num_hidden_layers=1, sliding_window_layout=(1,), rope_layout=(1,))
    fwd_w = jax.jit(lambda tok: M.trunk(params, tok, window_only)[0][-1])
    old = t.at[T - 1 - 48].set((t[T - 1 - 48] + 1) % 512)  # just outside: i - j = 48
    seen = t.at[T - 48].set((t[T - 48] + 1) % 512)  # the window's oldest key: i - j = 47
    assert np.array_equal(fwd_w(t), fwd_w(old))
    assert not np.allclose(fwd_w(t), fwd_w(seen), rtol=1e-4, atol=1e-6)
    assert not np.allclose(fwd_w(t), fwd_w(shuffled), rtol=1e-4, atol=1e-6)  # RoPE sees order


SCOPES = [
    "st.embed", "st.attn", "st.attn.proj", "st.attn.full", "st.attn.window", "st.moe",
    "st.moe.router", "st.moe.dispatch", "st.moe.experts", "st.moe.combine", "st.head_loss",
]


@pytest.fixture(scope="module")
def trained():
    """Three PodTrainer steps on (1,1), the default fused program, and the
    same three of bare SGD."""
    held, vocab, lr = (4, 4), 128, 0.5
    cfg = config(held, vocab)
    params, _ = inputs(held, vocab)
    loss_fn = lambda p, b: M.loss_fn(p, b, cfg)
    batches = [jax.random.randint(jax.random.key(10 + i), (1, 1, T), 0, vocab) for i in range(3)]
    trainer = PodTrainer(make_mesh(1, 1), params, loss_fn)
    trainer._step = trainer.lower(trainer.shard_batch(batches[0]), lr).compile()
    text = trainer._step.as_text()
    got = [float(trainer.step(trainer.shard_batch(b), lr)[0][0]) for b in batches]

    @jax.jit
    def sgd(p, b):
        (loss, _), g = jax.value_and_grad(loss_fn, has_aux=True)(p, b)
        return loss, jax.tree.map(lambda a, d: a - lr * d, p, g)

    bare, p = [], params
    for b in batches:
        loss, p = sgd(p, b[0])
        bare.append(float(loss))
    return dict(trainer=trainer, got=got, bare=bare, bare_params=p, text=text)


def test_podtrainer_steps_equal_bare_sgd(trained):
    assert trained["got"] == pytest.approx(trained["bare"], rel=1e-5)
    errs = leaf_errors(trained["trainer"].read(0), trained["bare_params"])
    assert max(errs.values()) <= 1e-5, max(errs.items(), key=lambda kv: kv[1])


def test_aux_comes_out_of_the_step_and_feeds_the_gauges(trained):
    from shared_tensor_tpu.utils.profiling import pod_registry

    aux = trained["trainer"].aux
    assert aux["ce_main"].shape == (1,) and aux["moe_pairs_held"].shape == (1, 4)
    assert set(aux) == {"ce_main", "moe_pairs_held", "moe_load_max_over_mean",
                        "moe_tokens_unrouted_share", "moe_rows_executed"}
    snap = pod_registry().snapshot()
    assert snap["st_moe_pairs_held_total"] == float(np.sum(aux["moe_pairs_held"]))


@pytest.mark.parametrize("scope", SCOPES)
def test_the_compiled_step_holds_the_scope(trained, scope):
    from shared_tensor_tpu.utils.profiling import scope_map

    scopes = set(scope_map(trained["text"]).values())
    assert any(s.split("/")[-1] == scope for s in scopes), sorted(scopes)
    if scope != "st.embed":
        assert all(s.startswith("st.grads") for s in scopes if s.endswith(scope))
    if scope.startswith("st.attn."):
        assert all("st.attn/" in s for s in scopes if s.endswith(scope))
    assert not [s for s in scopes if "st.mla" in s or "st.moe.shared" in s]


def test_attention_traces_are_counted_by_kind_with_their_tiles():
    from shared_tensor_tpu.obs.schema import label_key
    from shared_tensor_tpu.utils.profiling import pod_registry

    def counts():
        snap = pod_registry().snapshot()
        return {k: snap[label_key("st_attn_traces_total", "kind", k)]
                for k in ("full", "window")}

    cfg = config((4, 4), 128)
    before = counts()
    jax.eval_shape(lambda p, b: M.loss_fn(p, b, cfg), *inputs((4, 4), 128))
    after = counts()
    assert after["full"] - before["full"] == 1 and after["window"] - before["window"] == 3
    snap = pod_registry().snapshot()
    # the scan's tiles of 32 over 128 positions: the triangle has 10, the
    # band of 48 keys 9 (the one tile more than a window behind is skipped)
    assert snap[label_key("st_attn_tiles_listed", "kind", "full")] == 10
    assert snap[label_key("st_attn_tiles_listed", "kind", "window")] == 9
    text = pod_registry().prometheus_text()
    assert 'st_attn_traces_total{kind="window"}' in text
    assert 'st_attn_tiles_listed{kind="full"}' in text


def test_published_widths_give_223_leaves_and_a_2_63_gb_table():
    cfg = train_decoder.model_config(M, FILE)
    assert (cfg.moe_num_primary_experts, cfg.experts_held, cfg.vocab_held) == (64, (0, 16), 37984)
    assert (cfg.num_hidden_layers, cfg.vocab_size, cfg.compute_dtype) == (4, 151936, "bfloat16")
    assert [cfg.window(i) for i in range(4)] == [None, 4096, 4096, 4096]
    assert cfg.rope_layout[:4] == (0, 1, 1, 1) and len(cfg.rope_layout) == 52
    shapes = jax.eval_shape(lambda k: M.init_params(k, cfg), jax.random.key(0))
    spec = make_spec(shapes)
    assert spec.num_leaves == 223 and spec.total_n == 656_529_920
    want = {
        "model.embed_tokens.weight": (37984, 2560),
        "lm_head.weight": (37984, 2560),
        "model.norm.weight": (2560,),
        "model.layers.0.self_attn.q_proj.weight": (28 * 128, 2560),
        "model.layers.1.self_attn.k_proj.weight": (4 * 128, 2560),
        "model.layers.2.self_attn.v_proj.weight": (4 * 128, 2560),
        "model.layers.3.self_attn.o_proj.weight": (2560, 28 * 128),
        "model.layers.3.block_sparse_moe.primary_router.weight": (64, 2560),
        "model.layers.0.block_sparse_moe.experts.15.gate.weight": (768, 2560),
        "model.layers.0.block_sparse_moe.experts.0.down.weight": (2560, 768),
    }
    for name, shape in want.items():
        assert shapes[name].shape == shape, name
    assert "model.layers.0.block_sparse_moe.experts.16.up.weight" not in shapes
    assert "model.layers.4.input_layernorm.weight" not in shapes
