"""models/gated_swa_moe.py against the plain reference (chipbench/reference/
laguna_ref.py) on seeded random weights at a small preset, its shares of the
experts against the uncut layer, its frequency table, gate and head counts by
hand, and through PodTrainer."""

import functools
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench.jobs import train_decoder  # noqa: E402
from chipbench.reference import laguna_ref as R  # noqa: E402
from chipbench.reference import smallthinker_ref  # noqa: E402
from shared_tensor_tpu.models import gated_swa_moe as M  # noqa: E402
from shared_tensor_tpu.models import mla_moe, swa_moe  # noqa: E402
from shared_tensor_tpu.obs.schema import label_key  # noqa: E402
from shared_tensor_tpu.ops.table import make_spec  # noqa: E402
from shared_tensor_tpu.parallel import make_mesh  # noqa: E402
from shared_tensor_tpu.train import PodTrainer  # noqa: E402
from shared_tensor_tpu.utils.profiling import pod_registry, scope_map  # noqa: E402

with open(os.path.join(ROOT, "chipbench", "configs", "laguna-s-2.1.json")) as _f:
    FILE = json.load(_f)
# hidden 64, 2 K/V heads of 16 under 4 (full) and 6 (sliding) query heads, 16
# experts top-3 beside a shared one, window 48, layers [full + dense, sliding,
# sliding, sliding, full], YaRN on half a head of the full layers
PRESET = FILE["rehearsal"]["model"]
T = 128  # 2.7 windows: the band's far edge lies inside the sequence


def model(held=(0, 16), vocab=512):
    """The preset as the configuration file writes it, holding ``held`` of
    its 16 experts and ``vocab`` of its 512 rows."""
    return dict(PRESET, experts_held=list(held), num_experts=held[1], vocab_size=vocab)


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
    is 2.7 windows long; the gate's, the router's and the shared expert's
    leaves take their gradients too."""
    loss, aux, grads = program(held, vocab, "float32")
    ref_loss, (ce, logits, routed), ref_grads = reference(held, vocab)
    # 1e-5 relative: the two differ by reduction order alone
    assert abs(float(loss - ref_loss)) <= 1e-5 * float(ref_loss)
    assert float(aux["ce_main_of"][0]) == pytest.approx(float(ce), rel=1e-5)
    assert float(jnp.linalg.norm(aux["logits"][0] - logits) / jnp.linalg.norm(logits)) <= 1e-5
    assert set(grads) == set(ref_grads)
    worst = max(leaf_errors(grads, ref_grads).items(), key=lambda kv: kv[1])
    assert worst[1] <= 1e-5, worst
    assert float(jnp.linalg.norm(grads["model.layers.1.self_attn.g_proj.weight"])) > 0
    assert len(routed) == aux["choices"].shape[1] == 4  # layer 0 routes nothing
    for mine, (own, _) in zip(aux["choices"][0], routed):
        assert np.array_equal(np.sort(mine, axis=-1), np.sort(own, axis=-1))


def test_bfloat16_program_is_near_the_reference_forced_to_its_choices():
    """The chip's precision at the preset: bfloat16 products move the loss,
    the logits and the gradient by the rounding of a product, and the
    reference along the program's path chooses nearly the program's experts.
    Limits of the preset (widths of 64 read noisier than the chip's): the
    file's ``rehearsal.checks``."""
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
    queries in blocks of ``ROWS`` (``smallthinker_ref``'s helpers); here 32 of
    the preset's 128."""
    whole = reference((4, 4), 128)
    monkeypatch.setattr(smallthinker_ref, "ROWS", 32)
    m = model((4, 4), 128)
    (loss, outs), grads = jax.jit(jax.value_and_grad(
        lambda p, b: R.loss_and_outputs(p, b, m, None, POSITIONS), has_aux=True))(
            *inputs((4, 4), 128))
    assert float(loss) == pytest.approx(float(whole[0]), rel=1e-6)
    np.testing.assert_allclose(outs[0][1], whole[1][1], rtol=1e-4, atol=1e-5)
    assert max(leaf_errors(grads, whole[2]).values()) <= 1e-5


@pytest.mark.parametrize("layer", [1, 4])
def test_the_shares_add_up_to_the_uncut_layer(layer):
    """The deployment's claim: the routed parts the four shares of the
    experts give (the program, each told what it holds) sum to what the
    uncut reference gives for the whole layer, with attention, gate, router
    and the shared expert counted once. A sliding layer (6 heads, the band,
    plain RoPE) and a full one (4 heads, YaRN on half a head)."""
    whole = model((0, 16))
    params, _ = inputs((0, 16), 512)
    x = jax.random.normal(jax.random.key(5), (T, PRESET["hidden_size"]))
    pre = f"model.layers.{layer}."
    with jax.default_matmul_precision("highest"):
        want, (own, _) = R.block(params, layer, x, whole)
        h = x + R.self_attention(
            params, pre + "self_attn.",
            R._norm(x, params[pre + "input_layernorm.weight"], whole["rms_norm_eps"]),
            whole, layer)
        shared = R.swiglu(params, pre + "mlp.shared_expert.", R._norm(
            h, params[pre + "post_attention_layernorm.weight"], whole["rms_norm_eps"]))
    routed, pairs = 0.0, 0
    for first in range(0, 16, 4):
        cfg = config((first, 4))
        rope = M.layer_rope(cfg, cfg.layer_types[layer], T)
        y, aux = jax.jit(lambda p, x, cfg=cfg: M.block(
            mla_moe._sub(p, pre), x, rope, cfg, layer))(params, x)
        routed = routed + (y - h - shared)  # this share's experts alone
        pairs += int(aux["moe_pairs_held"])
        assert np.array_equal(np.sort(aux["choices"], axis=-1), np.sort(own, axis=-1))
    assert pairs == T * PRESET["num_experts_per_tok"]  # every pair held once
    assert float(jnp.max(jnp.abs(routed))) > 10 * float(jnp.max(jnp.abs(h + shared + routed - want)))
    np.testing.assert_allclose(h + shared + routed, want, rtol=2e-5, atol=2e-6)


def test_yarn_frequencies_are_the_hand_computed_ones():
    """The published group (64 turned dimensions, theta 5e5, factor 128, an
    original context of 8 192, beta 32 and 1). By hand: the pair that makes r
    turns over 8 192 positions is c(r) = 64 ln(8192 / (2 pi r)) / (2 ln 5e5):
    c(32) = 9.04 and c(1) = 17.49, so lo = 9 and hi = 18. Pair 0 (ramp 0)
    keeps f_0 = 1; pair 12 (ramp 1/3) has f_12 (1/3 / 128 + 2/3); pair 31
    (ramp 1) f_31 / 128. The reference's own table agrees."""
    keys = M.Config().rope("full_attention")
    assert (keys["rope_type"], keys["factor"], keys["partial_rotary_factor"]) == ("yarn", 128, 0.5)
    c = lambda r: 64 * math.log(8192 / (2 * math.pi * r)) / (2 * math.log(5e5))
    assert (math.floor(c(32)), math.ceil(c(1))) == (9, 18)
    assert 9.0 < c(32) < 9.1 and 17.4 < c(1) < 17.5
    inv = M.yarn_inv_freq(64, 5e5, 128, 8192, 32, 1)
    assert inv.shape == (32,) and inv.dtype == np.float32
    f = lambda i: 5e5 ** (-i / 32)
    assert inv[0] == 1.0
    assert inv[9] == pytest.approx(f(9), rel=1e-6)  # lo itself: ramp 0
    assert inv[12] == pytest.approx(f(12) * (1 / 3 / 128 + 2 / 3), rel=1e-6)
    assert inv[18] == pytest.approx(f(18) / 128, rel=1e-6)  # hi itself: ramp 1
    assert inv[31] == pytest.approx(f(31) / 128, rel=1e-6)
    np.testing.assert_array_equal(inv, R.yarn_frequencies(keys, 64))
    # the tables: 32 angles a position, cos and sin both times the attention factor
    cos, sin = M.layer_rope(M.Config(), "full_attention", 16)
    assert cos.shape == sin.shape == (16, 32)
    factor = 1.4852030263919618
    assert factor == pytest.approx(0.1 * math.log(128) + 1, rel=1e-12)
    np.testing.assert_allclose(cos[0], factor, rtol=1e-6)
    np.testing.assert_allclose(sin[5], factor * np.sin(5 * inv), rtol=1e-5, atol=1e-6)
    cos_w, _ = M.layer_rope(M.Config(), "sliding_attention", 16)
    assert cos_w.shape == (16, 64)
    np.testing.assert_allclose(cos_w[3], np.cos(3 * 1e4 ** (-np.arange(64) / 64)), rtol=1e-5, atol=1e-6)


def test_partial_rope_turns_the_first_dimensions_and_passes_the_rest():
    """Tables of 4 angles turn dimensions 0..7 of a head of 16 in pairs (i, i
    + 4), by complex arithmetic in float64 (times the factor), and leave 8..15
    alone; the reference's own rope agrees."""
    n, d = 16, 16
    keys = PRESET["rope_parameters"]["full_attention"]
    x = np.random.default_rng(3).normal(size=(n, 2, d)).astype(np.float32)
    cfg = config()
    cos, sin = M.layer_rope(cfg, "full_attention", n)
    assert cos.shape == (n, 4)
    got = swa_moe.rope_half(jnp.asarray(x), cos, sin)
    inv = M.yarn_inv_freq(8, keys["rope_theta"], keys["factor"],
                          keys["original_max_position_embeddings"], 32, 1).astype(np.float64)
    z = (x[..., :4] + 1j * x[..., 4:8]) * np.exp(1j * np.arange(n)[:, None] * inv)[:, None, :]
    want = np.concatenate([z.real * keys["attention_factor"], z.imag * keys["attention_factor"],
                           x[..., 8:]], -1)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(got)[..., 8:], x[..., 8:])
    np.testing.assert_allclose(
        R.turn(jnp.asarray(x), keys, jnp.arange(n, dtype=jnp.float32)), want,
        rtol=1e-5, atol=1e-6)


def test_a_gate_matrix_of_zeros_halves_the_attentions_output():
    """sigmoid(0) = 1/2 on every head; and without the gate's leaf the same
    function is the ungated attention the second decoder runs."""
    cfg = config()
    params, _ = inputs((0, 16), 512)
    x = jax.random.normal(jax.random.key(7), (T, PRESET["hidden_size"]))
    for layer in (0, 1):
        p = mla_moe._sub(params, f"model.layers.{layer}.self_attn.")
        rope = M.layer_rope(cfg, cfg.layer_types[layer], T)
        run = lambda p: swa_moe.attention(p, x, rope, cfg.window(layer), cfg, cfg.heads(layer))
        ungated = run({k: v for k, v in p.items() if k != "g_proj.weight"})
        halved = run(dict(p, **{"g_proj.weight": jnp.zeros_like(p["g_proj.weight"])}))
        np.testing.assert_allclose(halved, 0.5 * ungated, rtol=1e-5, atol=1e-7)
        gated = run(p)
        assert not np.allclose(gated, halved, rtol=1e-3, atol=1e-6)


def test_a_layers_heads_follow_num_attention_heads_per_layer():
    """The leaves' shapes, and what the traced calls report: 4 query heads on
    the full layers, 6 on the sliding ones, 2 K/V heads under both."""
    cfg = config((4, 4), 128)
    shapes = M.param_shapes(cfg)
    for layer, heads in enumerate(PRESET["num_attention_heads_per_layer"]):
        a = f"model.layers.{layer}.self_attn."
        assert shapes[a + "q_proj.weight"] == (heads * 16, 64)
        assert shapes[a + "o_proj.weight"] == (64, heads * 16)
        assert shapes[a + "g_proj.weight"] == (heads, 64)
        assert shapes[a + "k_proj.weight"] == shapes[a + "v_proj.weight"] == (2 * 16, 64)
        assert cfg.heads(layer) == heads
    assert [cfg.window(i) for i in range(5)] == [None, 48, 48, 48, None]
    assert [cfg.is_dense(i) for i in range(5)] == [True, False, False, False, False]
    assert cfg.expert_layers == 4

    def counts():
        snap = pod_registry().snapshot()
        return {k: snap[label_key("st_attn_traces_total", "kind", k)] for k in ("full", "window")}

    before = counts()
    jax.eval_shape(lambda p, b: M.loss_fn(p, b, cfg), *inputs((4, 4), 128))
    after = counts()
    assert after["full"] - before["full"] == 2 and after["window"] - before["window"] == 3
    snap = pod_registry().snapshot()
    assert snap[label_key("st_attn_heads", "kind", "full")] == 4
    assert snap[label_key("st_attn_heads", "kind", "window")] == 6
    # the scan's tiles of 32 over 128 positions: the triangle 10, the band of 48 keys 9
    assert snap[label_key("st_attn_tiles_listed", "kind", "full")] == 10
    assert snap[label_key("st_attn_tiles_listed", "kind", "window")] == 9
    assert 'st_attn_heads{kind="window"} 6' in pod_registry().prometheus_text()


def test_the_bytes_a_layers_checkpoint_keeps_of_its_attention_are_a_gauge_by_kind():
    """``st_attn_saved_bytes{kind}``: q, k, v, o (the operands' dtype) and lse
    (float32) of the newest traced call, heads first: at 128 tokens and heads
    of 16, 4 query heads on 2 K/V heads over the prefix, 6 on 2 under the
    window."""
    def saved(dtype):
        cfg = config((4, 4), 128, dtype=dtype)
        jax.eval_shape(lambda p, b: M.loss_fn(p, b, cfg), *inputs((4, 4), 128))
        snap = pod_registry().snapshot()
        return {k: snap[label_key("st_attn_saved_bytes", "kind", k)] for k in ("full", "window")}

    want = lambda heads, size: (heads + 2 + 2 + heads) * T * 16 * size + heads * T * 4
    assert saved("bfloat16") == {"full": want(4, 2), "window": want(6, 2)}
    assert f'st_attn_saved_bytes{{kind="window"}} {want(6, 2)}' in pod_registry().prometheus_text()
    assert saved("float32") == {"full": want(4, 4), "window": want(6, 4)}  # the scan's own programs


def test_router_weights_are_the_renormalised_top_of_a_softmax_over_all_experts():
    cfg = config()
    w_r = jax.random.normal(jax.random.key(2), (16, 64))
    u = jax.random.normal(jax.random.key(3), (T, 64))
    idx, w = M.route({"gate.weight": w_r}, u, cfg)
    logits = np.asarray(u, np.float64) @ np.asarray(w_r, np.float64).T
    s = np.exp(logits - logits.max(-1, keepdims=True))
    s /= s.sum(-1, keepdims=True)
    order = np.argsort(-s, axis=-1)[:, :3]
    assert np.array_equal(np.sort(idx, axis=-1), np.sort(order, axis=-1))
    chosen = np.take_along_axis(s, np.asarray(idx), axis=-1)
    np.testing.assert_allclose(w, 2.5 * chosen / chosen.sum(-1, keepdims=True), rtol=1e-4)
    np.testing.assert_allclose(np.sum(w, axis=-1), 2.5, rtol=1e-5)
    # renormalised, that is the second decoder's softmax over the chosen logits, scaled
    e = np.exp(np.take_along_axis(logits, np.asarray(idx), axis=-1))
    np.testing.assert_allclose(w, 2.5 * e / e.sum(-1, keepdims=True), rtol=1e-4)
    raw = M.route({"gate.weight": w_r}, u, config(norm_topk_prob=False))[1]
    np.testing.assert_allclose(raw, 2.5 * chosen, rtol=1e-4)


SCOPES = [
    "st.embed", "st.attn", "st.attn.proj", "st.attn.full", "st.attn.window", "st.attn.gate",
    "st.ffn", "st.moe", "st.moe.router", "st.moe.dispatch", "st.moe.experts", "st.moe.combine",
    "st.moe.shared", "st.head_loss",
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


def test_aux_comes_out_of_the_step_one_entry_an_expert_layer(trained):
    aux = trained["trainer"].aux
    assert aux["ce_main"].shape == (1,) and aux["moe_pairs_held"].shape == (1, 4)
    assert set(aux) == {"ce_main", "moe_pairs_held", "moe_load_max_over_mean",
                        "moe_tokens_unrouted_share", "moe_rows_executed"}
    snap = pod_registry().snapshot()
    assert snap["st_moe_pairs_held_total"] == float(np.sum(aux["moe_pairs_held"]))


@pytest.mark.parametrize("scope", SCOPES)
def test_the_compiled_step_holds_the_scope(trained, scope):
    scopes = set(scope_map(trained["text"]).values())
    assert any(s.split("/")[-1] == scope for s in scopes), sorted(scopes)
    if scope != "st.embed":
        assert all(s.startswith("st.grads") for s in scopes if s.endswith(scope))
    if scope.startswith("st.attn."):
        assert all("st.attn/" in s for s in scopes if s.endswith(scope))
    assert not [s for s in scopes if "st.mla" in s or "st.mtp" in s]


def test_published_widths_give_153_leaves_and_a_3_24_gb_table():
    cfg = train_decoder.model_config(M, FILE)
    assert (cfg.num_experts, cfg.experts_held, cfg.vocab_held) == (256, (0, 8), 12544)
    assert (cfg.num_hidden_layers, cfg.vocab_size, cfg.compute_dtype) == (5, 100352, "bfloat16")
    assert [cfg.window(i) for i in range(5)] == [None, 512, 512, 512, None]
    assert [cfg.heads(i) for i in range(5)] == [48, 72, 72, 72, 48]
    assert [cfg.is_dense(i) for i in range(5)] == [True, False, False, False, False]
    assert len(cfg.layer_types) == 48 and cfg.expert_layers == 4
    assert cfg.rope("full_attention")["attention_factor"] == 1.4852030263919618
    assert hash(cfg) == hash(train_decoder.model_config(M, FILE))
    shapes = jax.eval_shape(lambda k: M.init_params(k, cfg), jax.random.key(0))
    spec = make_spec(shapes)
    assert spec.num_leaves == 153 and spec.total_n == 811_017_216
    want = {
        "model.embed_tokens.weight": (12544, 3072),
        "lm_head.weight": (12544, 3072),
        "model.norm.weight": (3072,),
        "model.layers.0.self_attn.q_proj.weight": (48 * 128, 3072),
        "model.layers.1.self_attn.q_proj.weight": (72 * 128, 3072),
        "model.layers.1.self_attn.k_proj.weight": (8 * 128, 3072),
        "model.layers.3.self_attn.o_proj.weight": (3072, 72 * 128),
        "model.layers.3.self_attn.g_proj.weight": (72, 3072),
        "model.layers.4.self_attn.g_proj.weight": (48, 3072),
        "model.layers.0.mlp.up_proj.weight": (12288, 3072),
        "model.layers.4.mlp.gate.weight": (256, 3072),
        "model.layers.2.mlp.shared_expert.down_proj.weight": (3072, 1024),
        "model.layers.1.mlp.experts.7.gate_proj.weight": (1024, 3072),
    }
    for name, shape in want.items():
        assert shapes[name].shape == shape, name
    assert "model.layers.1.mlp.experts.8.up_proj.weight" not in shapes
    assert "model.layers.0.mlp.gate.weight" not in shapes
    assert "model.layers.5.input_layernorm.weight" not in shapes
