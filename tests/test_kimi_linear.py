"""models/kimi_linear.py against the plain reference (chipbench/reference/
kimi_ref.py) on seeded random weights at the configuration's rehearsal
preset: the chunked delta-rule scan against the recurrence one position at a
time (strong decays included), the model's loss, logits and every gradient,
the shares of experts against the uncut layer, and through PodTrainer."""

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
from chipbench.reference import kimi_ref as R  # noqa: E402
from shared_tensor_tpu.models import kimi_linear as M  # noqa: E402
from shared_tensor_tpu.models import mla_moe  # noqa: E402
from shared_tensor_tpu.ops.table import make_spec  # noqa: E402
from shared_tensor_tpu.parallel import make_mesh  # noqa: E402
from shared_tensor_tpu.train import PodTrainer  # noqa: E402
from shared_tensor_tpu.utils.profiling import pod_registry, scope_map  # noqa: E402

with open(os.path.join(ROOT, "chipbench", "configs", "kimi-linear-48b-a3b.json")) as _f:
    FILE = json.load(_f)
# hidden 64; layers KKKAK (layer 0 dense at 128, then expert layers); KDA 4
# heads of 16; MLA 4 heads of 16 + 8 / 16 on a kv rank of 32; 8 experts (of
# 32) top-4 of width 32, one shared
PRESET = FILE["rehearsal"]["model"]
T = 96  # a chunk of 64 and a padded one; three attention tiles of 32
POSITIONS = jnp.arange(0, T, 8)
WHOLE = dict(experts_held=[0, 32], num_experts=32)


def model(**over):
    return dict(PRESET, **over)


def config(dtype="float32", **over):
    return train_decoder.model_config(M, model(**over), compute_dtype=dtype)


@functools.lru_cache(maxsize=None)
def inputs():
    params = M.init_params(jax.random.key(0), config())
    return params, jax.random.randint(jax.random.key(1), (1, T), 0, PRESET["vocab_size"])


@functools.lru_cache(maxsize=None)
def program(dtype):
    cfg = config(dtype)
    (loss, aux), grads = jax.jit(jax.value_and_grad(
        lambda p, b: M.loss_fn(p, b, cfg, positions=POSITIONS), has_aux=True))(*inputs())
    return loss, aux, grads


@functools.lru_cache(maxsize=None)
def reference(forced=None):
    choices = None if forced is None else [list(program(forced)[1]["choices"][0])]
    (loss, outs), grads = jax.jit(jax.value_and_grad(
        lambda p, b: R.loss_and_outputs(p, b, model(), choices, POSITIONS),
        has_aux=True))(*inputs())
    return loss, outs[0], grads


def leaf_errors(got, want):
    return {k: float(jnp.linalg.norm(got[k] - want[k]) / (jnp.linalg.norm(want[k]) + 1e-30))
            for k in want}


def scan_case(n, strong=False, h=2, d=16):
    """q, k unit vectors (q times d^-1/2), v, log-decays g and beta of the
    recurrence; ``strong`` decays of about -20 a position, which a chunk of
    64 adds up to about -1 300."""
    k = jax.random.split(jax.random.key(n + strong), 5)
    q = M.l2_normalised(jax.random.normal(k[0], (n, h, d))) * d ** -0.5
    kk = M.l2_normalised(jax.random.normal(k[1], (n, h, d)))
    v = jax.random.normal(k[2], (n, h, d))
    if strong:
        g = -20.0 * jax.random.uniform(k[3], (n, h, d), minval=0.5, maxval=1.5)
    else:
        g = -jax.nn.softplus(jax.random.normal(k[3], (n, h, d)) - 1.0)
    return q, kk, v, g, jax.nn.sigmoid(jax.random.normal(k[4], (n, h)))


@pytest.mark.parametrize("n,strong", [(100, False), (192, True), (2100, False), (300, True)])
def test_the_chunked_scan_is_the_recurrence_forward_and_backward(n, strong):
    """Chunks of 64 against the reference's one position at a time, forward
    and all five cotangents. 100, 300 and 2 100 are no multiple of the chunk
    (the last chunk is padded with g = 0, beta = 0); 2 100 runs two groups of
    chunks (the state passed between them, the backward rule going back
    over them); 192 and 300 decay at about -20 a position, where a chunk's
    e^-Γ is past float32's range. The decays' own cotangent is then about a
    millionth of the others' (the state forgets within a position) and
    reads float32's rounding of the chunked form's sums, so it is held to a
    share of its own size that leaves that rounding room."""
    args = scan_case(n, strong)
    cot = jax.random.normal(jax.random.key(7), (n, 2, 16))

    @jax.jit
    def both(*args):
        with jax.default_matmul_precision("highest"):
            out = []
            for fn in (lambda *a: M.kda_scan(*a, jnp.float32), R.recurrence):
                y, vjp = jax.vjp(fn, *args)
                out.append((y, vjp(cot)))
            return out

    (got, got_g), (want, want_g) = both(*args)
    assert got.shape == want.shape == (n, 2, 16)
    assert bool(jnp.all(jnp.isfinite(got))) and all(bool(jnp.all(jnp.isfinite(a))) for a in got_g)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5 * float(jnp.max(jnp.abs(want))))
    for i, (a, b) in enumerate(zip(got_g, want_g)):
        tol = 2e-4 if strong and i == 3 else 2e-5
        assert float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)) <= tol, i
    if strong:
        gam = jnp.cumsum(args[3][:64], axis=0)
        assert bool(jnp.any(jnp.isinf(jnp.exp(-gam))))  # what a naive form would make


def test_the_scan_starts_from_a_zero_state_and_its_blocks_are_the_whole():
    """o_0 = beta_0 (q_0 . k_0) v_0: nothing enters the first chunk; and the
    reference's checkpointed blocks of 128 positions give the whole scan's
    answer (256 positions)."""
    q, k, v, g, beta = scan_case(256)
    o = jax.jit(M.kda_scan, static_argnums=5)(q, k, v, g, beta, jnp.float32)
    first = (beta[0] * jnp.sum(q[0] * k[0], axis=-1))[:, None] * v[0]
    np.testing.assert_allclose(o[0], first, rtol=1e-5, atol=1e-6)
    blocked = jax.jit(R.recurrence)(q, k, v, g, beta)
    whole = jax.jit(R.recurrence)(q[:255], k[:255], v[:255], g[:255], beta[:255])
    np.testing.assert_allclose(blocked[:255], whole, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(o, blocked, rtol=2e-5, atol=2e-5 * float(jnp.max(jnp.abs(o))))


def test_float32_program_equals_the_reference_in_loss_logits_and_every_gradient():
    """The preset's five layers (four KDA mixers, one MLA layer without
    position encoding; layer 0 dense, four expert layers) at 96 tokens; the
    KDA leaves take their gradients too."""
    loss, aux, grads = program("float32")
    ref_loss, (ce, logits, routed), ref_grads = reference()
    assert abs(float(loss - ref_loss)) <= 1e-5 * float(ref_loss)
    assert float(aux["ce_main_of"][0]) == pytest.approx(float(ce), rel=1e-5)
    assert float(jnp.linalg.norm(aux["logits"][0] - logits) / jnp.linalg.norm(logits)) <= 1e-5
    assert set(grads) == set(ref_grads)
    worst = max(leaf_errors(grads, ref_grads).items(), key=lambda kv: kv[1])
    assert worst[1] <= 2e-5, worst
    for leaf in ("A_log", "dt_bias", "q_conv1d.weight", "f_a_proj.weight", "g_b_proj.weight",
                 "b_proj.weight", "o_norm.weight"):
        assert float(jnp.linalg.norm(grads["model.layers.0.self_attn." + leaf])) > 0
    assert float(jnp.linalg.norm(grads["model.layers.3.self_attn.q_proj.weight"])) > 0
    assert len(routed) == aux["choices"].shape[1] == 4
    for mine, (own, _) in zip(aux["choices"][0], routed):
        assert np.array_equal(np.sort(mine, axis=-1), np.sort(own, axis=-1))


def test_bfloat16_program_is_near_the_reference_forced_to_its_choices():
    """The chip's precision at the preset's widths, held to the file's
    rehearsal limits."""
    chk = dict(FILE["checks"], **FILE["rehearsal"]["checks"])
    _, aux, grads = program("bfloat16")
    _, (ce, logits, routed), ref_grads = reference("bfloat16")
    assert abs(float(aux["ce_main_of"][0] - ce)) <= chk["ce_tol"]
    err = float(jnp.linalg.norm(aux["logits"][0] - logits) / jnp.linalg.norm(logits))
    assert 0 < err <= chk["logits_rel_tol"]
    agree, outside = train_decoder.choice_agreement(
        list(aux["choices"][0]), routed, chk["choices_margin"])
    assert float(agree) >= chk["choices_agree_min"] and int(outside) == 0
    errs = sorted(leaf_errors(grads, ref_grads).values())
    assert errs[-1] <= chk["update_rel_tol"]
    assert errs[len(errs) // 2] <= chk["update_rel_median_tol"]


def test_the_mla_layer_keeps_its_rope_part_unturned():
    """``mla_moe.mla`` with one ``q_proj`` and no tables: the same as the
    reference's latent attention, and independent of position but for the
    causal mask (the last query over a sequence is the first query over the
    sequence turned around and cut to one)."""
    params, _ = inputs()
    cfg = config()
    pre = "model.layers.3.self_attn."
    x = jax.random.normal(jax.random.key(5), (T, 64))
    with jax.default_matmul_precision("highest"):
        want = R.mla(params, pre, x, model())
        got = jax.jit(lambda p: mla_moe.mla(mla_moe._sub(p, pre), x, None, cfg))(params)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5 * float(jnp.max(jnp.abs(want))))
        one = jax.jit(lambda p, x: mla_moe.mla(mla_moe._sub(p, pre), x, None, cfg))(params, x[:1])
    np.testing.assert_allclose(one[0], got[0], rtol=2e-5, atol=2e-6)


# --- the expert shares ---------------------------------------------------------------


def test_the_expert_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """Four chips of 8 experts (of 32): the routed parts each gives, with the
    router and the shared expert counted once, sum to the uncut reference
    expert layer; every pair is held once."""
    cfg_whole = config(**WHOLE)
    params = M.init_params(jax.random.key(3), cfg_whole)
    x = jax.random.normal(jax.random.key(4), (T, 64))
    pre = "model.layers.1."
    m_whole = model(**WHOLE)
    with jax.default_matmul_precision("highest"):
        want, (own, _) = R.block(params, 1, x, m_whole)
        h = x + R.kda(params, pre + "self_attn.", R._norm(
            x, params[pre + "input_layernorm.weight"], PRESET["rms_norm_eps"]), m_whole)
        u = R._norm(h, params[pre + "post_attention_layernorm.weight"], PRESET["rms_norm_eps"])
        shared = R.swiglu(params, pre + "block_sparse_moe.shared_experts.", u)
    routed, pairs = 0.0, 0
    for first in range(0, 32, 8):
        cfg = config(**dict(WHOLE, experts_held=[first, 8]))
        y, aux = jax.jit(lambda p, cfg=cfg: M.block(mla_moe._sub(p, pre), x, cfg, 1))(params)
        routed = routed + (y - h - shared)
        pairs += int(aux["moe_pairs_held"])
        assert np.array_equal(np.sort(aux["choices"], axis=-1), np.sort(own, axis=-1))
    assert pairs == T * PRESET["num_experts_per_token"]
    assert float(jnp.max(jnp.abs(routed))) > 10 * float(jnp.max(jnp.abs(h + shared + routed - want)))
    np.testing.assert_allclose(h + shared + routed, want, rtol=2e-5, atol=2e-5)


# --- through PodTrainer, and what the program says about itself --------------------


SCOPES = [
    "st.embed", "st.kda", "st.kda.proj", "st.kda.conv", "st.kda.gate", "st.kda.scan",
    "st.kda.norm", "st.mla", "st.mla.proj", "st.mla.attn", "st.ffn", "st.moe", "st.moe.router",
    "st.moe.dispatch", "st.moe.experts", "st.moe.combine", "st.moe.shared", "st.head_loss",
]


@pytest.fixture(scope="module")
def trained():
    """Three PodTrainer steps on (1,1), the default fused program, and the
    same three of bare SGD."""
    lr = 0.5
    cfg = config()
    params, _ = inputs()
    loss_fn = lambda p, b: M.loss_fn(p, b, cfg)
    batches = [jax.random.randint(jax.random.key(10 + i), (1, 1, T), 0, 128) for i in range(3)]
    before = pod_registry().snapshot()["st_kda_traces_total"]
    trainer = PodTrainer(make_mesh(1, 1), params, loss_fn)
    trainer._step = trainer.lower(trainer.shard_batch(batches[0]), lr).compile()
    text = trainer._step.as_text()
    traces = pod_registry().snapshot()["st_kda_traces_total"] - before
    got = [float(trainer.step(trainer.shard_batch(b), lr)[0][0]) for b in batches]

    @jax.jit
    def sgd(p, b):
        (loss, _), g = jax.value_and_grad(loss_fn, has_aux=True)(p, b)
        return loss, jax.tree.map(lambda a, d: a - lr * d, p, g)

    bare, p = [], params
    for b in batches:
        loss, p = sgd(p, b[0])
        bare.append(float(loss))
    return dict(trainer=trainer, got=got, bare=bare, bare_params=p, text=text, traces=traces)


def test_podtrainer_steps_equal_bare_sgd(trained):
    assert trained["got"] == pytest.approx(trained["bare"], rel=1e-5)
    errs = leaf_errors(trained["trainer"].read(0), trained["bare_params"])
    assert max(errs.values()) <= 1e-5, max(errs.items(), key=lambda kv: kv[1])


def test_the_scans_counters_are_in_the_pod_registry(trained):
    """One count a traced scan (four mixers), and the newest call's heads,
    chunks and the bytes its layer's checkpoint keeps: the output and the
    state entering its one group."""
    assert trained["traces"] == 4
    snap = pod_registry().snapshot()
    assert snap["st_kda_heads"] == 4 and snap["st_kda_chunks"] == 2
    assert snap["st_kda_saved_bytes"] == 4 * (T * 4 * 16 + 4 * 16 * 16)
    text = pod_registry().prometheus_text()
    for name in ("st_kda_traces_total", "st_kda_heads", "st_kda_chunks", "st_kda_saved_bytes"):
        assert name in text


@pytest.mark.parametrize("scope", SCOPES)
def test_the_compiled_step_holds_the_scope(trained, scope):
    scopes = set(scope_map(trained["text"]).values())
    assert any(s.split("/")[-1] == scope for s in scopes), sorted(scopes)
    if scope != "st.embed":
        assert all(s.startswith("st.grads") for s in scopes if s.endswith(scope))
    if scope.startswith("st.kda."):
        assert all("st.kda/" in s for s in scopes if s.endswith(scope))
    assert not [s for s in scopes if "st.ssm" in s or "st.mtp" in s or "st.attn" in s]


def test_the_layers_checkpoint_keeps_the_scans_output_and_states(capsys):
    """One KDA layer under ``mla_moe.layer_checkpoint`` keeps, beside its
    arguments, the scan's output and the state entering each group (one
    here), and nothing else: the recomputed layer runs the scan no second
    time and no chunk's intermediates live on."""
    params, _ = inputs()
    cfg = config()
    x = jax.random.normal(jax.random.key(6), (T, 64))
    fn = mla_moe.layer_checkpoint(functools.partial(M.block, cfg=cfg, layer=1))
    p = mla_moe._sub(params, "model.layers.1.")
    jax.ad_checkpoint.print_saved_residuals(lambda p, x: fn(p, x)[0].sum(), p, x)
    lines = [line for line in capsys.readouterr().out.splitlines()
             if "from the argument" not in line]
    assert sorted(line.split()[0] for line in lines) == ["f32[1,4,16,16]", f"f32[{T},4,16]"], lines
    assert any(mla_moe.KDA_OUT in line for line in lines)


def test_published_widths_give_193_leaves_and_a_2_41_gb_table():
    cfg = train_decoder.model_config(M, FILE)
    assert (cfg.num_experts, cfg.experts_held, cfg.vocab_held) == (256, (0, 8), 20480)
    assert cfg.plan == "KKKAK" and cfg.expert_layers == 4 and cfg.num_experts_per_tok == 8
    assert (cfg.kda_heads, cfg.kda_head_dim, cfg.n_routed_experts) == (32, 128, 256)
    assert hash(cfg) == hash(train_decoder.model_config(M, FILE))
    shapes = jax.eval_shape(lambda k: M.init_params(k, cfg), jax.random.key(0))
    spec = make_spec(shapes)
    assert spec.num_leaves == 193 and spec.total_n == 602_433_408
    assert 2.409e9 < 4 * spec.total_n < 2.410e9
    kda = sum(int(np.prod(s.shape)) for k, s in shapes.items()
              if k.startswith("model.layers.1.self_attn."))
    mla = sum(int(np.prod(s.shape)) for k, s in shapes.items()
              if k.startswith("model.layers.3.self_attn."))
    experts = sum(int(np.prod(s.shape)) for k, s in shapes.items()
                  if k.startswith("model.layers.2.block_sparse_moe."))
    assert (kda, mla, experts) == (39_514_272, 29_114_880, 64_290_816)
    want = {
        "model.embed_tokens.weight": (20480, 2304),
        "lm_head.weight": (20480, 2304),
        "model.layers.0.self_attn.q_proj.weight": (4096, 2304),
        "model.layers.0.self_attn.q_conv1d.weight": (4, 4096),
        "model.layers.0.self_attn.A_log": (32,),
        "model.layers.0.self_attn.dt_bias": (4096,),
        "model.layers.0.self_attn.f_b_proj.weight": (4096, 128),
        "model.layers.0.self_attn.o_norm.weight": (128,),
        "model.layers.0.mlp.gate_proj.weight": (9216, 2304),
        "model.layers.3.self_attn.q_proj.weight": (6144, 2304),
        "model.layers.3.self_attn.kv_a_proj_with_mqa.weight": (576, 2304),
        "model.layers.3.self_attn.kv_b_proj.weight": (8192, 512),
        "model.layers.3.self_attn.o_proj.weight": (2304, 4096),
        "model.layers.4.block_sparse_moe.gate.weight": (256, 2304),
        "model.layers.4.block_sparse_moe.experts.7.w2.weight": (2304, 1024),
    }
    for name, shape in want.items():
        assert shapes[name].shape == shape, name
    assert "model.layers.1.block_sparse_moe.experts.8.w1.weight" not in shapes
    assert "model.layers.1.block_sparse_moe.gate.e_score_correction_bias" not in shapes
    assert "model.layers.3.self_attn.q_a_proj.weight" not in shapes


def test_the_kda_leaves_start_by_the_mamba2_convention():
    cfg = config()
    p = M.init_params(jax.random.key(5), cfg)
    a = -jnp.exp(p["model.layers.0.self_attn.A_log"])
    assert a.shape == (4,) and bool(jnp.all((a <= -1.0) & (a >= -16.0)))
    dt = jax.nn.softplus(p["model.layers.0.self_attn.dt_bias"])
    assert dt.shape == (64,) and bool(jnp.all((dt >= 1e-3 * 0.999) & (dt <= 0.1 * 1.001)))
    conv = p["model.layers.0.self_attn.k_conv1d.weight"]
    assert conv.shape == (4, 64) and float(jnp.max(jnp.abs(conv))) <= 0.5
    assert bool(jnp.all(p["model.layers.0.self_attn.o_norm.weight"] == 1))
    with pytest.raises(ValueError):
        config(q_lora_rank=16)
    with pytest.raises(ValueError):
        config(mla_use_nope=False)
