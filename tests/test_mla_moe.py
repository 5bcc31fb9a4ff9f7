"""models/mla_moe.py against the plain reference (chipbench/reference/
joyai_ref.py) on seeded random weights at a small preset, and through
PodTrainer: the eight checks ISSUE 29 lists."""

import collections
import dataclasses
import functools
import json
import os
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jex_core

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench.jobs import train_lm  # noqa: E402
from chipbench.reference import joyai_ref as R  # noqa: E402
from shared_tensor_tpu.models import mla_moe as M  # noqa: E402
from shared_tensor_tpu.obs.schema import label_key  # noqa: E402
from shared_tensor_tpu.ops import moe_pallas  # noqa: E402
from shared_tensor_tpu.ops.table import make_spec  # noqa: E402
from shared_tensor_tpu.parallel import make_mesh  # noqa: E402
from shared_tensor_tpu.train import PodTrainer  # noqa: E402
from shared_tensor_tpu.utils.profiling import pod_registry  # noqa: E402

with open(os.path.join(ROOT, "chipbench", "configs", "joyai-llm-flash.json")) as _f:
    FILE = json.load(_f)
PRESET = FILE["rehearsal"]["model"]  # hidden 64, 4 heads, 16 experts top-4, ...
CHECKS = dict(FILE["checks"], **FILE["rehearsal"]["checks"])  # the preset's own limits
T = 64


def model(held=(0, 16), vocab=512):
    """The preset as the configuration file writes it, holding ``held`` of
    its 16 experts and ``vocab`` of its 512 rows."""
    return dict(PRESET, experts_held=list(held), n_routed_experts=held[1], vocab_size=vocab)


def config(held=(0, 16), vocab=512, dtype="float32", **over):
    return train_lm.model_config(model(held, vocab), compute_dtype=dtype, **over)


@functools.lru_cache(maxsize=None)
def inputs(held, vocab):
    """Seeded weights (the router's bias set off zero, so that the bias
    matters) and one sequence."""
    params = M.init_params(jax.random.key(0), config(held, vocab))
    for name in params:
        if name.endswith("e_score_correction_bias"):
            params[name] = 0.05 * jax.random.normal(jax.random.key(7), params[name].shape)
    tokens = jax.random.randint(jax.random.key(1), (1, T), 0, vocab)
    return params, tokens


@functools.lru_cache(maxsize=None)
def program(held, vocab, dtype):
    """loss, aux and gradients of the program."""
    cfg = config(held, vocab, dtype)
    (loss, aux), grads = jax.jit(jax.value_and_grad(
        lambda p, b: M.loss_fn(p, b, cfg), has_aux=True))(*inputs(held, vocab))
    return loss, aux, grads


POSITIONS = jnp.arange(0, T, 4)


@functools.lru_cache(maxsize=None)
def checked(held, vocab, dtype):
    """What the program's loss hands a comparison, of the one sequence."""
    cfg = config(held, vocab, dtype)
    aux = jax.jit(lambda p, b: M.loss_fn(p, b, cfg, positions=POSITIONS)[1])(*inputs(held, vocab))
    return {"ce_main": aux["ce_main_of"][0], "ce_mtp": aux["ce_mtp_of"][0],
            "logits": aux["logits"][0], "choices": aux["choices"][0]}


@functools.lru_cache(maxsize=None)
def reference_fns(held, vocab):
    """The reference's jitted (forward, loss-and-gradients), the experts
    forced to the ``choices`` they are given."""
    m = model(held, vocab)

    def forward(p, t, choices):
        return R.outputs(p, t, m, choices, POSITIONS)

    return jax.jit(forward), jax.jit(jax.value_and_grad(
        lambda p, b, choices: R.loss(p, b, m, [choices])))


@functools.lru_cache(maxsize=None)
def reference(held, vocab):
    """loss and gradients of the reference, its experts its own."""
    m = model(held, vocab)
    return jax.jit(jax.value_and_grad(lambda p, b: R.loss(p, b, m)))(*inputs(held, vocab))


def leaf_errors(got, want):
    """Relative error in the L2 norm, leaf by leaf."""
    return {k: float(jnp.linalg.norm(got[k] - want[k]) / (jnp.linalg.norm(want[k]) + 1e-30))
            for k in want}


# 1 -----------------------------------------------------------------------------


@pytest.mark.parametrize("held,vocab", [((0, 16), 512), ((4, 4), 512), ((4, 4), 128)])
def test_float32_program_equals_the_reference_in_loss_and_every_gradient(held, vocab):
    """All experts held and a share of them; the vocabulary whole and sliced."""
    loss, aux, grads = program(held, vocab, "float32")
    ref_loss, ref_grads = reference(held, vocab)
    # 1e-5 relative: the two differ by reduction order alone
    assert abs(float(loss - ref_loss)) <= 1e-5 * float(ref_loss)
    assert set(grads) == set(ref_grads)
    worst = max(leaf_errors(grads, ref_grads).items(), key=lambda kv: kv[1])
    assert worst[1] <= 1e-5, worst
    assert float(aux["ce_main"] + PRESET["mtp_loss_weight"] * aux["ce_mtp"]) == pytest.approx(
        float(loss), rel=1e-6)


def test_the_reference_in_row_blocks_equals_the_reference_whole(monkeypatch):
    """At the chip's sizes the reference runs its row-wise parts in blocks of
    ``ROWS`` rows; here 16 of the preset's 64."""
    held, vocab = (4, 4), 128
    whole_loss, whole_grads = reference(held, vocab)
    monkeypatch.setattr(R, "ROWS", 16)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p, b: R.loss(p, b, model(held, vocab))))(*inputs(held, vocab))
    assert abs(float(loss - whole_loss)) <= 1e-6 * float(whole_loss)
    worst = max(leaf_errors(grads, whole_grads).items(), key=lambda kv: kv[1])
    assert worst[1] <= 1e-5, worst


# 2 -----------------------------------------------------------------------------


def chip_checks(held, vocab, dtype):
    """The chip job's comparisons (chipbench/jobs/train_lm.py) at the preset:
    name -> (reading, limit), and the choices' (agreement, pairs outside the
    margin)."""
    params, tokens = inputs(held, vocab)
    got, grads = checked(held, vocab, dtype), program(held, vocab, dtype)[2]
    choices = list(got["choices"])
    forward, loss_and_grads = reference_fns(held, vocab)
    (ref_main, ref_mtp), ref_logits, routed = forward(params, tokens[0], choices)
    _, ref_grads = loss_and_grads(params, tokens, choices)
    errs = leaf_errors(grads, ref_grads)
    errs = {k: v for k, v in errs.items() if not k.endswith("e_score_correction_bias")}
    agree, outside = train_lm.choice_agreement(choices, routed, CHECKS["choices_margin"])
    return {
        "ce_main": (abs(float(got["ce_main"] - ref_main)), CHECKS["ce_tol"]),
        "ce_mtp": (abs(float(got["ce_mtp"] - ref_mtp)), CHECKS["ce_tol"]),
        "logits": (float(jnp.linalg.norm(got["logits"] - ref_logits)
                         / jnp.linalg.norm(ref_logits)), CHECKS["logits_rel_tol"]),
        "update": (max(errs.values()), CHECKS["update_rel_tol"]),
        "update_median": (float(np.median(list(errs.values()))), CHECKS["update_rel_median_tol"]),
        "choices": (1.0 - float(agree), 1.0 - CHECKS["choices_agree_min"]),
        "choices_outside_margin": (int(outside), 0),
    }


def test_bfloat16_program_is_inside_the_chip_checks_tolerances():
    held, vocab = (4, 4), 128
    readings = chip_checks(held, vocab, "bfloat16")
    assert all(got <= limit for got, limit in readings.values()), readings
    # the bias takes no gradient, in any precision
    grads = program(held, vocab, "bfloat16")[2]
    assert all(not np.any(np.asarray(g)) for k, g in grads.items()
               if k.endswith("e_score_correction_bias"))


def test_a_lower_precision_variant_of_the_program_fails_a_tolerance(monkeypatch):
    """The expert products' operands rounded to 8 bits (e4m3). (A router in
    bfloat16 is not told apart at this preset: the bfloat16 products upstream
    of it move its scores as much.)"""
    monkeypatch.setattr(M, "_expert_operand", lambda x, dt: x.astype(
        jnp.float8_e4m3fn).astype(dt))
    program.cache_clear()
    checked.cache_clear()
    try:
        readings = chip_checks((4, 4), 128, "bfloat16")
    finally:
        program.cache_clear()
        checked.cache_clear()
    assert [k for k, (got, limit) in readings.items() if got > limit], readings


def test_float32_choices_are_the_references():
    readings = chip_checks((4, 4), 128, "float32")
    assert readings["choices"][0] == 0.0 and readings["choices_outside_margin"][0] == 0
    assert readings["update"][0] <= 1e-5 and readings["logits"][0] <= 1e-5


# 3, 4 --------------------------------------------------------------------------


def _layer_inputs(held):
    cfg = config(held)
    params, _ = inputs((0, 16), 512)  # all 16 experts' leaves
    u = jax.random.normal(jax.random.key(3), (T, PRESET["hidden_size"]))
    return cfg, params, u, "model.layers.1.mlp."


def test_the_shares_of_one_expert_layer_add_up_to_the_uncut_reference():
    cfg, params, u, pre = _layer_inputs((0, 16))
    m = model()
    whole, _, _ = R.expert_layer(params, pre, u, m)
    shared = R.swiglu(params, pre + "shared_experts.", u)
    routed = 0.0
    for first in range(0, 16, 4):
        share_cfg = config((first, 4))
        out, aux = jax.jit(lambda p, u: M.moe(M._sub(p, pre), u, share_cfg))(params, u)
        routed = routed + (out - M.swiglu(
            M._sub(params, pre + "shared_experts."), u, jnp.float32))
        # the reference cut to the same share computes the same part
        part, _, _ = R.expert_layer(params, pre, u, model((first, 4)))
        np.testing.assert_allclose(out, part, rtol=2e-5, atol=1e-6)
    np.testing.assert_allclose(routed + shared, whole, rtol=2e-5, atol=1e-6)


@pytest.mark.parametrize("every_token_here", [True, False])
def test_dropless_under_imbalance(every_token_here):
    """A bias that sends every token's every choice to the held experts (the
    worst case: T x k pairs on 4 experts), or none at all."""
    held = (4, 4)
    cfg, params, u, pre = _layer_inputs(held)
    bias = np.full(16, 0.0, np.float32)
    bias[4:8] = 10.0 if every_token_here else -10.0
    params = dict(params, **{pre + "gate.e_score_correction_bias": jnp.asarray(bias)})
    out, aux = jax.jit(lambda p, u: M.moe(M._sub(p, pre), u, cfg))(params, u)
    want, _, _ = R.expert_layer(params, pre, u, model(held))
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=1e-6)
    k = PRESET["num_experts_per_tok"]
    assert int(aux["moe_pairs_held"]) == (T * k if every_token_here else 0)
    assert float(aux["moe_tokens_unrouted_share"]) == (0.0 if every_token_here else 1.0)
    # the loop runs the tiles the pairs need, whole tiles an expert, or the
    # floor: 1.5 x the expected 64 pairs in tiles of 8, + 4
    tile = cfg.expert_tile
    assert int(aux["moe_rows_executed"]) == (held[1] * -(-T * k // held[1] // tile) * tile
                                             if every_token_here else (12 + 4) * tile)


def test_the_rows_executed_follow_the_pairs_held():
    """At the published sizes the table of tiles is laid out for the worst
    case (512 + 8 tiles of 128 rows for all 65 536 pairs) and the trip count
    is the real one: the expected 2 048 pairs a layer need 16 to 24 tiles
    (every expert's last tile part empty) and run the floor's 24 + 8; three
    times as many run what they need."""
    cfg = train_lm.model_config(FILE)
    counts = jnp.full((8,), 256, jnp.int32).at[3].add(1)
    order = jnp.arange(8192 * 8, dtype=jnp.int32)
    table, n_tiles = jax.eval_shape(
        lambda: M._tile_table(jnp.ones(8192 * 8), order, counts, 8192, cfg))
    assert table["pair"].shape == (512 + 8, 128) and n_tiles.shape == ()
    assert [int(a) for a in M._tiles_to_run(counts, 8192, cfg)] == [24 + 8, 512 + 8]
    assert int(M._tiles_to_run(3 * counts, 8192, cfg)[0]) == 8 * 6 + 1


# the token-major accumulator (ISSUE 34) -------------------------------------------


def routed_case(case, hidden, tile, dtype="float32", k=4, experts=16, held=(4, 5), t=64):
    """``held_experts``' operands for ``t`` tokens choosing ``k`` of
    ``experts``, experts 4-8 held. ``mixed``: choices at random (experts'
    last tiles part empty: padding rows), but token 0 has all its k pairs
    held and expert 8 no pair at all, and the loop runs its floor of tiles;
    ``past_floor``: every token's every pair is held (by 4-7), more tiles
    than the floor."""
    cfg = M.Config(hidden_size=hidden, moe_intermediate_size=24, n_routed_experts=experts,
                   num_experts_per_tok=k, experts_held=held, expert_tile=tile,
                   compute_dtype=dtype)
    keys = jax.random.split(jax.random.key(hidden + tile), 6)
    if case == "mixed":
        idx = jnp.argsort(jax.random.uniform(keys[0], (t, experts)), axis=1)[:, :k]
        idx = jnp.where(idx == 8, 9, idx).at[0].set(jnp.arange(4, 4 + k))
    else:
        idx = jnp.tile(jnp.arange(4, 4 + k), (t, 1))
    f = cfg.moe_intermediate_size
    p = {}
    for e in range(held[0], held[0] + held[1]):
        ke = jax.random.split(jax.random.fold_in(keys[1], e), 3)
        p[f"experts.{e}.gate_proj.weight"] = 0.3 * jax.random.normal(ke[0], (f, hidden))
        p[f"experts.{e}.up_proj.weight"] = 0.3 * jax.random.normal(ke[1], (f, hidden))
        p[f"experts.{e}.down_proj.weight"] = 0.3 * jax.random.normal(ke[2], (hidden, f))
    u = jax.random.normal(keys[2], (t, hidden))
    w = jax.random.uniform(keys[3], (t, k), minval=0.1)
    g = jax.random.normal(keys[4], (t, hidden))
    return cfg, p, u, idx.astype(jnp.int32), w, g


def routed_and_cotangents(cfg, act, p, u, idx, w, g, names=("gate_proj", "up_proj", "down_proj")):
    """``routed_experts``' output and its five cotangents (u, the three
    stacked weights by leaf, the pairs' weights) for the cotangent ``g``."""
    def fn(p, u, w):
        return M.held_experts(p, u, idx, w, cfg, act, names)[0]

    out, vjp = jax.vjp(fn, p, u, w)
    return out, vjp(g)


def combine_traces():
    snap = pod_registry().snapshot()
    return {p: snap[label_key("st_moe_combine_traces_total", "path", p)] for p in ("pallas", "xla")}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", ["mixed", "past_floor"])
@pytest.mark.parametrize("hidden,tile", [(128, 8), (256, 16)])
@pytest.mark.parametrize("path", ["xla", "pallas"])
def test_the_token_major_accumulator_equals_the_two_dimensional_one_to_the_bit(
        monkeypatch, path, hidden, tile, case, dtype):
    """The same adds in the same order: forward and all five cotangents of
    ``routed_experts`` with the sums carried ``[T, hidden / 128, 128]`` equal,
    element for element, what the ``[T, hidden]`` carry gives: by XLA's
    scatter-add on that carry, and by the kernel of ``ops/moe_pallas.py`` (in
    the interpreter), which skips the padding rows the scatter adds as zeros.
    Which path was traced is counted."""
    monkeypatch.setenv("ST_CODEC", path)
    before = combine_traces()
    cfg, *args = routed_case(case, hidden, tile, dtype)
    idx = args[2]
    counts = jnp.sum((idx[..., None] - 4 == jnp.arange(5)).reshape(-1, 5), axis=0)
    run, most = (int(a) for a in M._tiles_to_run(counts, idx.shape[0], cfg))
    needed = int(jnp.sum(-(-counts // tile)))
    if case == "mixed":
        assert int(counts[4]) == 0 and bool(jnp.all((idx[0] >= 4) & (idx[0] < 8)))
        assert bool(jnp.any(counts % tile != 0)) and needed < run < most
    else:
        assert run == needed and int(jnp.sum(counts)) == idx.size
    assert M._token_major(args[1].shape) == (64, hidden // 128, 128)
    got = jax.jit(partial(routed_and_cotangents, cfg, "silu"))(*args)
    after = combine_traces()
    # the forward loop's and the backward's
    assert {p: after[p] - before[p] for p in after} == {path: 2, "xla" if path == "pallas" else "pallas": 0}
    monkeypatch.setattr(M, "_token_major", lambda shape: tuple(shape))
    want = jax.jit(partial(routed_and_cotangents, cfg, "silu"))(*args)
    assert combine_traces()["xla"] == after["xla"] + 2  # two dimensions: XLA's, whatever the tier
    assert float(jnp.max(jnp.abs(want[0]))) > 0
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("live", [40, 37, 16, 9, 1, 0])
def test_the_combine_kernel_adds_the_live_rows_and_only_them(live):
    """``moe_pallas.combine_rows`` in the interpreter on five blocks of eight
    rows (both slots used again; whole blocks, a part block, empty ones)
    against XLA's scatter-add of the live rows, element for element. The
    rows past the live count name token 0, as the tile table's padding
    does, and leave it alone."""
    t, s, tile = 96, 2, 40
    acc = jax.random.normal(jax.random.key(0), (t, s, 128))
    y = jax.random.normal(jax.random.key(1), (tile, s * 128))
    tokens = jax.random.permutation(jax.random.key(2), t)[:tile].astype(jnp.int32)
    tokens = jnp.where(jnp.arange(tile) < live, tokens, tokens[0])
    assert moe_pallas._block(tile) == 8
    got = jax.jit(moe_pallas.combine_rows)(acc, y, tokens, live)
    want = acc.at[tokens[:live]].add(y[:live].reshape(live, s, 128))
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_the_combine_kernel_runs_where_the_codecs_do_on_token_major_rows(monkeypatch):
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    assert not moe_pallas.takes(f32(64, 2, 128), f32(16, 256))  # a CPU backend: XLA's
    monkeypatch.setenv("ST_CODEC", "pallas")
    assert moe_pallas.takes(f32(64, 2, 128), f32(16, 256))
    assert not moe_pallas.takes(f32(64, 256), f32(16, 256))  # two dimensions
    assert not moe_pallas.takes(f32(64, 2, 128), f32(12, 256))  # no whole blocks of rows
    assert not moe_pallas.takes(f32(64, 2, 128), jax.ShapeDtypeStruct((16, 256), jnp.bfloat16))


@pytest.mark.parametrize("hidden,carried", [(64, "f32[64,64]"), (128, "f32[64,1,128]"),
                                            (256, "f32[64,2,128]"), (192, "f32[64,192]")])
def test_the_accumulators_shape_is_chosen_by_the_operands(hidden, carried):
    """Whole lanes of 128: token-major; else (the rehearsals' 64) the
    two-dimensional carry stays. Forward and backward loop alike."""
    cfg, *args = routed_case("mixed", hidden, 8)
    text = str(jax.make_jaxpr(partial(routed_and_cotangents, cfg, "silu"))(*args))
    loops = [line for line in text.splitlines() if " while[" in line or "= while" in line]
    scatters = [line for line in text.splitlines() if "scatter-add" in line or "scatter_add" in line]
    assert loops and scatters
    other = "f32[64,%d]" % hidden if "128]" in carried else "f32[64,%d,128]" % (hidden // 128)
    assert carried in text
    rows = [line for line in scatters if carried in line.split("=")[0]]
    assert len(rows) == 2  # out's and du's
    assert not [line for line in scatters if other in line.split("=")[0]]


# 5 -----------------------------------------------------------------------------


def test_mla_equals_a_loop_over_heads_and_positions():
    cfg = config()
    params, _ = inputs((0, 16), 512)
    p = {k: np.asarray(v, np.float64) for k, v in M._sub(params, "model.layers.0.self_attn.").items()}
    n = 12
    x = np.asarray(jax.random.normal(jax.random.key(5), (n, cfg.hidden_size)), np.float64)
    got = M.mla(M._sub(params, "model.layers.0.self_attn."), jnp.asarray(x, jnp.float32),
                M.rope_tables(n, cfg.qk_rope_head_dim, cfg.rope_theta),
                config(attn_block=4))
    norm = lambda v, w: v / np.sqrt(np.mean(v * v, -1, keepdims=True) + cfg.rms_norm_eps) * w

    def rot(v, t):  # pairs (2i, 2i+1) as complex numbers
        d = v.shape[-1]
        z = (v[0::2] + 1j * v[1::2]) * np.exp(1j * t * cfg.rope_theta ** (-np.arange(0, d, 2) / d))
        return np.stack([z.real, z.imag], -1).reshape(d)

    nope, rd, vd, h = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim, 4
    q = (norm(x @ p["q_a_proj.weight"].T, p["q_a_layernorm.weight"])
         @ p["q_b_proj.weight"].T).reshape(n, h, nope + rd)
    kv_a = x @ p["kv_a_proj_with_mqa.weight"].T
    kv = (norm(kv_a[:, :cfg.kv_lora_rank], p["kv_a_layernorm.weight"])
          @ p["kv_b_proj.weight"].T).reshape(n, h, nope + vd)
    out = np.zeros((n, h, vd))
    for head in range(h):
        for t in range(n):
            scores = np.array([
                q[t, head, :nope] @ kv[s, head, :nope]
                + rot(q[t, head, nope:], t) @ rot(kv_a[s, cfg.kv_lora_rank:], s)
                for s in range(t + 1)]) / np.sqrt(nope + rd)
            w = np.exp(scores - scores.max())
            out[t, head] = (w / w.sum()) @ kv[: t + 1, head, nope:]
    want = out.reshape(n, h * vd) @ p["o_proj.weight"].T
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)


def test_rope_rotates_interleaved_pairs_as_complex_numbers():
    x = np.asarray(jax.random.normal(jax.random.key(2), (5, 3, 8)))
    theta = PRESET["rope_theta"]
    got = M.rope_interleaved(jnp.asarray(x), *M.rope_tables(5, 8, theta))
    z = x[..., 0::2] + 1j * x[..., 1::2]
    ang = np.arange(5)[:, None] * theta ** (-np.arange(0, 8, 2) / 8)[None, :]
    z = z * np.exp(1j * ang)[:, None, :]
    want = np.stack([z.real, z.imag], -1).reshape(x.shape)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(R.rope(jnp.asarray(x), theta), want, rtol=1e-5, atol=1e-6)


# 6, 8 --------------------------------------------------------------------------

SCOPES = [
    "st.embed", "st.mla", "st.mla.proj", "st.mla.attn", "st.moe", "st.moe.router",
    "st.moe.dispatch", "st.moe.experts", "st.moe.combine", "st.moe.shared", "st.ffn",
    "st.head_loss", "st.mtp",
]


@pytest.fixture(scope="module")
def trained():
    """Three PodTrainer steps on (1,1) and the same three of bare SGD."""
    held, vocab, lr = (4, 4), 128, 0.5
    cfg = config(held, vocab)
    params, _ = inputs(held, vocab)
    loss_fn = lambda p, b: M.loss_fn(p, b, cfg)
    batches = [jax.random.randint(jax.random.key(10 + i), (1, 1, T), 0, vocab) for i in range(3)]
    trainer = PodTrainer(make_mesh(1, 1), params, loss_fn)
    # compiled once: the text for the scopes, and the program the steps run
    trainer._step = trainer.lower(trainer.shard_batch(batches[0]), lr).compile()
    text = trainer._step.as_text()
    got, scales = [], None
    for b in batches:
        losses, scales = trainer.step(trainer.shard_batch(b), lr)
        got.append(float(losses[0]))

    @jax.jit
    def sgd(p, b):
        (loss, _), g = jax.value_and_grad(loss_fn, has_aux=True)(p, b)
        return loss, jax.tree.map(lambda a, d: a - lr * d, p, g)

    bare, p = [], params
    for b in batches:
        loss, p = sgd(p, b[0])
        bare.append(float(loss))
    return dict(trainer=trainer, params=params, got=got, bare=bare, bare_params=p,
                scales=scales, text=text)


def test_podtrainer_steps_equal_bare_sgd_and_the_bias_is_untouched(trained):
    assert trained["got"] == pytest.approx(trained["bare"], rel=1e-5)
    table = trained["trainer"].read(0)
    errs = leaf_errors(table, trained["bare_params"])
    assert max(errs.values()) <= 1e-5, max(errs.items(), key=lambda kv: kv[1])
    names = sorted(trained["params"])
    scales = np.asarray(trained["scales"])[0]
    for i, name in enumerate(names):
        if name.endswith("e_score_correction_bias"):
            assert np.array_equal(np.asarray(table[name]), np.asarray(trained["params"][name]))
            assert scales[i] == 0.0
    assert np.count_nonzero(scales) > len(names) // 2


def test_aux_comes_out_of_the_step_and_feeds_the_gauges(trained):
    from shared_tensor_tpu.utils.profiling import pod_registry

    aux = trained["trainer"].aux
    assert aux["ce_main"].shape == (1,) and aux["moe_pairs_held"].shape == (1, 3)
    assert np.isfinite(float(aux["ce_mtp"][0]))
    assert "choices" not in aux
    snap = pod_registry().snapshot()
    assert snap["st_moe_pairs_held_total"] == float(np.sum(aux["moe_pairs_held"]))
    assert snap["st_moe_load_max_over_mean"] >= 1.0
    assert 0.0 <= snap["st_moe_tokens_unrouted_share"] <= 1.0


@pytest.mark.parametrize("scope", SCOPES)
def test_the_compiled_step_holds_the_scope(trained, scope):
    from shared_tensor_tpu.utils.profiling import scope_map

    scopes = set(scope_map(trained["text"]).values())
    assert any(s.split("/")[-1] == scope for s in scopes), sorted(scopes)
    if scope not in ("st.embed", "st.mtp"):
        assert all(s.startswith("st.grads") for s in scopes if s.endswith(scope))


def test_the_modules_blocks_nest_inside_st_mtp(trained):
    from shared_tensor_tpu.utils.profiling import scope_map

    scopes = set(scope_map(trained["text"]).values())
    assert any("st.mtp/st.mla" in s for s in scopes) and any("st.mtp/st.moe" in s for s in scopes)


def test_replicas_converge_on_four_peers_once_updates_stop():
    held, vocab = (4, 4), 128
    cfg = config(held, vocab, num_hidden_layers=1)  # dense layer + the module's expert layer
    params = M.init_params(jax.random.key(0), cfg)
    trainer = PodTrainer(make_mesh(4, 1), params, lambda p, b: M.loss_fn(p, b, cfg))
    batch = lambda i: trainer.shard_batch(
        jax.random.randint(jax.random.key(20 + i), (4, 1, T), 0, vocab))
    for i in range(2):
        trainer.step(batch(i), 0.5)
    spread = trainer.replica_spread()
    assert spread > 0
    for i in range(12):
        trainer.step(batch(0), 0.0)
    assert trainer.replica_spread() < 0.75 * spread


# 7 -----------------------------------------------------------------------------


def test_published_widths_give_the_checkpoints_209_leaves():
    cfg = train_lm.model_config(FILE)
    assert (cfg.n_routed_experts, cfg.experts_held, cfg.vocab_held) == (256, (0, 8), 16160)
    assert (cfg.num_hidden_layers, cfg.compute_dtype) == (5, "bfloat16")
    shapes = jax.eval_shape(lambda k: M.init_params(k, cfg), jax.random.key(0))
    spec = make_spec(shapes)
    assert spec.num_leaves == 209 and spec.total_n == 491_697_408
    want = {
        "model.embed_tokens.weight": (16160, 2048),
        "lm_head.weight": (16160, 2048),
        "model.norm.weight": (2048,),
        "model.layers.0.mlp.down_proj.weight": (2048, 7168),
        "model.layers.0.self_attn.q_a_proj.weight": (1536, 2048),
        "model.layers.1.self_attn.q_b_proj.weight": (32 * 192, 1536),
        "model.layers.2.self_attn.kv_a_proj_with_mqa.weight": (512 + 64, 2048),
        "model.layers.3.self_attn.kv_b_proj.weight": (32 * 256, 512),
        "model.layers.4.self_attn.o_proj.weight": (2048, 4096),
        "model.layers.4.mlp.gate.weight": (256, 2048),
        "model.layers.4.mlp.gate.e_score_correction_bias": (256,),
        "model.layers.1.mlp.experts.7.up_proj.weight": (768, 2048),
        "model.layers.1.mlp.shared_experts.down_proj.weight": (2048, 768),
        "model.layers.5.eh_proj.weight": (2048, 4096),
        "model.layers.5.enorm.weight": (2048,),
        "model.layers.5.hnorm.weight": (2048,),
        "model.layers.5.shared_head.norm.weight": (2048,),
        "model.layers.5.mlp.experts.0.gate_proj.weight": (768, 2048),
    }
    for name, shape in want.items():
        assert shapes[name].shape == shape, name
    assert "model.layers.1.mlp.experts.8.up_proj.weight" not in shapes
    assert "model.layers.0.mlp.gate.weight" not in shapes  # layer 0 is dense


# --- what a layer's checkpoint keeps, in all three decoders --------------------


def _decoder(name):
    """A decoder at its cell's rehearsal size: the module, its configuration,
    seeded weights and one sequence; and one expert layer of it (layer 1) as
    ``block(p, x, rope)`` with its leaves, input and tables, the name of the
    q projection's weight and the ``[heads, T, width]`` of its q, k and v."""
    from chipbench.jobs import train_decoder
    from shared_tensor_tpu.models import gated_swa_moe, swa_moe

    mod, file, t = {
        "mla_moe": (M, "joyai-llm-flash", 64),
        "swa_moe": (swa_moe, "smallthinker-21b-a3b", 128),
        "gated_swa_moe": (gated_swa_moe, "laguna-s-2.1", 128),
    }[name]
    with open(os.path.join(ROOT, "chipbench", "configs", file + ".json")) as f:
        preset = json.load(f)["rehearsal"]["model"]
    if mod is M:
        cfg = train_lm.model_config(preset)
        block = partial(M.block, cfg=cfg, is_moe=True)
        rope = M.rope_tables(t, cfg.qk_rope_head_dim, cfg.rope_theta)
        h, dq = cfg.num_attention_heads, cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        wq, qkv = "self_attn.q_b_proj.weight", [(h, t, dq), (h, t, dq), (h, t, cfg.v_head_dim)]
    else:
        cfg = train_decoder.model_config(mod, preset)
        if mod is swa_moe:
            block = partial(mod.block, cfg=cfg, window=cfg.window(1))
            rope, h = M.rope_tables(t, cfg.head_dim, cfg.rope_theta), cfg.num_attention_heads
        else:
            block = partial(mod.block, cfg=cfg, layer=1)
            rope, h = mod.layer_rope(cfg, cfg.layer_types[1], t), cfg.heads(1)
        kv = (cfg.num_key_value_heads, t, cfg.head_dim)
        wq, qkv = "self_attn.q_proj.weight", [(h, t, cfg.head_dim), kv, kv]
    params = mod.init_params(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (1, t), 0, cfg.vocab_held)
    x = jax.random.normal(jax.random.key(2), (t, cfg.hidden_size))
    layer = (block, M._sub(params, M._layer(1)), x, rope, wq, qkv)
    return mod, cfg, params, tokens, layer


def _keeping_the_output_alone(fn):
    """The layer checkpoint of before: ``o`` and ``lse``, nothing of q, k, v."""
    return jax.checkpoint(fn, policy=jax.checkpoint_policies.save_only_these_names(M.ATTN_OUT))


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("name", ["mla_moe", "swa_moe", "gated_swa_moe"])
def test_keeping_q_k_v_leaves_every_gradient_as_it_was_to_the_bit(name, dtype, monkeypatch):
    mod, cfg, params, tokens, _ = _decoder(name)
    cfg = dataclasses.replace(cfg, compute_dtype=dtype)
    grad = lambda: jax.jit(jax.value_and_grad(
        lambda p, b: mod.loss_fn(p, b, cfg), has_aux=True))(params, tokens)
    (loss, _), grads = grad()
    layers = []  # every layer of the second program goes through the checkpoint of before
    monkeypatch.setattr(mod, "layer_checkpoint",
                        lambda fn: layers.append(fn) or _keeping_the_output_alone(fn))
    (loss_was, _), grads_was = grad()
    assert len(layers) == getattr(cfg, "n_blocks", cfg.num_hidden_layers)
    assert float(loss) == float(loss_was) and np.isfinite(float(loss))
    for leaf in grads:
        np.testing.assert_array_equal(grads[leaf], grads_was[leaf], err_msg=leaf)
    assert any(float(jnp.max(jnp.abs(g))) > 0 for g in grads.values())


def _product_reads(jaxpr, read) -> int:
    """The products (``dot_general``) of ``jaxpr``, nested programs included,
    that take one of the variables ``read`` or what other operations than a
    product make of them (a cast, a transposition)."""
    read, n = set(read), 0
    for eqn in jaxpr.eqns:
        hit = [i for i, v in enumerate(eqn.invars) if isinstance(v, jex_core.Var) and v in read]
        if not hit:
            continue
        inner = [getattr(j, "jaxpr", j) for j in eqn.params.values()
                 if hasattr(getattr(j, "jaxpr", j), "eqns")]
        if eqn.primitive.name == "dot_general":
            n += 1
        elif inner:
            assert all(len(sub.invars) == len(eqn.invars) for sub in inner), eqn.primitive
            n += sum(_product_reads(sub, [sub.invars[i] for i in hit]) for sub in inner)
        else:
            read.update(eqn.outvars)
    return n


@pytest.mark.parametrize("name", ["mla_moe", "swa_moe", "gated_swa_moe"])
def test_a_layers_checkpoint_keeps_q_k_v_and_their_making_runs_once(name, capsys):
    *_, (block, p, x, rope, wq, qkv) = _decoder(name)

    def kept(wrap):
        jax.ad_checkpoint.print_saved_residuals(lambda p, x: wrap(block)(p, x, rope)[0], p, x)
        return collections.Counter(line.split()[0] for line in capsys.readouterr().out.splitlines())

    # beside o and lse and the layer's arguments: q, k, v, heads first as the
    # backward rule takes them, and nothing else
    more = kept(M.layer_checkpoint) - kept(_keeping_the_output_alone)
    assert more == collections.Counter(f"bf16[{h},{t},{d}]" for h, t, d in qkv)
    assert not kept(_keeping_the_output_alone) - kept(M.layer_checkpoint)

    def reads_of_wq(wrap):
        jaxpr = jax.make_jaxpr(jax.grad(lambda p, x: jnp.sum(wrap(block)(p, x, rope)[0])))(p, x).jaxpr
        return _product_reads(jaxpr, [jaxpr.invars[sorted(p).index(wq)]])

    # the forward product and the cotangent's way back read the weight (a
    # third product makes its gradient); the recomputed layer read it again
    assert (reads_of_wq(M.layer_checkpoint), reads_of_wq(_keeping_the_output_alone)) == (2, 3)
