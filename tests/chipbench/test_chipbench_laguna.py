"""The ``laguna_s_train_8k`` cell without a chip: its rehearsal prints the
contract's line traced and untraced with every listed reader giving a value,
the analytic counts against a hand count, the new reader on observations that
have something to read and on a program that has nothing, and what the
configuration's file states about its cut."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import counts_laguna, harness  # noqa: E402
from chipbench.jobs import train_decoder  # noqa: E402

CELL = "laguna_s_train_8k"
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
with open(os.path.join(ROOT, "chipbench", "configs", "laguna-s-2.1.json")) as _f:
    FILE = json.load(_f)
LISTED = [
    "dispatch_ms.train", "model_flops_util", "kernel_ms_per_step.train",
    "device_idle_share.train", "moe_ms_per_step.train", "head_loss_ms_per_step.train",
    "sync_ms_per_step.train", "moe_load_max_over_mean", "attn_ms_per_step.train",
    "attn_window_ms_per_step.train", "attn_full_ms_per_step.train", "attn_kernels_roofline",
    "attn_gate_ms_per_step.train",
]
SCOPES = {
    "st.grads/st.attn/st.attn.window": 6.0, "st.grads/st.attn/st.attn.full": 2.0,
    "st.grads/st.attn/st.attn.proj": 1.5, "st.grads/st.attn/st.attn.gate": 0.75,
    "st.grads/st.attn": 0.5, "st.grads/st.moe/st.moe.shared": 3.0, "st.grads/st.ffn": 4.0,
    "st.grads": 0.2, "unscoped": 0.1,
}


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    return tmp_path_factory.mktemp("laguna_cache")


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_the_contracts_line(trace, cache, tmp_path):
    """128 tokens, a window of 48 (narrower than the kernels' one tile of
    128), 4 and 6 query heads on 2 K/V heads, bfloat16 on the kernel tier:
    the band's kernels run, interpreted."""
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 1235), "--seconds", "1", "--trace", str(trace),
         "--rehearse-cpu"],
        capture_output=True, text=True, timeout=900, cwd=ROOT,
        env=dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(cache), TMPDIR=str(tmp_path),
                 BENCH_RUN="ignored"),
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    checks = line["checks"]
    assert checks["compiled_inside_window"] == 0
    assert checks["reference_forward"][0]["choices_outside_margin"] == 0
    assert len(checks["aux"]["moe_pairs_held"]) == 4  # layer 0 is dense: four expert layers
    assert "ce_mtp" not in checks["aux"]
    want = {m["name"] for m in MANIFEST["per_layer" if trace else "end_to_end"]
            if "workloads" not in m or CELL in m["workloads"]}
    if trace:
        assert want == set(LISTED)
        # the CPU has no peak, so no share of one; everything else is read
        assert set(line["metrics"]) == want - {"model_flops_util", "attn_kernels_roofline"}
        assert not {"mla_ms_per_step.train", "mtp_ms_per_step.train"} & set(line["metrics"])
        assert line["metrics"]["attn_gate_ms_per_step.train"]["value"] > 0
        scopes = checks["scopes_ms_per_step"]
        for kind in ("window", "full", "gate", "proj"):
            assert any(s.endswith("st.attn/st.attn." + kind) for s in scopes), sorted(scopes)
        for scope in ("st.ffn", "st.moe/st.moe.shared", "st.moe/st.moe.router"):
            assert any(s.endswith(scope) for s in scopes), sorted(scopes)
        assert scopes.get("unscoped", 0.0) <= 0.05 * sum(scopes.values())
    else:
        assert set(line["metrics"]) == want == {
            "train_samples_per_s", "train_step_p95_ms", "setup_s"}
        assert all(m["value"] > 0 for m in line["metrics"].values())
    assert not [p for p in os.listdir(tmp_path) if p.startswith("chipbench_trace_")]


def test_the_counts_are_the_hand_counts():
    """By hand, for the file's sizes: a full layer's attention 3072x6144 (q) +
    2 x 3072x1024 (k, v) + 6144x3072 (o) + 48x3072 (gate) = 44 187 648, a
    sliding layer's (72 heads) 63 135 744; layer 0's MLP 3 x 3072x12288; an
    expert layer's router 3072x256, shared expert 3 x 3072x1024 and 10 x
    8/256 = 0.3125 experts of 3 x 3072x1024 a token; the head 12544x3072.
    Pairs a head at 8 192: the triangle 8192x8193/2, the band 512x513/2 +
    7680x512."""
    full_attn, window_attn, expert = 44_187_648, 63_135_744, 9_437_184
    assert full_attn == 2 * 3072 * 6144 + 2 * 3072 * 1024 + 48 * 3072
    assert window_attn == 2 * 3072 * 9216 + 2 * 3072 * 1024 + 72 * 3072
    sparse = 786_432 + expert + 0.3125 * expert
    by_hand = 2 * full_attn + 3 * window_attn + 113_246_208 + 4 * sparse + 12_544 * 3_072
    assert by_hand == 482_254_848
    assert counts_laguna.matrix_params_per_token(FILE) == by_hand
    full, band = 8192 * 8193 // 2, 512 * 513 // 2 + 7680 * 512
    assert (full, band) == (33_558_528, 4_063_488)
    assert round(1000 * band / full) == 121  # 12.1 % of the triangle
    head_pairs = 2 * 48 * full + 3 * 72 * band
    assert counts_laguna.head_pairs(FILE, 8192) == head_pairs
    assert counts_laguna.attention_flops(FILE, 8192) == head_pairs * 512
    assert counts_laguna.attention_kernel_flops(FILE, 8192) == head_pairs * (512 + 1280)
    assert round(full * 48 * 512 / 1e9) == 825 and round(band * 72 * 512 / 1e9) == 150
    assert 2.098e12 < counts_laguna.attention_flops(FILE, 8192) < 2.100e12
    assert 7.345e12 < counts_laguna.attention_kernel_flops(FILE, 8192) < 7.347e12
    total = counts_laguna.train_flops_per_sequence(FILE, 8192)
    assert total == 6 * 482_254_848 * 8192 + 3 * head_pairs * 512
    assert 29.99e12 < total < 30.01e12
    # a sequence shorter than the window: every layer is the triangle, at its own heads
    assert counts_laguna.head_pairs(FILE, 256) == (2 * 48 + 3 * 72) * (256 * 257 // 2)
    # the rehearsal's group is counted by the same functions
    small = FILE["rehearsal"]["model"]
    assert counts_laguna.head_pairs(small, 128) == 2 * 4 * (128 * 129 // 2) + 3 * 6 * (
        48 * 49 // 2 + 80 * 48)


def test_the_new_reader_reads_the_gates_scope():
    obs = {"scopes": SCOPES}
    read = lambda name: harness.load_by_path("layer_metrics", name).read(obs)
    assert read("attn_gate_ms_per_step.train") == pytest.approx(0.75)
    assert read("attn_ms_per_step.train") == pytest.approx(10.75)  # the gate is st.attn's
    assert read("attn_window_ms_per_step.train") == pytest.approx(6.0)
    assert read("moe_ms_per_step.train") == pytest.approx(3.0)  # st.ffn is not st.moe's


def test_the_new_reader_finds_nothing_where_the_program_has_nothing():
    """The parent program, or a cell of another model: no such scope, so no
    value and no error."""
    reader = harness.load_by_path("layer_metrics", "attn_gate_ms_per_step.train")
    for obs in ({}, {"scopes": None}, {"scopes": {}},
                {"scopes": {"st.grads/st.attn/st.attn.proj": 1.0, "st.grads/st.attn": 0.5,
                            "unscoped": 0.1}}):
        assert reader.read(obs) is None


def test_the_file_states_the_cut():
    assert FILE["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert FILE["published"] == {"num_hidden_layers": 48, "num_experts": 256, "vocab_size": 100352}
    assert (FILE["num_hidden_layers"], FILE["num_experts"], FILE["vocab_size"]) == (5, 8, 12544)
    assert FILE["experts_held"] == [0, 8] and set(FILE["deployment"]) >= set(FILE["reduced"])
    assert "32 chips share each layer" in FILE["deployment"]["num_experts"]
    assert "811 017 216 in 153 leaves" in FILE["deployment"]["parameters"]
    # the widths as published, the lists and the nested group whole
    assert (FILE["hidden_size"], FILE["intermediate_size"], FILE["num_attention_heads"],
            FILE["num_key_value_heads"], FILE["head_dim"], FILE["moe_intermediate_size"],
            FILE["shared_expert_intermediate_size"], FILE["num_experts_per_tok"],
            FILE["sliding_window"], FILE["max_position_embeddings"]) == (
                3072, 12288, 48, 8, 128, 1024, 1024, 10, 512, 1048576)
    period = ["full_attention"] + ["sliding_attention"] * 3
    assert FILE["layer_types"] == period * 12
    assert FILE["num_attention_heads_per_layer"] == [48, 72, 72, 72] * 12
    assert FILE["mlp_layer_types"] == ["dense"] + ["sparse"] * 47
    assert FILE["gating"] == "per-head" and FILE["gating_types"] == ["per_head"] * 48
    rope = FILE["rope_parameters"]
    assert rope["full_attention"] == {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
        "original_max_position_embeddings": 8192, "beta_slow": 1, "beta_fast": 32,
        "attention_factor": 1.4852030263919618, "partial_rotary_factor": 0.5}
    assert rope["sliding_attention"] == {
        "rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1}
    for key in ("gate", "router", "shared_expert", "qk_norm", "rope", "yarn", "window",
                "balance", "packing", "tensor_names", "initialisation"):
        assert len(FILE["assumed"][key]) > 40, key
    # every tolerance is written with its reason
    for key in ("ce_tol", "logits_rel_tol", "update_rel_tol", "update_rel_median_tol",
                "sgd_loss_tol"):
        assert FILE["checks"][key] > 0 and len(FILE["checks"][key + "_why"]) > 40
        assert "TBD" not in FILE["checks"][key + "_why"]
    assert 0.9 < FILE["checks"]["choices_agree_min"] <= 1 and len(FILE["checks"]["choices_why"]) > 40
    assert len(FILE["expert_tile_why"]) > 40 and len(FILE["learning_rate_why"]) > 40
    assert "TBD" not in json.dumps(FILE) and "TO BE SET" not in json.dumps(FILE)
    for key in ("model_module", "reference_module", "counts_module"):
        assert FILE[key]
    small = FILE["rehearsal"]["model"]
    assert small["sliding_window"] < 128  # the rehearsal's length, and its kernels' tile
    assert small["num_attention_heads_per_layer"] == [4, 6, 6, 6, 4]


def test_the_model_config_takes_the_published_router_and_vocabulary():
    from shared_tensor_tpu.models import gated_swa_moe

    cfg = train_decoder.model_config(gated_swa_moe, FILE)
    assert (cfg.num_hidden_layers, cfg.num_experts, cfg.vocab_size) == (5, 256, 100352)
    assert (cfg.experts_held, cfg.vocab_held, cfg.expert_tile) == (
        (0, 8), 12544, FILE["expert_tile"])
    assert (cfg.num_experts_per_tok, cfg.expert_layers) == (10, 4)  # what the job reads of it
    small = train_decoder.model_config(gated_swa_moe, FILE["rehearsal"]["model"])
    assert (small.num_experts, small.experts_held, small.vocab_held) == (16, (4, 4), 128)
    assert small.rope("full_attention")["factor"] == 4
