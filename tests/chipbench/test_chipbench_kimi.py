"""The ``kimi_linear_train_16k`` cell without a chip: its rehearsal prints the
contract's line traced and untraced with every listed reader giving a value,
the analytic counts against a hand count, the three new readers on
observations that have something to read and on a program that has nothing,
and what the configuration's file states about its cut."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import counts_kimi as C  # noqa: E402
from chipbench import harness  # noqa: E402
from chipbench.jobs import train_decoder  # noqa: E402

CELL = "kimi_linear_train_16k"
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
with open(os.path.join(ROOT, "chipbench", "configs", "kimi-linear-48b-a3b.json")) as _f:
    FILE = json.load(_f)
NEW = ["kda_ms_per_step.train", "kda_scan_ms_per_step.train", "kda_scan_roofline"]
LISTED = [
    "dispatch_ms.train", "model_flops_util", "kernel_ms_per_step.train",
    "device_idle_share.train", "mla_ms_per_step.train", "moe_ms_per_step.train",
    "head_loss_ms_per_step.train", "sync_ms_per_step.train", "moe_load_max_over_mean",
    "attn_kernels_roofline", "step_call_ms.train", "step_call_max_ms.train",
    "step_gap_max_over_median.train", "gc_pause_max_ms.train",
] + NEW
SCOPES = {
    "st.grads/st.kda/st.kda.scan": 20.0, "st.grads/st.kda/st.kda.proj": 10.0,
    "st.grads/st.kda/st.kda.conv": 2.0, "st.grads/st.kda/st.kda.gate": 1.0,
    "st.grads/st.kda/st.kda.norm": 1.5, "st.grads/st.kda": 0.5,
    "st.grads/st.mla/st.mla.attn": 3.0, "st.grads/st.moe": 2.0, "st.grads": 0.2,
    "unscoped": 0.1,
}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# by hand at the file's sizes, a token: a KDA mixer's q, k, v, o 4 x 4096 x 2304,
# its decay and gate pairs 2 x (128 x 2304 + 4096 x 128) and b_proj 32 x 2304;
# the MLA mixer's q_proj 6144 x 2304, kv_a 576 x 2304, kv_b 8192 x 512 and
# o_proj 2304 x 4096; the dense FFN 3 x 9216 x 2304; an expert layer's router 256
# x 2304 and 1 shared + 8 x 8 / 256 experts of 3 x 1024 x 2304; the head 20480 x
# 2304
KDA = 4 * 4096 * 2304 + 2 * (128 * 2304 + 4096 * 128) + 32 * 2304
MLA = 6144 * 2304 + 576 * 2304 + 8192 * 512 + 2304 * 4096
DENSE = 3 * 9216 * 2304
EXPERTS = 256 * 2304 + (1 + 8 * 8 / 256) * 3 * 1024 * 2304


def read(name, obs):
    return harness.load_by_path("layer_metrics", name).read(obs)


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    return tmp_path_factory.mktemp("kimi_cache")


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_the_contracts_line(trace, cache, tmp_path):
    """128 tokens, two chunks of 64, bfloat16 on the kernel tier: the KDA
    scan, the latent attention and the expert loop all run. (At 128 tokens a
    held expert can take no token of the first batch, as at seed 2**31 +
    4404: its leaves then have no reference gradient and check (b) of
    ``train_decoder`` divides 0 by 0; at the cell's 16 384 tokens a held
    expert takes about 512 pairs. This seed routes tokens to every held
    expert.)"""
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 4040), "--seconds", "1", "--trace", str(trace),
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
    assert checks["leaves"] == 193
    want = {m["name"] for m in MANIFEST["per_layer" if trace else "end_to_end"]
            if "workloads" not in m or CELL in m["workloads"]}
    if trace:
        assert want == set(LISTED)
        # the CPU has no peak, so no share of one; everything else is read
        assert set(line["metrics"]) == want - {
            "model_flops_util", "attn_kernels_roofline", "kda_scan_roofline"}
        for name in ("kda_ms_per_step.train", "kda_scan_ms_per_step.train",
                     "mla_ms_per_step.train", "moe_ms_per_step.train"):
            assert line["metrics"][name]["value"] > 0
        scopes = checks["scopes_ms_per_step"]
        for scope in ("st.kda/st.kda.proj", "st.kda/st.kda.conv", "st.kda/st.kda.gate",
                      "st.kda/st.kda.scan", "st.kda/st.kda.norm", "st.mla/st.mla.attn",
                      "st.moe/st.moe.shared", "st.ffn"):
            assert any(s.endswith(scope) for s in scopes), sorted(scopes)
        assert scopes.get("unscoped", 0.0) <= 0.05 * sum(scopes.values())
    else:
        assert set(line["metrics"]) == want == {
            "train_samples_per_s", "train_step_p95_ms", "setup_s"}
        assert all(m["value"] > 0 for m in line["metrics"].values())
    assert not [p for p in os.listdir(tmp_path) if p.startswith("chipbench_trace_")]


def test_the_counts_are_the_hand_counts():
    assert (KDA, MLA, DENSE, EXPERTS) == (39_460_864, 29_114_368, 63_700_992, 9_437_184)
    by_hand = 4 * KDA + MLA + DENSE + 4 * EXPERTS + 20480 * 2304
    assert by_hand == 335_593_472 == C.matrix_params_per_token(FILE)
    pairs = 16384 * 16385 // 2
    assert C.attention_pairs(FILE, 16384) == pairs
    assert C.attention_flops(FILE, 16384) == pairs * 32 * 2 * (192 + 128)
    # forward: scores 2 x 192, values 2 x 128; backward: scores, dk, dq 2 x 192
    # each, dp, dv 2 x 128 each
    assert C.attention_kernel_flops(FILE, 16384) == pairs * 32 * (640 + 1152 + 512)
    # a chunk of 64, a head of 128: k with k below the diagonal 64 x 63 x 128, q
    # with k on and below it 64 x 65 x 128, the solve 64 x 63 x 256, W S, Q S and
    # K^T V' 2 x 64 x 128 x 128 each, P V' 64 x 65 x 128; 256 chunks, 32 heads,
    # four mixers
    chunk = 64 * 63 * 128 + 64 * 65 * 128 + 64 * 63 * 256 + 6 * 64 * 128 * 128 + 64 * 65 * 128
    assert chunk == 8_904_704
    assert C.kda_scan_forward_flops(FILE, 16384) == 4 * 256 * 32 * chunk == 291_789_340_672
    assert C.kda_scan_flops(FILE, 16384) == 3 * 291_789_340_672
    # bfloat16 q, k, v [T, 32, 128], float32 g [T, 32, 128] and beta [T, 32] in,
    # bfloat16 o out: forward inputs + o, backward inputs + o's cotangent + the
    # inputs' cotangents
    inputs = 16384 * 32 * (3 * 2 * 128 + 4 * 128 + 4)
    o = 2 * 16384 * 32 * 128
    assert C.kda_scan_bytes(FILE, 16384) == 4 * (3 * inputs + 2 * o) == 9_151_971_328
    total = C.train_flops_per_sequence(FILE, 16384)
    assert total == 6 * by_hand * 16384 + 3 * pairs * 32 * 640 + 3 * 291_789_340_672
    assert 42.11e12 < total < 42.12e12
    # a length that is no multiple of the chunk runs a last, padded chunk
    assert C.kda_scan_forward_flops(FILE, 16384 + 1) == 4 * 257 * 32 * chunk
    # the rehearsal's group is counted by the same functions: 4 heads of 16
    small = FILE["rehearsal"]["model"]
    assert C.kda_scan_forward_flops(small, 128) == 4 * 2 * 4 * (
        64 * 63 * 16 + 64 * 65 * 16 + 64 * 63 * 32 + 6 * 64 * 16 * 16 + 64 * 65 * 16)


def test_the_new_readers_read_their_scopes():
    obs = {"scopes": SCOPES}
    assert read("kda_ms_per_step.train", obs) == pytest.approx(35.0)
    assert read("kda_scan_ms_per_step.train", obs) == pytest.approx(20.0)
    assert read("mla_ms_per_step.train", obs) == pytest.approx(3.0)


def test_the_scans_roofline_is_its_bound_over_its_time():
    """The HBM bound at the file's sizes (9.15 GB at 819 GB/s, 11.17 ms, over
    the FLOPs' 4.44 ms) over 20 ms of ``st.kda.scan``; a time at the bound
    reads 100 %, not more; the cell is found by the FLOPs a sample the job
    reports, at the timed sizes or the rehearsal's."""
    obs = {"scopes": SCOPES, "peaks": PEAKS,
           "counts": {"train_flops_per_sample": C.train_flops_per_sequence(FILE, 16384)}}
    bound = max(C.kda_scan_flops(FILE, 16384) / 197e12, C.kda_scan_bytes(FILE, 16384) / 819e9)
    assert bound == pytest.approx(11.1746e-3, rel=1e-4)
    assert read("kda_scan_roofline", obs) == pytest.approx(100.0 * bound / 20e-3)
    at_bound = dict(obs, scopes={"st.grads/st.kda/st.kda.scan": 1e3 * bound})
    assert read("kda_scan_roofline", at_bound) == pytest.approx(100.0)
    small = FILE["rehearsal"]["model"]
    obs["counts"]["train_flops_per_sample"] = C.train_flops_per_sequence(small, 128)
    assert read("kda_scan_roofline", obs) == pytest.approx(
        100.0 * C.kda_scan_bytes(small, 128) / 819e9 / 20e-3)


def test_the_new_readers_find_nothing_where_the_program_has_nothing():
    """The parent program, or a cell of another model: no such scope, or no
    configuration with a KDA scan, so no value and no error."""
    other = {"st.grads/st.ssm/st.ssm.scan": 1.0, "st.grads/st.moe": 0.5, "unscoped": 0.1}
    for obs in ({}, {"scopes": None}, {"scopes": {}}, {"scopes": other, "peaks": PEAKS},
                {"scopes": SCOPES, "peaks": PEAKS, "counts": {"train_flops_per_sample": 1.0}},
                {"scopes": SCOPES, "peaks": None, "counts": {"train_flops_per_sample": 1.0}}):
        assert read("kda_scan_roofline", obs) is None
        if obs.get("scopes") is not SCOPES:
            for name in NEW:
                assert read(name, obs) is None


def test_the_file_states_the_cut():
    reduced = ["num_hidden_layers", "num_experts", "vocab_size"]
    assert FILE["reduced"] == reduced and set(FILE["deployment"]) >= set(reduced)
    assert FILE["published"] == {"num_hidden_layers": 27, "num_experts": 256, "vocab_size": 163840}
    assert [FILE[k] for k in reduced] == [5, 8, 20480] and FILE["experts_held"] == [0, 8]
    assert "32 chips share each expert layer" in FILE["deployment"]["num_experts"]
    assert "602 433 408 in 193 leaves" in FILE["deployment"]["parameters"]
    # the widths as published
    lin = FILE["linear_attn_config"]
    assert (lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]) == (32, 128, 4)
    assert lin["kda_layers"][:4] == [1, 2, 3, 5] and lin["full_attn_layers"][0] == 4
    assert (FILE["hidden_size"], FILE["num_attention_heads"], FILE["kv_lora_rank"],
            FILE["qk_nope_head_dim"], FILE["qk_rope_head_dim"], FILE["v_head_dim"],
            FILE["intermediate_size"], FILE["moe_intermediate_size"],
            FILE["num_experts_per_token"], FILE["num_shared_experts"],
            FILE["routed_scaling_factor"], FILE["q_lora_rank"], FILE["mla_use_nope"]) == (
                2304, 32, 512, 128, 64, 128, 9216, 1024, 8, 1, 2.446, None, True)
    for key in ("tensor_names", "kda", "g_b_bias", "attention", "router", "experts",
                "initialisation", "balance", "packing", "conv_layout"):
        assert len(FILE["assumed"][key]) > 40, key
    for key in ("ce_tol", "logits_rel_tol", "update_rel_tol", "update_rel_median_tol",
                "sgd_loss_tol"):
        assert FILE["checks"][key] > 0 and len(FILE["checks"][key + "_why"]) > 40
    assert 0.9 < FILE["checks"]["choices_agree_min"] <= 1 and len(FILE["checks"]["choices_why"]) > 40
    assert len(FILE["checks"]["loss_fall_why"]) > 40
    assert len(FILE["expert_tile_why"]) > 40 and len(FILE["learning_rate_why"]) > 40
    assert "TBD" not in json.dumps(FILE) and "PROVISIONAL" not in json.dumps(FILE)
    small = FILE["rehearsal"]["model"]
    assert small["num_hidden_layers"] == 5 and small["published"]["num_experts"] == 32


def test_the_model_config_takes_the_published_counts_and_the_held_shares():
    from shared_tensor_tpu.models import kimi_linear

    cfg = train_decoder.model_config(kimi_linear, FILE)
    assert (cfg.num_hidden_layers, cfg.num_experts, cfg.vocab_size) == (5, 256, 163840)
    assert (cfg.experts_held, cfg.vocab_held, cfg.expert_tile) == ((0, 8), 20480, 256)
    assert (cfg.num_experts_per_tok, cfg.expert_layers, cfg.plan) == (8, 4, "KKKAK")
    small = train_decoder.model_config(kimi_linear, FILE["rehearsal"]["model"])
    assert (small.kda_heads, small.kda_head_dim, small.plan) == (4, 16, "KKKAK")
