"""The ``smallthinker_train_16k`` cell without a chip: its rehearsal prints
the contract's line traced and untraced, the analytic counts against a hand
count, the new readers on observations that have something to read and on a
program that has nothing, the job's reading of the attention kernels from a
small trace, and what the configuration's file states about its cut."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import counts_swa, harness  # noqa: E402
from chipbench.jobs import train_decoder  # noqa: E402

CELL = "smallthinker_train_16k"
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
with open(os.path.join(ROOT, "chipbench", "configs", "smallthinker-21b-a3b.json")) as _f:
    FILE = json.load(_f)
NEW_READERS = [
    "attn_ms_per_step.train", "attn_window_ms_per_step.train", "attn_full_ms_per_step.train",
    "attn_kernels_roofline",
]
SCOPES = {
    "st.grads/st.attn/st.attn.window": 6.0, "st.grads/st.attn/st.attn.full": 2.0,
    "st.grads/st.attn/st.attn.proj": 1.5, "st.grads/st.attn": 0.5,
    "st.grads/st.moe/st.moe.experts": 11.0, "st.grads/st.mla/st.mla.attn": 100.0,
    "st.grads": 0.2, "unscoped": 0.1,
}


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    return tmp_path_factory.mktemp("smallthinker_cache")


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_the_contracts_line(trace, cache, tmp_path):
    """128 tokens, a window of 48, 4 query heads on 2 K/V heads, bfloat16 on
    the kernel tier: the band's kernels run, interpreted."""
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 1233), "--seconds", "1", "--trace", str(trace),
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
    assert len(checks["aux"]["moe_pairs_held"]) == 4  # every layer is an expert layer
    assert "ce_mtp" not in checks["aux"]
    want = {m["name"] for m in MANIFEST["per_layer" if trace else "end_to_end"]
            if "workloads" not in m or CELL in m["workloads"]}
    if trace:
        # the CPU has no peak, so no share of one; everything else is read
        assert set(line["metrics"]) == want - {"model_flops_util", "attn_kernels_roofline"}
        assert set(NEW_READERS) - {"attn_kernels_roofline"} <= set(line["metrics"])
        assert not {"mla_ms_per_step.train", "mtp_ms_per_step.train"} & set(line["metrics"])
        scopes = checks["scopes_ms_per_step"]
        for kind in ("window", "full"):
            assert any(s.endswith("st.attn/st.attn." + kind) for s in scopes), sorted(scopes)
        assert scopes.get("unscoped", 0.0) <= 0.05 * sum(scopes.values())
    else:
        assert set(line["metrics"]) == want == {
            "train_samples_per_s", "train_step_p95_ms", "setup_s"}
        assert all(m["value"] > 0 for m in line["metrics"].values())
    assert not [p for p in os.listdir(tmp_path) if p.startswith("chipbench_trace_")]


def test_the_counts_are_the_hand_counts():
    """By hand, for the file's sizes: a layer's attention 2560x3584 (q) + 2 x
    2560x512 (k, v) + 3584x2560 (o) = 20 971 520; its router 2560x64 = 163
    840; 6 x 16/64 = 1.5 experts of 3x2560x768 = 5 898 240 a token; the head
    37984x2560. Pairs a head at 16 384: the triangle 16384x16385/2, the band
    4096x4097/2 + 12288x4096."""
    layer = 20_971_520 + 163_840 + 1.5 * 5_898_240
    by_hand = 4 * layer + 37_984 * 2_560
    assert by_hand == 217_169_920
    assert counts_swa.matrix_params_per_token(FILE) == by_hand
    full, band = 16384 * 16385 // 2, 4096 * 4097 // 2 + 12288 * 4096
    assert (full, band) == (134_225_920, 58_722_304)
    assert counts_swa.attention_pairs(FILE, 16384) == full + 3 * band
    assert counts_swa.attention_flops(FILE, 16384) == (full + 3 * band) * 28 * 512
    assert counts_swa.attention_kernel_flops(FILE, 16384) == (full + 3 * band) * 28 * (512 + 1280)
    assert round(full * 28 * 512 / 1e9) == 1924 and round(band * 28 * 512 / 1e9) == 842
    total = counts_swa.train_flops_per_sequence(FILE, 16384)
    assert total == 6 * 217_169_920 * 16384 + 3 * (full + 3 * band) * 28 * 512
    assert 34.69e12 < total < 34.71e12
    # a sequence shorter than the window: every layer is the triangle
    assert counts_swa.attention_pairs(FILE, 1024) == 4 * (1024 * 1025 // 2)


def test_the_new_readers_read_their_scopes_and_the_kernels_share():
    obs = {
        "scopes": SCOPES, "peaks": {"bf16_flops_per_s": 200e12},
        "attn_kernels": {"fwd": 0.05, "bwd": 0.10, "calls": 8.0},
        "counts": {"attn_kernel_flops_per_step": 15e12},
    }
    read = lambda name: harness.load_by_path("layer_metrics", name).read(obs)
    assert read("attn_ms_per_step.train") == pytest.approx(10.0)  # st.mla.attn is not st.attn's
    assert read("attn_window_ms_per_step.train") == pytest.approx(6.0)
    assert read("attn_full_ms_per_step.train") == pytest.approx(2.0)
    assert read("attn_kernels_roofline") == pytest.approx(50.0)  # 15e12 / 200e12 / 0.15 s


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_finds_nothing_where_the_program_has_nothing(name):
    """The parent program, or a cell of another model: no such scopes, no
    such kernels, so no value and no error."""
    reader = harness.load_by_path("layer_metrics", name)
    for obs in ({}, {"scopes": None, "attn_kernels": None, "counts": {}},
                {"scopes": {}, "peaks": {"bf16_flops_per_s": 197e12}},
                {"scopes": {"st.grads/st.mla/st.mla.attn": 1.0, "unscoped": 0.1},
                 "peaks": {"bf16_flops_per_s": 197e12}, "attn_kernels": None,
                 "counts": {"train_flops_per_sample": 1.0}}):
        assert reader.read(obs) is None


def test_the_job_reads_the_attention_kernels_by_name_from_the_whole_window():
    """Every ``st_attn_fwd`` / ``st_attn_bwd`` event inside the window
    counts, however short (the ten longest operations are not asked), and
    nothing else does."""
    ev = lambda label, start, dur: [label, float(start), float(dur), "custom-call:tpu_custom_call"]
    trace = {
        "host": [["chipbench:window", 1000.0, 9000.0]],
        "devices": {"0": [
            ev("st_attn_fwd.3 = bf16[28,16384,128] custom-call", 1000, 200),
            ev("st_attn_bwd.1 = (bf16[28,16384,128]) custom-call", 2000, 600),
            ev("st_attn_fwd = bf16[28,16384,128] custom-call", 3000, 100),
            ev("st_quantize_rows.1 = u32[5129176,4] custom-call", 4000, 5000),
            ev("st_attn_fwd.9 = bf16[28,16384,128] custom-call", 20000, 100),  # after the window
            ["fusion.7 = f32[16384,2560] fusion", 5000.0, 300.0, "fusion"],
        ]},
    }
    got = train_decoder.attention_kernel_seconds(trace, steps=2)
    assert got == {"fwd": pytest.approx(150e-9), "bwd": pytest.approx(300e-9), "calls": 1.5}
    assert train_decoder.attention_kernel_seconds({"devices": {}, "host": []}, 2) is None
    assert train_decoder.attention_kernel_seconds(
        dict(trace, devices={"0": trace["devices"]["0"][3:]}), 2) is None
    assert train_decoder.attention_kernel_seconds(None, 2) is None


def test_the_file_states_the_cut():
    assert FILE["reduced"] == ["num_hidden_layers", "moe_num_primary_experts", "vocab_size"]
    assert FILE["published"] == {
        "num_hidden_layers": 52, "moe_num_primary_experts": 64, "vocab_size": 151936}
    assert (FILE["num_hidden_layers"], FILE["moe_num_primary_experts"], FILE["vocab_size"]) == (
        4, 16, 37984)
    assert FILE["experts_held"] == [0, 16] and set(FILE["deployment"]) >= set(FILE["reduced"])
    assert "4 chips share each layer" in FILE["deployment"]["moe_num_primary_experts"]
    # the widths as published, the layouts whole
    assert (FILE["hidden_size"], FILE["num_attention_heads"], FILE["num_key_value_heads"],
            FILE["head_dim"], FILE["moe_ffn_hidden_size"],
            FILE["moe_num_active_primary_experts"], FILE["sliding_window_size"]) == (
                2560, 28, 4, 128, 768, 6, 4096)
    assert FILE["rope_layout"] == FILE["sliding_window_layout"] == [0, 1, 1, 1] * 13
    for key in ("rope", "window", "router_input", "router_weights", "secondary_experts",
                "sparse_reglu", "balance", "packing", "tensor_names", "initialisation"):
        assert len(FILE["assumed"][key]) > 40, key
    # every tolerance is written with its reason
    for key in ("ce_tol", "logits_rel_tol", "update_rel_tol", "update_rel_median_tol",
                "sgd_loss_tol"):
        assert FILE["checks"][key] > 0 and len(FILE["checks"][key + "_why"]) > 40
    assert 0.9 < FILE["checks"]["choices_agree_min"] <= 1 and len(FILE["checks"]["choices_why"]) > 40
    assert len(FILE["expert_tile_why"]) > 40 and len(FILE["learning_rate_why"]) > 40
    for key in ("model_module", "reference_module", "counts_module"):
        assert FILE[key]
    assert FILE["rehearsal"]["model"]["sliding_window_size"] < 128  # the rehearsal's length


def test_the_model_config_takes_the_published_router_and_vocabulary():
    from shared_tensor_tpu.models import swa_moe

    cfg = train_decoder.model_config(swa_moe, FILE)
    assert (cfg.num_hidden_layers, cfg.moe_num_primary_experts, cfg.vocab_size) == (4, 64, 151936)
    assert (cfg.experts_held, cfg.vocab_held, cfg.expert_tile) == (
        (0, 16), 37984, FILE["expert_tile"])
    small = train_decoder.model_config(swa_moe, FILE["rehearsal"]["model"])
    assert (small.moe_num_primary_experts, small.experts_held, small.vocab_held) == (16, (4, 4), 128)
