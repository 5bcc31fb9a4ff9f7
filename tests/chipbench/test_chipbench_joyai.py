"""The ``joyai_flash_train_8k`` cell without a chip: its rehearsal prints the
contract's line traced and untraced, the analytic FLOP count against a hand
count, the plain reference against itself on shares of the experts, the
by-scope reduction on a small recording, and the new readers on a program
that has nothing for them to read."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import counts_lm, harness, scope_reduce  # noqa: E402

CELL = "joyai_flash_train_8k"
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
with open(os.path.join(ROOT, "chipbench", "configs", "joyai-llm-flash.json")) as _f:
    FILE = json.load(_f)
NEW_READERS = [
    "mla_ms_per_step.train", "moe_ms_per_step.train", "head_loss_ms_per_step.train",
    "mtp_ms_per_step.train", "sync_ms_per_step.train", "moe_load_max_over_mean",
]


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    return tmp_path_factory.mktemp("joyai_cache")


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_the_contracts_line(trace, cache, tmp_path):
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 977), "--seconds", "1", "--trace", str(trace),
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
    assert checks["reference_update"]["bias_leaves_bit_identical"] is True
    assert checks["reference_forward"][0]["choices_outside_margin"] == 0
    assert len(checks["aux"]["moe_pairs_held"]) == 3  # two expert layers and the module's
    want = {m["name"] for m in MANIFEST["per_layer" if trace else "end_to_end"]
            if "workloads" not in m or CELL in m["workloads"]}
    if trace:
        # the CPU has no peak, so no utilization; everything else is read
        assert set(line["metrics"]) == want - {"model_flops_util"}
        assert set(NEW_READERS) <= set(line["metrics"])
        scopes = checks["scopes_ms_per_step"]
        assert sum(scopes.values()) == pytest.approx(
            1e3 * line["device"]["busy_s"] / 3, rel=0.35)  # self time of 3 traced steps
        assert scopes.get("unscoped", 0.0) <= 0.05 * sum(scopes.values())
    else:
        assert set(line["metrics"]) == want == {
            "train_samples_per_s", "train_step_p95_ms", "setup_s"}
        assert all(m["value"] > 0 for m in line["metrics"].values())
    assert not [p for p in os.listdir(tmp_path) if p.startswith("chipbench_trace_")]


def test_the_flop_count_is_the_hand_count():
    """By hand, for the file's sizes: per block the MLA projections are
    2048x1536 + 1536x6144 + 2048x576 + 512x8192 + 4096x2048 = 26 345 472; the
    dense SwiGLU 3x2048x7168 = 44 040 192; an expert layer's router 2048x256,
    shared expert 3x2048x768 and 8x8/256 = 0.25 routed experts = 6 422 528;
    the head 16160x2048 = 33 095 680; the module adds eh_proj 4096x2048, one
    block, one expert layer and the head again."""
    block, dense, moe, head = 26_345_472, 44_040_192, 6_422_528, 33_095_680
    by_hand = 5 * block + dense + 4 * moe + head + (8_388_608 + block + moe + head)
    assert by_hand == 308_805_632
    assert counts_lm.matrix_params_per_token(FILE) == by_hand
    attention = 6 * (8192 * 8192 // 2) * 2 * 32 * (192 + 128)
    assert counts_lm.attention_flops(FILE, 8192) == attention
    total = counts_lm.train_flops_per_sequence(FILE, 8192)
    assert total == 6 * by_hand * 8192 + 3 * attention
    assert 27.4e12 < total < 27.7e12


def test_the_reference_adds_up_over_its_own_shares():
    import jax
    import numpy as np

    from chipbench.jobs import train_lm
    from chipbench.reference import joyai_ref
    from shared_tensor_tpu.models import mla_moe

    preset = FILE["rehearsal"]["model"]
    whole = dict(preset, experts_held=[0, 16], n_routed_experts=16)
    params = mla_moe.init_params(jax.random.key(3), train_lm.model_config(whole))
    u = jax.random.normal(jax.random.key(4), (32, preset["hidden_size"]))
    pre = "model.layers.1.mlp."
    with jax.default_matmul_precision("highest"):
        full, own, _ = joyai_ref.expert_layer(params, pre, u, whole)
        shared = joyai_ref.swiglu(params, pre + "shared_experts.", u)
        routed = sum(
            joyai_ref.expert_layer(params, pre, u, dict(whole, experts_held=[first, 4]))[0]
            - shared for first in range(0, 16, 4))
    np.testing.assert_allclose(routed + shared, full, rtol=2e-5, atol=1e-6)
    assert own.shape == (32, preset["num_experts_per_tok"])


def test_scope_reduction_on_the_small_recording():
    with open(os.path.join(ROOT, "chipbench", "testdata", "scope_reduce_small.json")) as f:
        rec = json.load(f)
    got = scope_reduce.by_scope(rec["trace"], rec["text"], rec["steps"])
    assert got == pytest.approx(rec["expect_ms_per_step"])
    obs = {"scopes": got}
    read = lambda name: harness.load_by_path("layer_metrics", name).read(obs)
    assert read("mla_ms_per_step.train") == pytest.approx(500e-6)
    assert read("moe_ms_per_step.train") == pytest.approx(300e-6)
    assert read("head_loss_ms_per_step.train") == pytest.approx(400e-6)
    assert read("mtp_ms_per_step.train") == pytest.approx(400e-6)
    assert read("sync_ms_per_step.train") == pytest.approx(600e-6)


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_finds_nothing_where_the_program_has_nothing(name):
    """The parent program, or any cell that is not a language model's: no
    scopes and no aux in the observations, so no value and no error."""
    reader = harness.load_by_path("layer_metrics", name)
    for obs in ({}, {"scopes": None, "aux": None}, {"scopes": {}, "aux": {}},
                {"scopes": {"st.grads": 1.0, "unscoped": 0.1}, "aux": {"ce_main": [1.0]}}):
        if name == "sync_ms_per_step.train" and obs.get("scopes"):
            continue
        assert reader.read(obs) is None
    assert scope_reduce.by_scope(None, "", 3) is None
    assert scope_reduce.by_scope({"devices": {}}, "", 3) is None


def test_the_file_states_the_cut():
    assert FILE["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert FILE["published"] == {
        "num_hidden_layers": 40, "n_routed_experts": 256, "vocab_size": 129280}
    assert (FILE["num_hidden_layers"], FILE["n_routed_experts"], FILE["vocab_size"]) == (
        5, 8, 16160)
    assert FILE["experts_held"] == [0, 8] and set(FILE["deployment"]) >= set(FILE["reduced"])
    for key in ("mtp_loss_weight", "eh_proj_halves", "balance", "packing", "initialisation"):
        assert key in FILE["assumed"]
    # every tolerance is written with its reason
    for key in ("ce_tol", "logits_rel_tol", "update_rel_tol", "update_rel_median_tol",
                "sgd_loss_tol"):
        assert FILE["checks"][key] > 0 and len(FILE["checks"][key + "_why"]) > 40
    assert 0.9 < FILE["checks"]["choices_agree_min"] <= 1 and FILE["checks"]["choices_why"]
