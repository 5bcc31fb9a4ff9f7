"""Operations of one training step of the ``joyai-llm-flash`` configuration,
computed from its shapes: the yardstick's arithmetic for ``model_flops_util``
in the language-model cells. Nothing here touches a device.
"""

from __future__ import annotations


def matrix_params_per_token(m: dict) -> int:
    """Matrix parameters one token's forward pass multiplies by, for the
    configuration's dict ``m`` (the file's keys): every projection of every
    block, the dense layer's SwiGLU, of an expert layer the router, the
    shared expert and the routed experts a token is expected to meet here
    (``num_experts_per_tok`` x held / routed: the share of its choices this
    chip holds), the prediction module's ``eh_proj``, and the head once for
    each loss. The embedding is a lookup and the norms run on the VPU."""
    d, h = m["hidden_size"], m["num_attention_heads"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    attention = (
        d * m["q_lora_rank"] + m["q_lora_rank"] * h * qk
        + d * (m["kv_lora_rank"] + m["qk_rope_head_dim"])
        + m["kv_lora_rank"] * h * (m["qk_nope_head_dim"] + m["v_head_dim"])
        + h * m["v_head_dim"] * d
    )
    dense = 3 * d * m["intermediate_size"]
    expert = 3 * d * m["moe_intermediate_size"]
    routed = m["num_experts_per_tok"] * m["experts_held"][1] / m["published"]["n_routed_experts"]
    moe = d * m["published"]["n_routed_experts"] + (m["n_shared_experts"] + routed) * expert
    head = m["vocab_size"] * d
    first = m["first_k_dense_replace"]
    total = m["num_hidden_layers"] * attention + first * dense
    total += (m["num_hidden_layers"] - first) * moe + head
    if m["num_nextn_predict_layers"]:
        total += 2 * d * d + attention + moe + head
    return round(total)


def attention_flops(m: dict, seq: int) -> int:
    """Forward multiply-adds x 2 of causal attention over one sequence, all
    blocks: scores and weighted values over the S^2 / 2 causal pairs, per
    head (d_nope + d_rope) + d_v wide."""
    blocks = m["num_hidden_layers"] + m["num_nextn_predict_layers"]
    width = m["qk_nope_head_dim"] + m["qk_rope_head_dim"] + m["v_head_dim"]
    return blocks * (seq * seq // 2) * 2 * m["num_attention_heads"] * width


def train_flops_per_sequence(m: dict, seq: int) -> int:
    """Forward plus backward of one sequence of ``seq`` tokens: 6 x the
    matrix parameters a token meets (2 forward, 4 backward), plus 3 x the
    forward attention. Recomputation does not count. 27.5 TFLOP for the
    configuration at 8 192 tokens."""
    return 6 * matrix_params_per_token(m) * seq + 3 * attention_flops(m, seq)
