"""Operations of one training step of the ``smallthinker-21b-a3b``
configuration, computed from its shapes: the yardstick's arithmetic for
``model_flops_util`` and ``attn_kernels_roofline`` in its cells. Nothing here
touches a device.
"""

from __future__ import annotations


def matrix_params_per_token(m: dict) -> int:
    """Matrix parameters one token's forward pass multiplies by, for the
    configuration's dict ``m`` (the file's keys): per layer the four
    attention projections, the router and the experts a token is expected to
    meet here (``moe_num_active_primary_experts`` x held / routed: the share
    of its choices this chip holds), and the head once. The embedding is a
    lookup and the norms run on the VPU."""
    d, hd = m["hidden_size"], m["head_dim"]
    attention = 2 * d * m["num_attention_heads"] * hd + 2 * d * m["num_key_value_heads"] * hd
    routed = (m["moe_num_active_primary_experts"] * m["experts_held"][1]
              / m["published"]["moe_num_primary_experts"])
    moe = d * m["published"]["moe_num_primary_experts"] + routed * 3 * d * m["moe_ffn_hidden_size"]
    return round(m["num_hidden_layers"] * (attention + moe) + m["vocab_size"] * d)


def attention_pairs(m: dict, seq: int) -> int:
    """(query, key) pairs one head's attention holds over one sequence, all
    layers: a full layer the causal triangle with its diagonal, a window
    layer each query's ``sliding_window_size`` newest keys (fewer for the
    first queries)."""
    w = min(m["sliding_window_size"], seq)
    full = seq * (seq + 1) // 2
    band = w * (w + 1) // 2 + (seq - w) * w
    return sum(band if m["sliding_window_layout"][i] else full
               for i in range(m["num_hidden_layers"]))


def attention_flops(m: dict, seq: int) -> int:
    """Forward multiply-adds x 2 of attention over one sequence, all layers,
    by exact pairs: scores and weighted values, ``head_dim`` wide each, a
    query head (512 a pair a head at 128)."""
    return attention_pairs(m, seq) * m["num_attention_heads"] * 2 * 2 * m["head_dim"]


def attention_kernel_flops(m: dict, seq: int) -> int:
    """What the two attention kernels must do a step, by exact pairs and not
    by tiles visited: the forward's two products and the backward's five
    (scores again, dp, dv, dk, dq), 512 + 1 280 a pair a head at 128."""
    return attention_pairs(m, seq) * m["num_attention_heads"] * 7 * 2 * m["head_dim"]


def train_flops_per_sequence(m: dict, seq: int) -> int:
    """Forward plus backward of one sequence of ``seq`` tokens: 6 x the
    matrix parameters a token meets (2 forward, 4 backward), plus 3 x the
    forward attention. Recomputation does not count. 34.70 TFLOP for the
    configuration at 16 384 tokens."""
    return 6 * matrix_params_per_token(m) * seq + 3 * attention_flops(m, seq)
