"""Operations of one training step of the ``laguna-s-2.1`` configuration,
computed from its shapes: the yardstick's arithmetic for ``model_flops_util``
and ``attn_kernels_roofline`` in its cells. A layer has its own count of
query heads, its own reach (the whole prefix or a window) and its own MLP (a
dense one, or a router, a shared expert and the held experts), so every sum
here goes layer by layer. Nothing here touches a device.
"""

from __future__ import annotations


def _layers(m: dict):
    """``(query heads, is a window layer, is a dense layer)`` of every layer
    that runs."""
    return [(m["num_attention_heads_per_layer"][i], m["layer_types"][i] == "sliding_attention",
             m["mlp_layer_types"][i] == "dense") for i in range(m["num_hidden_layers"])]


def matrix_params_per_token(m: dict) -> int:
    """Matrix parameters one token's forward pass multiplies by, for the
    configuration's dict ``m`` (the file's keys): per layer the four
    attention projections at the layer's own heads and its gate; a dense
    layer's SwiGLU, or the router, the shared expert and the experts a token
    is expected to meet here (``num_experts_per_tok`` x held / routed: the
    share of its choices this chip holds); and the head once. The embedding
    is a lookup and the norms run on the VPU."""
    d, hd = m["hidden_size"], m["head_dim"]
    routed = m["num_experts_per_tok"] * m["experts_held"][1] / m["published"]["num_experts"]
    sparse = (d * m["published"]["num_experts"] + 3 * d * m["shared_expert_intermediate_size"]
              + routed * 3 * d * m["moe_intermediate_size"])
    total = m["vocab_size"] * d
    for heads, _, dense in _layers(m):
        total += 2 * d * heads * hd + 2 * d * m["num_key_value_heads"] * hd + heads * d
        total += 3 * d * m["intermediate_size"] if dense else sparse
    return round(total)


def head_pairs(m: dict, seq: int) -> int:
    """(query, key) pairs x query heads that attention holds over one
    sequence, all layers: a full layer the causal triangle with its diagonal,
    a window layer each query's ``sliding_window`` newest keys (fewer for the
    first queries), each times the layer's own heads."""
    w = min(m["sliding_window"], seq)
    full = seq * (seq + 1) // 2
    band = w * (w + 1) // 2 + (seq - w) * w
    return sum(heads * (band if window else full) for heads, window, _ in _layers(m))


def attention_flops(m: dict, seq: int) -> int:
    """Forward multiply-adds x 2 of attention over one sequence, all layers,
    by exact pairs: scores and weighted values, ``head_dim`` wide each (512 a
    pair a head at 128)."""
    return head_pairs(m, seq) * 2 * 2 * m["head_dim"]


def attention_kernel_flops(m: dict, seq: int) -> int:
    """What the two attention kernels must do a step, by exact pairs and not
    by tiles visited: the forward's two products and the backward's five
    (scores again, dp, dv, dk, dq), 512 + 1 280 a pair a head at 128."""
    return head_pairs(m, seq) * 7 * 2 * m["head_dim"]


def train_flops_per_sequence(m: dict, seq: int) -> int:
    """Forward plus backward of one sequence of ``seq`` tokens: 6 x the
    matrix parameters a token meets (2 forward, 4 backward), plus 3 x the
    forward attention. Recomputation does not count. 30.00 TFLOP for the
    configuration at 8 192 tokens."""
    return 6 * matrix_params_per_token(m) * seq + 3 * attention_flops(m, seq)
