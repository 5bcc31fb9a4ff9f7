"""Operations and bytes of one training step of the ``kimi-linear-48b-a3b``
configuration, computed from its shapes: the yardstick's arithmetic for
``model_flops_util``, ``attn_kernels_roofline`` and ``kda_scan_roofline`` in
its cell. Layer ``i`` (from 0) mixes by KDA where ``i + 1`` is in
``linear_attn_config["kda_layers"]``, else by latent attention (MLA); its
feed-forward part is dense below ``first_k_dense_replace``, else an expert
layer. The file's top-level counts are what this chip holds. Nothing here
touches a device.

The scan's counts are of the chunked algorithm's products (chunks of
``CHUNK``) and of its operands and results by shape and dtype: the work the
recurrence needs, not what XLA writes between passes, so a kernel that does
the same work cannot read above its roofline.
"""

from __future__ import annotations

CHUNK = 64  # positions a chunk (models/kimi_linear.py)


def _kda_layers(m: dict) -> list[bool]:
    kda = m["linear_attn_config"]["kda_layers"]
    return [i + 1 in kda for i in range(m["num_hidden_layers"])]


def matrix_params_per_token(m: dict) -> int:
    """Matrix parameters one token's forward pass multiplies by, for the
    configuration's dict ``m``: a KDA mixer's q, k, v, o, its two low-rank
    pairs (decay and output gate) and ``b_proj``; an MLA mixer's ``q_proj``,
    ``kv_a_proj_with_mqa``, ``kv_b_proj`` and ``o_proj``; the dense SwiGLU; an
    expert layer's router, shared expert and the experts a token is expected
    to meet here (``num_experts_per_token`` x held / routed); the head once.
    The embedding is a lookup; the convolutions, norms and the scan are not
    products of a parameter matrix."""
    d, lin = m["hidden_size"], m["linear_attn_config"]
    width, hd = lin["num_heads"] * lin["head_dim"], lin["head_dim"]
    kda = 4 * width * d + 2 * (hd * d + width * hd) + lin["num_heads"] * d
    h, nope, rope, vd = (m["num_attention_heads"], m["qk_nope_head_dim"],
                         m["qk_rope_head_dim"], m["v_head_dim"])
    mla = (h * (nope + rope) * d + (m["kv_lora_rank"] + rope) * d
           + m["kv_lora_rank"] * h * (nope + vd) + d * h * vd)
    expert = 3 * d * m["moe_intermediate_size"]
    routed = m["num_experts_per_token"] * m["experts_held"][1] / m["published"]["num_experts"]
    moe = d * m["published"]["num_experts"] + (m["num_shared_experts"] + routed) * expert
    total = m["vocab_size"] * d
    for i, is_kda in enumerate(_kda_layers(m)):
        total += kda if is_kda else mla
        total += 3 * d * m["intermediate_size"] if i < m["first_k_dense_replace"] else moe
    return round(total)


def attention_pairs(m: dict, seq: int) -> int:
    """(query, key) pairs one head's attention holds over one sequence, all
    MLA layers: the causal triangle with its diagonal."""
    return _kda_layers(m).count(False) * seq * (seq + 1) // 2


def attention_flops(m: dict, seq: int) -> int:
    """Forward multiply-adds x 2 of attention over one sequence by exact
    pairs: scores ``qk_nope + qk_rope`` wide and weighted values
    ``v_head_dim`` wide, a head."""
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    return attention_pairs(m, seq) * m["num_attention_heads"] * 2 * (qk + m["v_head_dim"])


def attention_kernel_flops(m: dict, seq: int) -> int:
    """What the two attention kernels must do a step, by exact pairs: the
    forward's two products (scores, weighted values) and the backward's five
    (scores again, dp, dv, dk, dq), 2 x (3 x 192 + 2 x 128) + 2 x (2 x 192 +
    128) a pair a head at this configuration's widths; one MLA layer of 32
    heads here."""
    qk, vd = m["qk_nope_head_dim"] + m["qk_rope_head_dim"], m["v_head_dim"]
    per_pair = 2 * (qk + vd) + 2 * (3 * qk + 2 * vd)
    return attention_pairs(m, seq) * m["num_attention_heads"] * per_pair


def _chunks(seq: int) -> int:
    return -(-seq // CHUNK)


def kda_scan_forward_flops(m: dict, seq: int) -> int:
    """The chunked scan's products over one sequence, all KDA mixers, a
    chunk of C positions and a head of d key and dv value channels: the
    decayed inner products of k with k below the diagonal (C (C - 1) d) and
    of q with k on and below it (C (C + 1) d), the forward substitution ``(I
    + A) [W | U] = [β K̃ | β V]`` (C (C - 1) (d + dv)), ``W S``, ``Q̃ S`` and
    ``K̄ᵀ V'`` (2 C d dv each) and ``P V'`` (C (C + 1) dv), multiply-adds x 2."""
    lin, c = m["linear_attn_config"], CHUNK
    d = dv = lin["head_dim"]
    chunk = (c * (c - 1) * d + c * (c + 1) * d + c * (c - 1) * (d + dv) + 6 * c * d * dv
             + c * (c + 1) * dv)
    return _kda_layers(m).count(True) * _chunks(seq) * lin["num_heads"] * chunk


def kda_scan_flops(m: dict, seq: int) -> int:
    """What the scan must do a step, forward and backward: its products
    forward and two of the same size each in the backward pass (a cotangent
    of each operand). Remaking the forward in the backward rule is
    recomputation and does not count."""
    return 3 * kda_scan_forward_flops(m, seq)


def kda_scan_bytes(m: dict, seq: int) -> int:
    """Bytes the scan must move a step, from its operands' and results'
    shapes and dtypes, all KDA mixers: forward it reads ``q, k, v [T, H, d]``
    in ``compute_dtype``, the float32 ``g [T, H, d]`` and ``β [T, H]``, and
    writes ``o [T, H, dv]`` in ``compute_dtype``; backward it reads the same
    inputs and ``o``'s cotangent and writes one cotangent an input. What
    lives between (decays, chunk states) is the implementation's and not
    counted."""
    lin = m["linear_attn_config"]
    h, d = lin["num_heads"], lin["head_dim"]
    b = 2 if m["compute_dtype"] == "bfloat16" else 4
    inputs = seq * h * (3 * b * d + 4 * d + 4)
    output = b * seq * h * d
    return _kda_layers(m).count(True) * (3 * inputs + 2 * output)


def train_flops_per_sequence(m: dict, seq: int) -> int:
    """Forward plus backward of one sequence of ``seq`` tokens: 6 x the
    matrix parameters a token meets (2 forward, 4 backward), plus 3 x the
    forward attention by exact pairs, plus the scan's products forward and
    backward. Recomputation does not count."""
    return (6 * matrix_params_per_token(m) * seq + 3 * attention_flops(m, seq)
            + kda_scan_flops(m, seq))
