"""The plain reference for the ``laguna-s-2.1`` configuration: a decoder whose
attention layers are full (48 query heads, RoPE on half a head with YaRN's
frequencies and attention factor) or over a window of 512 keys (72 query
heads, plain RoPE on the whole head) on 8 K/V heads, each head's output
scaled by a sigmoid gate of the layer's normed input; a dense SwiGLU first
layer; then a softmax-over-all router, top 10 renormalised and scaled, beside
an ungated shared expert. Written from its equations in ``jax.numpy``,
float32 at precision ``highest``, with no kernels, no bfloat16, no tiling of
the causal triangle and no dispatch: every held expert is applied to all
tokens and masked by who chose it. It imports nothing from
``shared_tensor_tpu``; the row blocks, the norm and the masked grouped-query
softmax are ``smallthinker_ref``'s, the same equations.

``m`` is the configuration file's dict (the published keys, plus
``experts_held`` = [first, count]); ``params`` a dict keyed by the
checkpoint's tensor names, ``[out, in]`` matrices. For x ``[T, hidden]`` of
one sequence, layer l with ``H = num_attention_heads_per_layer[l]``:

- n1 = RMSNorm(x); q = n1 W_q in H heads, k = n1 W_k and v = n1 W_v in
  ``num_key_value_heads`` heads of ``head_dim``; query head i reads K/V head
  i // (H / kv heads).
- RoPE by ``rope_parameters[layer_types[l]]``: the first r =
  ``partial_rotary_factor`` x ``head_dim`` dimensions of every q and k head,
  dimension i < r/2 paired with i + r/2, turned by the angle t x inv_freq_i;
  the others pass. ``default``: inv_freq_i = theta^(-2i/r). ``yarn``:
  :func:`yarn_frequencies`, and the turned dimensions times
  ``attention_factor``.
- scores / sqrt(head_dim) under an explicit mask: key j for query i iff j <=
  i and, on ``sliding_attention`` layers, i - j < ``sliding_window``.
  a = P v; g = sigmoid(n1 W_g) ``[T, H]``; h = x + concat_h(g_h a_h) W_o.
- n2 = RMSNorm(h). ``mlp_layer_types[l]`` dense: y = h + SwiGLU(n2) at
  ``intermediate_size``. sparse: s = softmax(n2 W_r) over all experts; chosen
  = top k of s; w = ``moe_routed_scaling_factor`` s[chosen] / sum s[chosen];
  y = h + SwiGLU_shared(n2) + sum_{e chosen and held} w_e SwiGLU_e(n2). What
  absent experts would add is left out.
- loss = CE(token t+1 | RMSNorm(y_L) W_head) over the held rows.

At the chip's sizes the row-wise parts and the queries go in blocks of
``smallthinker_ref.ROWS`` and each block and layer is made again in the
backward pass, as there; none of that changes the arithmetic of a position.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.smallthinker_ref import (
    _highest, _in_row_blocks, _lin, _norm, attention,
)


def yarn_frequencies(keys: dict, r: int) -> np.ndarray:
    """The r/2 angular frequencies of a ``rope_type: yarn`` group over r
    turned dimensions. A pair that makes ``beta_fast`` turns or more over the
    ``original_max_position_embeddings`` keeps its trained frequency
    theta^(-2i/r); one that makes ``beta_slow`` or fewer is slowed by
    ``factor``; the pairs between those two (by index: the first at or below
    the fast one, the last at or above the slow one) blend the two linearly.
    The pair that makes n turns over the original context L is the real i
    with L theta^(-2i/r) = 2 pi n."""
    theta, length = float(keys["rope_theta"]), keys["original_max_position_embeddings"]
    trained = np.array([theta ** (-2.0 * i / r) for i in range(r // 2)])
    index_of_turns = lambda n: r * math.log(length / (2 * math.pi * n)) / (2 * math.log(theta))
    first = max(math.floor(index_of_turns(keys["beta_fast"])), 0)
    last = min(math.ceil(index_of_turns(keys["beta_slow"])), r - 1)
    if last == first:
        last += 0.001
    slowed_share = np.clip((np.arange(r // 2) - first) / (last - first), 0.0, 1.0)
    mixed = slowed_share * trained / keys["factor"] + (1.0 - slowed_share) * trained
    return mixed.astype(np.float32)


def turn(x, keys: dict, pos):
    """``x [T, H, d]`` with RoPE by the group ``keys``: the first r dimensions
    as r/2 complex numbers (dimension i with i + r/2) times exp(i pos
    inv_freq), times ``attention_factor`` under yarn; the rest untouched."""
    d = x.shape[-1]
    r = int(d * keys.get("partial_rotary_factor", 1))
    if keys["rope_type"] == "yarn":
        inv, scale = jnp.asarray(yarn_frequencies(keys, r)), keys["attention_factor"]
    else:
        inv, scale = keys["rope_theta"] ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r), 1.0
    ang = pos[:, None] * inv[None, :]
    z = (x[..., : r // 2] + 1j * x[..., r // 2: r]) * jnp.exp(1j * ang)[:, None, :]
    return jnp.concatenate([z.real * scale, z.imag * scale, x[..., r:]], axis=-1)


def self_attention(p, pre, x, m, layer):
    n, h_kv, d = x.shape[0], m["num_key_value_heads"], m["head_dim"]
    h, kind = m["num_attention_heads_per_layer"][layer], m["layer_types"][layer]
    keys = m["rope_parameters"][kind]

    def qkv(x, pos):
        q = turn(_lin(x, p[pre + "q_proj.weight"]).reshape(-1, h, d), keys, pos)
        k = turn(_lin(x, p[pre + "k_proj.weight"]).reshape(-1, h_kv, d), keys, pos)
        return q, k, _lin(x, p[pre + "v_proj.weight"]).reshape(-1, h_kv, d)

    q, k, v = _in_row_blocks(qkv, x, jnp.arange(n, dtype=jnp.float32))
    window = m["sliding_window"] if kind == "sliding_attention" else None

    def gated_out(a, x):
        g = jax.nn.sigmoid(_lin(x, p[pre + "g_proj.weight"]))  # [rows, H]
        return _lin((a * g[:, :, None]).reshape(-1, h * d), p[pre + "o_proj.weight"])

    return _in_row_blocks(gated_out, attention(q, k, v, window), x)


def swiglu(p, pre, u):
    return _lin(jax.nn.silu(_lin(u, p[pre + "gate_proj.weight"]))
                * _lin(u, p[pre + "up_proj.weight"]), p[pre + "down_proj.weight"])


def expert_layer(p, pre, u, m, chosen=None):
    """Shared expert + the held experts' part; ``chosen [T, k]`` overrides the
    router's own choice (its weights stay the router's). Returns the output,
    the router's own choice and its logits (which order as its softmax)."""
    logits = _lin(u, p[pre + "gate.weight"])
    s = jax.nn.softmax(logits, axis=-1)
    own = jax.lax.top_k(s, m["num_experts_per_tok"])[1]
    idx = own if chosen is None else chosen
    w = jnp.take_along_axis(s, idx, axis=1)
    if m["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=1, keepdims=True)
    w = w * m["moe_routed_scaling_factor"]
    first, count = m["experts_held"]
    stack = lambda name: jnp.stack(
        [p[pre + f"experts.{e}.{name}_proj.weight"] for e in range(first, first + count)])

    def one(out, ew):
        e, gate, up, down = ew
        w_e = jnp.sum(jnp.where(idx == e, w, 0.0), axis=1)  # 0 where e was not chosen
        return out + w_e[:, None] * _lin(jax.nn.silu(_lin(u, gate)) * _lin(u, up), down), None

    routed = jax.lax.scan(one, jnp.zeros_like(u), (
        jnp.arange(first, first + count), stack("gate"), stack("up"), stack("down")))[0]
    return swiglu(p, pre + "shared_expert.", u) + routed, own, logits


def block(p, i, x, m, chosen=None):
    pre, eps = f"model.layers.{i}.", m["rms_norm_eps"]
    h = x + self_attention(
        p, pre + "self_attn.", _norm(x, p[pre + "input_layernorm.weight"], eps), m, i)
    u = _norm(h, p[pre + "post_attention_layernorm.weight"], eps)
    if m["mlp_layer_types"][i] == "dense":
        return h + _in_row_blocks(lambda u: swiglu(p, pre + "mlp.", u), u), None
    f, own, logits = _in_row_blocks(
        lambda u, chosen: expert_layer(p, pre + "mlp.", u, m, chosen), u, chosen)
    return h + f, (own, logits)


@_highest
def hidden(p, tokens, m, choices=None):
    """``(y_L, routed)`` of one sequence: the last layer's output and, one
    entry an expert layer in order, the router's own choice and its logits.
    ``choices`` (one ``[T, k]`` an expert layer) forces the experts."""
    forced = iter(choices) if choices is not None else None
    routed = []
    x = p["model.embed_tokens.weight"][tokens]
    for i in range(m["num_hidden_layers"]):
        sparse = m["mlp_layer_types"][i] != "dense"
        x, r = jax.checkpoint(lambda p, x, c, i=i: block(p, i, x, m, c))(
            p, x, next(forced) if forced is not None and sparse else None)
        routed += [r] if r is not None else []
    return x, routed


@_highest
def logits(p, y, m):
    return _lin(_norm(y, p["model.norm.weight"], m["rms_norm_eps"]), p["lm_head.weight"])


@_highest
def outputs(p, tokens, m, choices=None, at=None):
    """``(CE, logits, routed)`` of one sequence: the mean next-token
    cross-entropy, the logits at the positions ``at`` (``None`` without) and
    :func:`hidden`'s ``routed``."""
    y, routed = hidden(p, tokens, m, choices)

    def nll(y, targets):
        lg = logits(p, y, m)
        return jax.nn.logsumexp(lg, axis=-1) - jnp.take_along_axis(
            lg, targets[:, None], axis=-1)[:, 0]

    ce = jnp.mean(_in_row_blocks(nll, y, jnp.roll(tokens, -1))[:-1])
    return ce, None if at is None else logits(p, y[at], m), routed


def loss_and_outputs(p, batch, m, choices=None, at=None):
    """Mean over the sequences of ``batch [B, T]`` of the cross-entropy, and
    every sequence's :func:`outputs`; ``choices`` (per sequence, per expert
    layer) forces the experts."""
    outs = [outputs(p, batch[b], m, None if choices is None else choices[b], at)
            for b in range(batch.shape[0])]
    return sum(ce for ce, _, _ in outs) / batch.shape[0], outs


def loss(p, batch, m, choices=None):
    """The mean loss alone: ``jax.grad`` of this is the reference gradient."""
    return loss_and_outputs(p, batch, m, choices)[0]
