"""The plain reference for the ``kimi-linear-48b-a3b`` configuration: a
hybrid decoder of Kimi Delta Attention (KDA) mixers, latent attention without
position encoding and sigmoid-routed expert layers, written from its
equations in ``jax.numpy``, float32 at precision ``highest``, with no
kernels, no bfloat16, no chunks and no dispatch. KDA is the recurrence
itself, one position at a time (a ``lax.scan`` over the positions, in
checkpointed blocks of ``STEPS`` steps so that its gradient fits), not the
program's chunked form; attention is a plain masked softmax; every held
expert is applied to all tokens and masked by who chose it. It imports
nothing from ``shared_tensor_tpu``; the row blocks, the norm and the masked
softmax are ``smallthinker_ref``'s.

``m`` is the configuration file's dict: its top-level counts are what this
chip holds (the experts ``experts_held`` = [first, count] of
``published["num_experts"]``); ``params`` a dict keyed by the checkpoint's
tensor names. For x ``[T, hidden]`` of one sequence, layer l (from 0) with
u = RMSNorm(x) (eps ``rms_norm_eps``), h = x + mixer(u), y = h +
ffn(RMSNorm(h)):

- KDA where l + 1 is in ``linear_attn_config["kda_layers"]``, H heads of d:
  q, k, v = silu(conv(u W)) each, conv_t = sum_i w_i a_{t-i} over the 4 taps
  (row ``K - 1 - i`` of ``*_conv1d.weight [K, channels]`` on position t - i,
  none before 0); q, k divided by sqrt(sum of squares + 1e-6) per head, q
  times d^-1/2; g = -exp(A_log[h]) softplus(u W_fa W_fb + dt_bias); beta =
  sigmoid(u W_b); for t = 0, 1, ...: S = exp(g_t) S (row c of S times
  exp(g_t[c])), S = S + beta_t k_t (v_t - S^T k_t)^T, o_t = S^T q_t (S_{-1} =
  0); mixer = W_o [RMSNorm_d(o) w_norm * sigmoid(u W_ga W_gb)].
- else MLA: q = u W_q in heads of 128 + 64; kv_a = u W_kva; k = [RMSNorm(kv_a[:
  512]) W_kvb (128 a head) | kv_a[512:] (the same 64 for every head)], v the
  other 128 of W_kvb a head; no rotary turn; causal softmax(q k^T / sqrt(192))
  v; mixer = concat_h W_o.
- ffn: layer 0 SwiGLU; else s = sigmoid(u W_r) over all experts, chosen = top
  k of s (the correction bias is zero), w = scale s[chosen] / sum s[chosen],
  ffn = swiglu_shared(u) + sum_{e chosen and held} w_e swiglu_e(u), an
  expert's leaves w1 (gate), w3 (up), w2 (down).
- loss = CE(token t+1 | RMSNorm(y_L) W_head) over the held rows.

What absent experts would add is left out.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference.smallthinker_ref import _highest, _in_row_blocks, _lin, _norm, attention

STEPS = 128  # time steps a checkpointed block of the recurrence


def _layer(i: int) -> str:
    return f"model.layers.{i}."


def recurrence(q, k, v, g, beta):
    """``o [T, H, dv]``, o_t = S_t^T q_t for S_t = (I - beta_t k_t k_t^T)
    Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T from S_{-1} = 0, one position
    after the other: ``q, k, g [T, H, d]``, ``v [T, H, dv]``, ``beta [T,
    H]``."""
    n, h, d = q.shape

    def step(s, at):
        q_t, k_t, v_t, g_t, b_t = at
        s = jnp.exp(g_t)[:, :, None] * s
        error = v_t - jnp.sum(k_t[:, :, None] * s, axis=1)  # v_t - S^T k_t, [H, dv]
        s = s + b_t[:, None, None] * k_t[:, :, None] * error[:, None, :]
        return s, jnp.sum(q_t[:, :, None] * s, axis=1)

    run = lambda s, ats: jax.lax.scan(step, s, ats)
    start, ats = jnp.zeros((h, d, v.shape[-1]), q.dtype), (q, k, v, g, beta)
    if n <= STEPS or n % STEPS:
        return run(start, ats)[1]
    blocks = jax.tree.map(lambda a: a.reshape(n // STEPS, STEPS, *a.shape[1:]), ats)
    return jax.lax.scan(jax.checkpoint(run), start, blocks)[1].reshape(n, h, -1)


def _conv_silu(a, w):
    taps, n = w.shape[0], a.shape[0]
    return jax.nn.silu(sum(
        w[taps - 1 - i] * jnp.concatenate([jnp.zeros((i, a.shape[1]), a.dtype), a[:n - i]])
        for i in range(taps)))


def kda(p, pre, u, m):
    lin = m["linear_attn_config"]
    n, h, d = u.shape[0], lin["num_heads"], lin["head_dim"]

    def proj(u):
        return tuple(_lin(u, p[pre + f"{name}_proj.weight"]) for name in "qkv") + (
            _lin(_lin(u, p[pre + "f_a_proj.weight"]), p[pre + "f_b_proj.weight"]),
            _lin(u, p[pre + "b_proj.weight"]),
            _lin(_lin(u, p[pre + "g_a_proj.weight"]), p[pre + "g_b_proj.weight"]))

    q, k, v, f, b, gate = _in_row_blocks(proj, u)
    q, k, v = (_conv_silu(a, p[pre + f"{name}_conv1d.weight"]).reshape(n, h, d)
               for a, name in zip((q, k, v), "qkv"))
    unit = lambda a: a / jnp.sqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)
    g = -jnp.exp(p[pre + "A_log"])[:, None] * jax.nn.softplus(
        (f + p[pre + "dt_bias"]).reshape(n, h, d))
    o = recurrence(unit(q) / jnp.sqrt(jnp.asarray(d, u.dtype)), unit(k), v, g, jax.nn.sigmoid(b))
    o = _norm(o, p[pre + "o_norm.weight"], m["rms_norm_eps"]) * jax.nn.sigmoid(
        gate.reshape(n, h, d))
    return _in_row_blocks(lambda o: _lin(o, p[pre + "o_proj.weight"]), o.reshape(n, h * d))


def mla(p, pre, u, m):
    n, h = u.shape[0], m["num_attention_heads"]
    nope, rd, vd, rank = (m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"],
                          m["kv_lora_rank"])

    def qkv(u):
        n = u.shape[0]
        q = _lin(u, p[pre + "q_proj.weight"]).reshape(n, h, nope + rd)
        kv_a = _lin(u, p[pre + "kv_a_proj_with_mqa.weight"])
        kv = _lin(_norm(kv_a[:, :rank], p[pre + "kv_a_layernorm.weight"], m["rms_norm_eps"]),
                  p[pre + "kv_b_proj.weight"]).reshape(n, h, nope + vd)
        k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(kv_a[:, None, rank:], (n, h, rd))],
                            axis=-1)
        return q, k, kv[..., nope:]

    q, k, v = _in_row_blocks(qkv, u)
    return _in_row_blocks(lambda o: _lin(o.reshape(-1, h * vd), p[pre + "o_proj.weight"]),
                          attention(q, k, v, None))


def swiglu(p, pre, u, names=("gate_proj", "up_proj", "down_proj")):
    gate, up, down = (p[pre + f"{name}.weight"] for name in names)
    return _lin(jax.nn.silu(_lin(u, gate)) * _lin(u, up), down)


def expert_layer(p, pre, u, m, chosen=None):
    """Shared expert + the held experts' part; ``chosen [T, k]`` overrides
    the router's own choice (its weights stay the router's). Returns the
    output, the router's own choice and its selection scores."""
    s = jax.nn.sigmoid(_lin(u, p[pre + "gate.weight"]))
    own = jax.lax.top_k(s, m["num_experts_per_token"])[1]
    idx = own if chosen is None else chosen
    w = jnp.take_along_axis(s, idx, axis=1)
    if m["moe_renormalize"]:
        w = w / jnp.sum(w, axis=1, keepdims=True)
    w = w * m["routed_scaling_factor"]
    first, count = m["experts_held"]
    stack = lambda name: jnp.stack(
        [p[pre + f"experts.{e}.{name}.weight"] for e in range(first, first + count)])

    def one(out, ew):
        e, w1, w3, w2 = ew
        w_e = jnp.sum(jnp.where(idx == e, w, 0.0), axis=1)  # 0 where e was not chosen
        return out + w_e[:, None] * _lin(jax.nn.silu(_lin(u, w1)) * _lin(u, w3), w2), None

    routed = jax.lax.scan(one, jnp.zeros_like(u), (
        jnp.arange(first, first + count), stack("w1"), stack("w3"), stack("w2")))[0]
    return swiglu(p, pre + "shared_experts.", u) + routed, own, s


def block(p, i, x, m, chosen=None):
    pre, eps = _layer(i), m["rms_norm_eps"]
    u = _norm(x, p[pre + "input_layernorm.weight"], eps)
    kind = kda if i + 1 in m["linear_attn_config"]["kda_layers"] else mla
    h = x + kind(p, pre + "self_attn.", u, m)
    u = _norm(h, p[pre + "post_attention_layernorm.weight"], eps)
    if i < m["first_k_dense_replace"]:
        return h + _in_row_blocks(lambda u: swiglu(p, pre + "mlp.", u), u), None
    f, own, select = _in_row_blocks(
        lambda u, chosen: expert_layer(p, pre + "block_sparse_moe.", u, m, chosen), u, chosen)
    return h + f, (own, select)


@_highest
def hidden(p, tokens, m, choices=None):
    """``(y_L, routed)`` of one sequence: the last layer's output and, one
    entry an expert layer in order, the router's own choice and its
    selection scores. ``choices`` (one ``[T, k]`` an expert layer) forces the
    experts."""
    forced = iter(choices) if choices is not None else None
    routed = []
    x = p["model.embed_tokens.weight"][tokens]
    for i in range(m["num_hidden_layers"]):
        experts = i >= m["first_k_dense_replace"]
        x, r = jax.checkpoint(lambda p, x, c, i=i: block(p, i, x, m, c))(
            p, x, next(forced) if forced is not None and experts else None)
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
