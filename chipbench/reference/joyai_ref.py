"""The plain reference for the ``joyai-llm-flash`` configuration: a
DeepSeek-V3-family decoder (arXiv:2412.19437) written from its equations in
``jax.numpy``, float32 at precision ``highest``, with no kernels, no bfloat16
and no dispatch: every held expert is applied to all tokens and masked by who
chose it. It imports nothing from ``shared_tensor_tpu.models``.

``model`` is the configuration file's dict (the published keys, plus
``experts_held`` = [first, count] and ``mtp_loss_weight``);
``params`` a dict keyed by the checkpoint's tensor names, ``[out, in]``
matrices. Equations, for x ``[T, hidden]`` of one sequence:

- RMSNorm(x) = x / sqrt(mean(x^2) + eps) * w.
- MLA: c_q = RMSNorm(x W_qa); q = c_q W_qb, per head [q_nope | q_rope];
  [c_kv | k_r] = x W_kva; c_kv = RMSNorm(c_kv); per head [k_nope | v] =
  c_kv W_kvb; RoPE (pairs (2i, 2i+1), angle t theta^(-2i/d)) on q_rope and
  on the one k_r all heads share; softmax over the causal prefix of
  (q_nope.k_nope + q_rope.k_r) / sqrt(d_nope + d_rope); concat_h(P v) W_o.
- block: h = x + MLA(RMSNorm(x)); y = h + FFN(RMSNorm(h)); layer 0's FFN is
  W_down(silu(W_gate u) * W_up u).
- expert layer: s = sigmoid(u W_g); chosen = top k of s + b; w_i = scale *
  s_i / (sum_{j chosen} s_j + 1e-20); FFN(u) = E_shared(u) + sum_{i chosen
  and held} w_i E_i(u). What absent experts would add is left out.
- loss = CE(token t+1 | RMSNorm(y_L) W_head) + weight * CE(token t+2 | the
  prediction module), the module being [RMSNorm_e(Emb(t_{i+1})) |
  RMSNorm_h(y_L,i)] W_eh -> one expert-layer block -> shared_head.norm ->
  the same W_head.

At the chip's sizes attention runs head by head, and what is computed row by
row (the projections, a layer's FFN or expert layer, the head and its
cross-entropy) in blocks of ``ROWS`` rows one after the other, each block made
again in the backward pass, like each layer, so that the gradient fits; the
prediction module runs all T positions (the last joined with token 0) and
drops the last afterwards, so that its products have the main model's shapes.
None of that changes the arithmetic of a position that counts: a row's
products are its own, and attention is causal. The blocks are there for the
compiler: a float32 product at precision ``highest`` costs the TPU's compiler
4 to 11 s a distinct shape at 8 192 rows, and a tenth of that inside a loop
over blocks (chip-free compile, PR 29); the reference's forward and gradient
took 4 of the cell's 15 minutes of set-up.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _highest(fn):
    """``fn`` traced with every product at precision ``highest`` (on a TPU a
    float32 product is otherwise made of bfloat16 passes)."""
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)
    return wrapped


ROWS = 1024


def _lin(x, w):
    return jnp.matmul(x, w.T)


def _in_row_blocks(fn, *xs):
    """``fn(*xs)`` for a ``fn`` that works row by row on arrays ``xs [n, ...]``
    (``None`` passes through) and returns such arrays: more than ``ROWS`` rows
    go through in blocks of ``ROWS``, one after the other."""
    n = xs[0].shape[0]
    if n <= ROWS or n % ROWS:
        return fn(*xs)
    split = lambda a: a.reshape(n // ROWS, ROWS, *a.shape[1:])
    out = jax.lax.map(jax.checkpoint(lambda block: fn(*block)), jax.tree.map(split, xs))
    return jax.tree.map(lambda a: a.reshape(n, *a.shape[2:]), out)


def _norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, theta, pos=None):
    """``x [T, ..., d]``: pair (2i, 2i+1) of position t (``pos [T]``, else 0,
    1, ..) turned by the angle t theta^(-2i/d), as one complex multiplication
    a pair."""
    n, d = x.shape[0], x.shape[-1]
    pos = jnp.arange(n, dtype=jnp.float32) if pos is None else pos
    ang = pos[:, None] * (
        theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d))[None, :]
    ang = ang.reshape(n, *(1,) * (x.ndim - 2), d // 2)
    pairs = x.reshape(*x.shape[:-1], d // 2, 2)
    z = (pairs[..., 0] + 1j * pairs[..., 1]) * jnp.exp(1j * ang)
    return jnp.stack([z.real, z.imag], axis=-1).reshape(x.shape)


def attention(q, k, v):
    """Causal softmax attention, ``[T, H, .]`` operands, one head at a time."""
    n = q.shape[0]

    @jax.checkpoint
    def head(qkv):
        qh, kh, vh = qkv
        s = jnp.matmul(qh, kh.T) / jnp.sqrt(jnp.float32(qh.shape[-1]))
        t = jnp.arange(n)
        s = jnp.where(t[None, :] <= t[:, None], s, -jnp.inf)  # key at or before query
        return jnp.matmul(jax.nn.softmax(s, axis=-1), vh)

    heads = jax.lax.map(head, tuple(jnp.swapaxes(a, 0, 1) for a in (q, k, v)))
    return jnp.swapaxes(heads, 0, 1)


def mla(p, pre, x, m):
    n, h = x.shape[0], m["num_attention_heads"]
    nope, rd, vd = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    eps, rank = m["rms_norm_eps"], m["kv_lora_rank"]

    def qkv(x, pos):
        n = x.shape[0]
        c_q = _norm(_lin(x, p[pre + "q_a_proj.weight"]), p[pre + "q_a_layernorm.weight"], eps)
        q = _lin(c_q, p[pre + "q_b_proj.weight"]).reshape(n, h, nope + rd)
        kv_a = _lin(x, p[pre + "kv_a_proj_with_mqa.weight"])
        c_kv = _norm(kv_a[:, :rank], p[pre + "kv_a_layernorm.weight"], eps)
        k_r = rope(kv_a[:, None, rank:], m["rope_theta"], pos)
        kv = _lin(c_kv, p[pre + "kv_b_proj.weight"]).reshape(n, h, nope + vd)
        q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], m["rope_theta"], pos)], axis=-1)
        k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_r, (n, h, rd))], axis=-1)
        return q, k, kv[..., nope:]

    q, k, v = _in_row_blocks(qkv, x, jnp.arange(n, dtype=jnp.float32))
    return _in_row_blocks(
        lambda o: _lin(o.reshape(-1, h * vd), p[pre + "o_proj.weight"]), attention(q, k, v))


def swiglu(p, pre, u):
    return _lin(jax.nn.silu(_lin(u, p[pre + "gate_proj.weight"]))
                * _lin(u, p[pre + "up_proj.weight"]), p[pre + "down_proj.weight"])


def expert_layer(p, pre, u, m, chosen=None):
    """Shared expert + the held experts' part; ``chosen [T, k]`` overrides the
    router's own choice (its weights stay the router's). Returns the output,
    the router's own choice and its selection scores ``s + b``."""
    k = m["num_experts_per_tok"]
    s = jax.nn.sigmoid(_lin(u, p[pre + "gate.weight"]))
    select = s + jax.lax.stop_gradient(p[pre + "gate.e_score_correction_bias"])
    own = jax.lax.top_k(select, k)[1]
    idx = own if chosen is None else chosen
    w = jnp.take_along_axis(s, idx, axis=1)
    if m["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20)
    w = w * m["routed_scaling_factor"]
    out = swiglu(p, pre + "shared_experts.", u)
    first, count = m["experts_held"]
    for e in range(first, first + count):
        w_e = jnp.sum(jnp.where(idx == e, w, 0.0), axis=1)  # 0 where e was not chosen
        out = out + w_e[:, None] * swiglu(p, pre + f"experts.{e}.", u)
    return out, own, select


def block(p, i, x, m, chosen=None):
    pre, eps = f"model.layers.{i}.", m["rms_norm_eps"]
    h = x + mla(p, pre + "self_attn.", _norm(x, p[pre + "input_layernorm.weight"], eps), m)
    u = _norm(h, p[pre + "post_attention_layernorm.weight"], eps)
    if i < m["first_k_dense_replace"]:
        return h + _in_row_blocks(lambda u: swiglu(p, pre + "mlp.", u), u), None
    f, own, select = _in_row_blocks(
        lambda u, chosen: expert_layer(p, pre + "mlp.", u, m, chosen), u, chosen)
    return h + f, (own, select)


@_highest
def hidden(p, tokens, m, choices=None):
    """``(y_L, z, routed)`` of one sequence: the last layer's output, the
    prediction module's block output (``None`` without one; its last row
    counts for nothing) and, one entry an expert layer in order (the
    module's last, of T - 1 positions), the router's own choice and
    selection scores. ``choices`` (one ``[T, k]`` an expert layer) forces
    the experts."""
    forced = iter(choices) if choices is not None else None
    pick = lambda i: next(forced) if forced is not None and i >= m["first_k_dense_replace"] else None
    routed = []
    x = p["model.embed_tokens.weight"][tokens]
    for i in range(m["num_hidden_layers"]):
        x, r = jax.checkpoint(lambda p, x, c, i=i: block(p, i, x, m, c))(p, x, pick(i))
        routed += [r] if r is not None else []
    z = None
    if m["num_nextn_predict_layers"]:
        i = m["num_hidden_layers"]
        pre, eps = f"model.layers.{i}.", m["rms_norm_eps"]
        emb = p["model.embed_tokens.weight"][jnp.roll(tokens, -1)]
        joined = _in_row_blocks(lambda emb, x: _lin(jnp.concatenate(
            [_norm(emb, p[pre + "enorm.weight"], eps), _norm(x, p[pre + "hnorm.weight"], eps)],
            axis=-1), p[pre + "eh_proj.weight"]), emb, x)
        z, r = jax.checkpoint(lambda p, x, c: block(p, i, x, m, c))(
            p, joined, None if forced is None else next(forced))
        routed.append(tuple(a[:-1] for a in r))  # the last position has no next token
    return x, z, routed


@_highest
def logits(p, y, m, norm="model.norm.weight"):
    return _lin(_norm(y, p[norm], m["rms_norm_eps"]), p["lm_head.weight"])


def _ce(p, y, targets, m, norm):
    """Mean cross-entropy of ``targets [n]`` under the logits of the first n
    rows of ``y``."""
    def nll(y, targets):
        lg = logits(p, y, m, norm)
        return jax.nn.logsumexp(lg, axis=-1) - jnp.take_along_axis(
            lg, targets[:, None], axis=-1)[:, 0]

    padded = jnp.pad(targets, (0, y.shape[0] - targets.shape[0]))
    return jnp.mean(_in_row_blocks(nll, y, padded)[:targets.shape[0]])


@_highest
def outputs(p, tokens, m, choices=None, at=None):
    """``((CE_main, CE_mtp), logits, routed)`` of one sequence: the two
    losses, the logits at the positions ``at`` (``None`` without) and
    :func:`hidden`'s ``routed``."""
    y, z, routed = hidden(p, tokens, m, choices)
    ce_main, ce_mtp = _ce(p, y, tokens[1:], m, "model.norm.weight"), jnp.float32(0)
    if z is not None:
        norm = f"model.layers.{m['num_hidden_layers']}.shared_head.norm.weight"
        ce_mtp = _ce(p, z, tokens[2:], m, norm)
    return (ce_main, ce_mtp), None if at is None else logits(p, y[at], m), routed


def losses(p, tokens, m, choices=None):
    """``(CE_main, CE_mtp)`` of one sequence."""
    return outputs(p, tokens, m, choices)[0]


def loss_and_outputs(p, batch, m, choices=None, at=None):
    """Mean over the sequences of ``batch [B, T]`` of ``CE_main + weight x
    CE_mtp``, and every sequence's :func:`outputs`; ``choices`` (per
    sequence, per expert layer) forces the experts."""
    outs = [outputs(p, batch[b], m, None if choices is None else choices[b], at)
            for b in range(batch.shape[0])]
    total = sum(ce_main + m["mtp_loss_weight"] * ce_mtp for (ce_main, ce_mtp), _, _ in outs)
    return total / batch.shape[0], outs


def loss(p, batch, m, choices=None):
    """The mean loss alone: ``jax.grad`` of this is the reference gradient."""
    return loss_and_outputs(p, batch, m, choices)[0]
