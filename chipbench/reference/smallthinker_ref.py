"""The plain reference for the ``smallthinker-21b-a3b`` configuration: a
decoder with grouped-query attention, window (RoPE) and full (no position
encoding) layers in one model, a router that reads the layer's input before
attention, and ReGLU experts, written from its equations in ``jax.numpy``,
float32 at precision ``highest``, with no kernels, no bfloat16, no tiling of
the causal triangle and no dispatch: every held expert is applied to all
tokens and masked by who chose it. It imports nothing from
``shared_tensor_tpu``.

``model`` is the configuration file's dict (the published keys, plus
``experts_held`` = [first, count]); ``params`` a dict keyed by the
checkpoint's tensor names, ``[out, in]`` matrices. For x ``[T, hidden]`` of
one sequence, layer l:

- RMSNorm(x) = x / sqrt(mean(x^2) + eps) * w.
- g = x W_r (the layer's input, un-normed); chosen = top k of g; w = softmax
  over the k chosen logits.
- q = n1 W_q in ``num_attention_heads`` heads, k = n1 W_k and v = n1 W_v in
  ``num_key_value_heads`` heads of ``head_dim``, n1 = RMSNorm(x); query head i
  reads K/V head i // (heads / kv heads). Where ``rope_layout[l]``: RoPE in
  the half-split layout (dimension i pairs with i + d/2, angle t
  theta^(-2i/d)) on q and k. Scores / sqrt(head_dim) under an explicit mask:
  key j for query i iff j <= i and, where ``sliding_window_layout[l]``,
  i - j < ``sliding_window_size``. h = x + concat_h(P v) W_o.
- y = h + sum_{e chosen and held} w_e W_down,e(relu(W_gate,e n2) * W_up,e n2),
  n2 = RMSNorm(h). What absent experts would add is left out.
- loss = CE(token t+1 | RMSNorm(y_L) W_head) over the held rows.

At the chip's sizes what is computed row by row goes in blocks of ``ROWS``
rows one after the other, attention in blocks of ``ROWS`` queries against all
T keys (the mask a ``[ROWS, T]`` array), one K/V head's group of query heads
at a time, the experts one after the other in a loop over their stacked
weights; each block and each layer is made again in the backward pass so that
the gradient fits. None of that changes the arithmetic of a position. The
loops are there for the compiler too: a float32 product at precision
``highest`` costs the TPU's compiler seconds a distinct shape outside a loop.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

ROWS = 1024


def _highest(fn):
    """``fn`` traced with every product at precision ``highest`` (on a TPU a
    float32 product is otherwise made of bfloat16 passes)."""
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)
    return wrapped


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


def rope(x, theta, pos):
    """``x [T, H, d]``: dimension i < d/2 and dimension i + d/2 of position
    ``pos[t]`` turned by the angle pos theta^(-2i/d), as one complex
    multiplication a pair."""
    d = x.shape[-1]
    ang = pos[:, None] * (theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d))[None, :]
    z = (x[..., : d // 2] + 1j * x[..., d // 2:]) * jnp.exp(1j * ang)[:, None, :]
    return jnp.concatenate([z.real, z.imag], axis=-1)


def attention(q, k, v, window):
    """Softmax attention of ``q [T, H, d]`` over ``k, v [T, H_kv, d]`` under
    the mask "key at or before the query and, with a ``window``, fewer than
    ``window`` positions before it"; query head i reads K/V head i // (H /
    H_kv)."""
    n, h, d = q.shape
    h_kv = k.shape[1]
    q = jnp.swapaxes(q, 0, 1).reshape(h_kv, h // h_kv, n, d)
    keys = jnp.arange(n)

    def group(qkv):
        qg, kh, vh = qkv  # [G, T, d], [T, d], [T, d]

        def rows(q_rows, first):
            s = jnp.matmul(q_rows, kh.T) / jnp.sqrt(jnp.float32(d))  # [G, rows, T]
            i = first + jnp.arange(q_rows.shape[1])
            seen = keys[None, :] <= i[:, None]
            if window is not None:
                seen = seen & (i[:, None] - keys[None, :] < window)
            return jnp.matmul(jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1), vh)

        if n <= ROWS or n % ROWS:
            return rows(qg, 0)
        blocks = jnp.swapaxes(qg.reshape(qg.shape[0], n // ROWS, ROWS, d), 0, 1)
        out = jax.lax.map(jax.checkpoint(lambda a: rows(*a)),
                          (blocks, jnp.arange(0, n, ROWS)))
        return jnp.swapaxes(out, 0, 1).reshape(qg.shape[0], n, -1)

    out = jax.lax.map(group, (q, jnp.swapaxes(k, 0, 1), jnp.swapaxes(v, 0, 1)))
    return jnp.swapaxes(out.reshape(h, n, -1), 0, 1)


def self_attention(p, pre, x, m, layer):
    n, h, h_kv, d = x.shape[0], m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    turned = bool(m["rope_layout"][layer])

    def qkv(x, pos):
        q = _lin(x, p[pre + "q_proj.weight"]).reshape(-1, h, d)
        k = _lin(x, p[pre + "k_proj.weight"]).reshape(-1, h_kv, d)
        v = _lin(x, p[pre + "v_proj.weight"]).reshape(-1, h_kv, d)
        if turned:
            q, k = rope(q, m["rope_theta"], pos), rope(k, m["rope_theta"], pos)
        return q, k, v

    q, k, v = _in_row_blocks(qkv, x, jnp.arange(n, dtype=jnp.float32))
    window = m["sliding_window_size"] if m["sliding_window_layout"][layer] else None
    return _in_row_blocks(lambda o: _lin(o.reshape(-1, h * d), p[pre + "o_proj.weight"]),
                          attention(q, k, v, window))


def route(p, pre, x, m, chosen=None):
    """``(idx, w, own, g)``: the experts used (``chosen [T, k]`` if given, else
    the router's own top k), their weights (a softmax over the logits of the
    experts used), the router's own choice and its logits."""
    g = _lin(x, p[pre + "primary_router.weight"])
    own = jax.lax.top_k(g, m["moe_num_active_primary_experts"])[1]
    idx = own if chosen is None else chosen
    return idx, jax.nn.softmax(jnp.take_along_axis(g, idx, axis=1), axis=-1), own, g


def reglu(u, gate, up, down):
    return _lin(jax.nn.relu(_lin(u, gate)) * _lin(u, up), down)


def held_experts(p, pre, u, idx, w, m):
    """``sum_{e chosen and held} w_e E_e(u)``: every held expert on all rows,
    weighted by 0 where it was not chosen."""
    first, count = m["experts_held"]
    held = jnp.arange(first, first + count)
    stack = lambda name: jnp.stack(
        [p[pre + f"experts.{e}.{name}.weight"] for e in range(first, first + count)])

    def one(out, ew):
        e, gate, up, down = ew
        w_e = jnp.sum(jnp.where(idx == e, w, 0.0), axis=1)
        return out + w_e[:, None] * reglu(u, gate, up, down), None

    return jax.lax.scan(one, jnp.zeros_like(u), (held, stack("gate"), stack("up"), stack("down")))[0]


def block(p, i, x, m, chosen=None):
    pre, eps = f"model.layers.{i}.", m["rms_norm_eps"]
    idx, w, own, g = _in_row_blocks(
        lambda x, c: route(p, pre + "block_sparse_moe.", x, m, c), x, chosen)
    h = x + self_attention(
        p, pre + "self_attn.", _norm(x, p[pre + "input_layernorm.weight"], eps), m, i)
    f = _in_row_blocks(
        lambda h, idx, w: held_experts(
            p, pre + "block_sparse_moe.",
            _norm(h, p[pre + "post_attention_layernorm.weight"], eps), idx, w, m),
        h, idx, w)
    return h + f, (own, g)


@_highest
def hidden(p, tokens, m, choices=None):
    """``(y_L, routed)`` of one sequence: the last layer's output and, one
    entry a layer, the router's own choice and its logits. ``choices`` (one
    ``[T, k]`` a layer) forces the experts."""
    routed = []
    x = p["model.embed_tokens.weight"][tokens]
    for i in range(m["num_hidden_layers"]):
        x, r = jax.checkpoint(lambda p, x, c, i=i: block(p, i, x, m, c))(
            p, x, None if choices is None else choices[i])
        routed.append(r)
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
    every sequence's :func:`outputs`; ``choices`` (per sequence, per layer)
    forces the experts."""
    outs = [outputs(p, batch[b], m, None if choices is None else choices[b], at)
            for b in range(batch.shape[0])]
    return sum(ce for ce, _, _ in outs) / batch.shape[0], outs


def loss(p, batch, m, choices=None):
    """The mean loss alone: ``jax.grad`` of this is the reference gradient."""
    return loss_and_outputs(p, batch, m, choices)[0]
