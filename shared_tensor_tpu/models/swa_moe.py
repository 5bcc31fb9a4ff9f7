"""A decoder whose attention layers are of two kinds in one model
(SmallThinker-21BA3B-Instruct's ``config.json`` keys, arXiv:2507.20984) as
pure functions on a parameter pytree: grouped-query attention that is, layer
by layer, full and without position encoding (``rope_layout`` /
``sliding_window_layout`` 0) or over a sliding window with RoPE (1); a router
that reads the layer's *input*, before attention; a softmax over the chosen
logits; ReGLU experts in every layer, no dense layer, no shared expert.

One layer, for ``x [T, hidden]``::

    h = x + W_o Attn_l(W_q n1, W_k n1, W_v n1),         n1 = RMSNorm(x)
    g = W_r x;  idx = top_k(g);  w = softmax(g[idx])     (float32, x un-normed)
    y = h + sum_{e in idx, e held} w_e down_e(relu(gate_e n2) * up_e n2),  n2 = RMSNorm(h)

The pytree is a flat dict keyed by the checkpoint's tensor names, one leaf a
tensor in ``[out, in]`` shape, as ``mla_moe``'s; what the two decoders share
is ``mla_moe``'s and imported from there, not copied: the product helper,
RMSNorm, the RoPE tables, the attention entry point (the fused kernels of
``ops/attention_pallas.py`` on the chip, the scan elsewhere, here with a
window and fewer K/V heads than query heads), the dropless expert loop (told
which experts it holds, routing over all of them), the cross-entropy in
blocks of tokens. Precision is ``mla_moe``'s too: bfloat16 operands with
float32 accumulation; router, norms, RoPE, softmax, loss and the residual
stream float32. Each layer runs under ``mla_moe.layer_checkpoint`` and keeps
its attention's q, k, v and output.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from .mla_moe import (
    _layer, _mm, _router_logits, _sub, causal_attention, expert_counters, head_logits,
    head_loss, held_experts, layer_checkpoint, rms_norm, rope_tables,
)

_PERIOD = (0, 1, 1, 1)


@dataclasses.dataclass(frozen=True)
class Config:
    """The published keys by their published names, what this chip holds of
    them, and how the products are computed."""

    hidden_size: int = 2560
    num_hidden_layers: int = 52
    num_attention_heads: int = 28
    num_key_value_heads: int = 4
    head_dim: int = 128
    moe_ffn_hidden_size: int = 768
    moe_num_primary_experts: int = 64
    moe_num_active_primary_experts: int = 6
    moe_primary_router_apply_softmax: bool = True
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1.5e6
    #: a layer each, as published (the layers beyond ``num_hidden_layers``
    #: are not read): 1 = RoPE on q and k / a window of ``sliding_window_size``
    rope_layout: tuple[int, ...] = _PERIOD * 13
    sliding_window_layout: tuple[int, ...] = _PERIOD * 13
    sliding_window_size: int = 4096
    vocab_size: int = 151936
    #: (first, count) of the experts held here; the router keeps
    #: ``moe_num_primary_experts`` outputs whatever is held.
    experts_held: tuple[int, int] = (0, 64)
    #: rows of the vocabulary held here: token ids, logits and loss are over them.
    vocab_held: int = 151936
    #: matrices normal(0, ``init_std``), the embedding normal(0, 1) as
    #: ``mla_moe``'s: the router reads the raw residual stream, whose rows are
    #: the embedding's plus what the layers add, so its logits start ``init_std
    #: x sqrt(hidden)`` = 0.30 apart and differ token by token from layer 0 on
    init_std: float = 0.006
    compute_dtype: str = "bfloat16"
    attn_block: int = 512  # the scan's tile, where the fused kernels do not run
    loss_block: int = 2048  # tokens a block of logits
    expert_tile: int = 128  # rows a tile of one expert's tokens
    expert_spare: float = 1.5  # see mla_moe.Config.expert_spare

    def __post_init__(self):
        if not self.moe_primary_router_apply_softmax:
            raise ValueError("only the softmax router is written")
        if min(len(self.rope_layout), len(self.sliding_window_layout)) < self.num_hidden_layers:
            raise ValueError("a layout entry a layer")

    @property
    def dtype(self):
        return jnp.dtype(self.compute_dtype)

    # the names mla_moe's expert loop reads (routed_experts)
    @property
    def n_routed_experts(self) -> int:
        return self.moe_num_primary_experts

    @property
    def num_experts_per_tok(self) -> int:
        return self.moe_num_active_primary_experts

    @property
    def expert_layers(self) -> int:
        return self.num_hidden_layers

    def window(self, layer: int) -> int | None:
        return self.sliding_window_size if self.sliding_window_layout[layer] else None


def param_shapes(cfg: Config) -> dict[str, tuple[int, ...]]:
    """Every leaf's checkpoint name and shape (``nn.Linear`` is [out, in])."""
    d, hd = cfg.hidden_size, cfg.head_dim
    shapes: dict[str, tuple[int, ...]] = {
        "model.embed_tokens.weight": (cfg.vocab_held, d),
        "model.norm.weight": (d,),
        "lm_head.weight": (cfg.vocab_held, d),
    }
    first, count = cfg.experts_held
    for i in range(cfg.num_hidden_layers):
        p = _layer(i)
        shapes[p + "input_layernorm.weight"] = (d,)
        shapes[p + "post_attention_layernorm.weight"] = (d,)
        a = p + "self_attn."
        shapes[a + "q_proj.weight"] = (cfg.num_attention_heads * hd, d)
        shapes[a + "k_proj.weight"] = (cfg.num_key_value_heads * hd, d)
        shapes[a + "v_proj.weight"] = (cfg.num_key_value_heads * hd, d)
        shapes[a + "o_proj.weight"] = (d, cfg.num_attention_heads * hd)
        m = p + "block_sparse_moe."
        shapes[m + "primary_router.weight"] = (cfg.moe_num_primary_experts, d)
        for e in range(first, first + count):
            shapes[m + f"experts.{e}.gate.weight"] = (cfg.moe_ffn_hidden_size, d)
            shapes[m + f"experts.{e}.up.weight"] = (cfg.moe_ffn_hidden_size, d)
            shapes[m + f"experts.{e}.down.weight"] = (d, cfg.moe_ffn_hidden_size)
    return shapes


def init_params(key: jax.Array, cfg, shapes=param_shapes) -> dict[str, jax.Array]:
    """Matrices normal(0, ``init_std``), the embedding normal(0, 1), norms 1,
    for the leaves ``shapes(cfg)`` names."""
    shapes = shapes(cfg)
    params = {}
    for k, (name, shape) in zip(jax.random.split(key, len(shapes)), sorted(shapes.items())):
        if len(shape) == 1:
            params[name] = jnp.ones(shape, jnp.float32)
        else:
            std = 1.0 if name == "model.embed_tokens.weight" else cfg.init_std
            params[name] = std * jax.random.normal(k, shape, jnp.float32)
    return params


def rope_half(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate the pairs ``(i, i + r/2)`` of the first ``r`` = twice the
    tables' width dimensions of the last axis of ``x [T, H, dim]`` by
    position t's angles (the ``rotate_half`` layout); dimensions from ``r``
    on pass (a partial rotary factor)."""
    x = x.astype(jnp.float32)
    r = 2 * cos.shape[-1]
    a, b, *rest = jnp.split(x, 2 if r == x.shape[-1] else (r // 2, r), axis=-1)
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([a * c - b * s, b * c + a * s, *rest], axis=-1)


def attention(p: dict, x: jax.Array, rope, window: int | None, cfg,
              heads: int | None = None) -> jax.Array:
    """Grouped-query attention of ``x [T, hidden]`` (already normed) over the
    whole causal prefix (``window`` None) or each query's ``window`` newest
    keys, in ``heads`` query heads (``cfg.num_attention_heads`` by default:
    a model may give a layer its own count), with RoPE on q and k where
    ``rope`` is given (tables narrower than half a head turn the head's first
    dimensions only); ``p`` holds the ``self_attn.*`` leaves. Where they hold
    a ``g_proj.weight [heads, hidden]``, every head's output is scaled by the
    sigmoid of its row's product with ``x`` before ``o_proj`` (a headwise
    output gate, arXiv:2505.06708), in float32."""
    n, hd, dt = x.shape[0], cfg.head_dim, cfg.dtype
    heads = heads or cfg.num_attention_heads
    with jax.named_scope("st.attn.proj"):
        q = _mm(x, p["q_proj.weight"], dt).reshape(n, heads, hd)
        k = _mm(x, p["k_proj.weight"], dt).reshape(n, cfg.num_key_value_heads, hd)
        v = _mm(x, p["v_proj.weight"], dt).reshape(n, cfg.num_key_value_heads, hd)
        if rope is not None:
            q, k = rope_half(q, *rope), rope_half(k, *rope)
        q, k, v = q.astype(dt), k.astype(dt), v.astype(dt)
    scope = "st.attn.full" if window is None else "st.attn.window"
    with jax.named_scope(scope):
        o = causal_attention(q, k, v, cfg.attn_block, window, scope=scope)
    if "g_proj.weight" in p:
        with jax.named_scope("st.attn.gate"):
            o = o * jax.nn.sigmoid(_mm(x, p["g_proj.weight"], dt))[:, :, None]
    with jax.named_scope("st.attn.proj"):
        return _mm(o.reshape(n, -1), p["o_proj.weight"], dt)


def route(w_router: jax.Array, x: jax.Array, cfg: Config):
    """The ``moe_num_active_primary_experts`` experts of every token and
    their weights, ``[T, k]`` each: float32 logits of the un-normed ``x``,
    the top k, a softmax over the k chosen logits (after which
    ``norm_topk_prob`` changes nothing)."""
    logits, idx = lax.top_k(_router_logits(x, w_router), cfg.num_experts_per_tok)
    return idx, jax.nn.softmax(logits, axis=-1)


def block(p: dict, x: jax.Array, rope, cfg: Config, window: int | None):
    """One layer with its leaves ``p``: the router on the layer's input,
    attention, the held experts' part; and the expert layer's counters."""
    eps = cfg.rms_norm_eps
    with jax.named_scope("st.moe"), jax.named_scope("st.moe.router"):
        idx, w = route(p["block_sparse_moe.primary_router.weight"], x, cfg)
    with jax.named_scope("st.attn"):
        h = x + attention(_sub(p, "self_attn."), rms_norm(x, p["input_layernorm.weight"], eps),
                          rope, window, cfg)
    u = rms_norm(h, p["post_attention_layernorm.weight"], eps)
    with jax.named_scope("st.moe"):
        f, held = held_experts(_sub(p, "block_sparse_moe."), u, idx, w, cfg, "relu",
                               names=("gate", "up", "down"))
    return h + f, expert_counters(held, cfg)


def trunk(params: dict, tokens: jax.Array, cfg: Config):
    """The last layer's output ``[T, hidden]`` (before ``model.norm``) of one
    sequence and the layers' counters."""
    rope = rope_tables(tokens.shape[0], cfg.head_dim, cfg.rope_theta)
    with jax.named_scope("st.embed"):
        x = params["model.embed_tokens.weight"][tokens]
    auxes = []
    for i in range(cfg.num_hidden_layers):
        fn = layer_checkpoint(partial(block, cfg=cfg, window=cfg.window(i)))
        x, aux = fn(_sub(params, _layer(i)), x, rope if cfg.rope_layout[i] else None)
        auxes.append(aux)
    return x, auxes


def forward(params: dict, tokens: jax.Array, cfg: Config) -> jax.Array:
    """float32 logits ``[T, vocab_held]`` of one sequence ``tokens [T]``:
    position i's row scores token i+1. (The loss never builds this array; it
    is for checks and small inputs.)"""
    y, _ = trunk(params, tokens, cfg)
    return head_logits(y, params["model.norm.weight"], params["lm_head.weight"], cfg)


def _sequence_loss(params: dict, tokens: jax.Array, cfg, trunk):
    n = tokens.shape[0]
    y, auxes = trunk(params, tokens, cfg)
    with jax.named_scope("st.head_loss"):
        ce = head_loss(y, params["model.norm.weight"], params["lm_head.weight"],
                       jnp.roll(tokens, -1), (jnp.arange(n) < n - 1).astype(jnp.float32),
                       cfg) / (n - 1)
    aux = {"ce_main": ce}
    aux.update({name: jnp.stack([a[name] for a in auxes]) for name in auxes[0]})
    return ce, aux, y


def loss_fn(params: dict, batch: jax.Array, cfg, positions: jax.Array | None = None,
            trunk=trunk) -> tuple[jax.Array, Any]:
    """``(loss, aux)`` of ``batch [B, T]`` token ids (documents packed, no
    mask between them): the next-token cross-entropy over the held slice of
    the vocabulary, the mean over the sequences. ``aux`` is ``mla_moe``'s
    without the prediction module's entries: ``ce_main`` and, one entry an
    expert layer, ``moe_pairs_held``, ``moe_load_max_over_mean``,
    ``moe_tokens_unrouted_share``, ``moe_rows_executed``; with ``positions``
    also ``ce_main_of [B]``, ``logits [B, len(positions), vocab_held]`` and
    ``choices [B, expert layers, T, k]``. ``trunk(params, tokens, cfg)`` is
    the decoder under the head: this module's, or another's that ends in the
    same norm, head and loss."""
    outs = [_sequence_loss(params, batch[b], cfg, trunk) for b in range(batch.shape[0])]
    n = len(outs)
    aux = {name: sum(a[name] for _, a, _ in outs) / n
           for name in outs[0][1] if name != "choices"}
    for name in ("moe_pairs_held", "moe_rows_executed"):
        aux[name] = sum(a[name] for _, a, _ in outs)
    if positions is not None:
        aux["logits"] = jnp.stack([head_logits(
            y[positions], params["model.norm.weight"], params["lm_head.weight"], cfg)
            for _, _, y in outs])
        aux["ce_main_of"] = jnp.stack([a["ce_main"] for _, a, _ in outs])
        aux["choices"] = jnp.stack([a["choices"] for _, a, _ in outs])
    return sum(l for l, _, _ in outs) / n, aux
