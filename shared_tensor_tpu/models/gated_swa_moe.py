"""A decoder whose attention layers differ in width as well as in reach
(Laguna-S-2.1's ``config.json`` keys) as pure functions on a parameter
pytree: grouped-query attention that is, layer by layer, full (48 query
heads; RoPE on the first half of a head, YaRN's stretched frequencies and
attention factor) or over a sliding window (72 query heads; plain RoPE on the
whole head), every head's output scaled by a sigmoid gate of the layer's
input before ``o_proj``; a dense SwiGLU first layer, then expert layers whose
router is a softmax over all experts' logits, the top k renormalised and
scaled, beside an ungated shared expert.

One layer, for ``x [T, hidden]`` and the layer's own ``H`` query heads::

    n1 = RMSNorm(x);  a = Attn_l(rope_l(W_q n1), rope_l(W_k n1), W_v n1)    [T, H, head_dim]
    h  = x + W_o concat_h(sigmoid(W_g n1)_h a_h)                            (the gate float32)
    s  = softmax(W_r n2);  idx = top_k(s);  w = scale s[idx] / sum(s[idx]),  n2 = RMSNorm(h)
    y  = h + shared(n2) + sum_{e in idx, e held} w_e down_e(silu(gate_e n2) * up_e n2)

(layer 0: ``y = h + down(silu(gate n2) * up n2)`` at ``intermediate_size``).

The pytree is a flat dict keyed by the checkpoint's tensor names, one leaf a
tensor in ``[out, in]`` shape, as the two other decoders'. Nothing they have
is written again here: the grouped projections, the partial rotation, the
gate and the attention entry point are ``swa_moe.attention``'s (given this
layer's heads and tables), the dense layer ``mla_moe.swiglu``, the expert
layer ``mla_moe.moe`` (given this router and the shared expert's name), norm,
head, loss and the sequence loop ``swa_moe.loss_fn``'s (given this trunk).
What is new is the frequency law, the router and the layer plan. Precision is
theirs: bfloat16 operands with float32 accumulation; router, norms, RoPE,
softmax, gate, loss and the residual stream float32. Each layer runs under
``mla_moe.layer_checkpoint`` and keeps its attention's q, k, v and output.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import swa_moe
from .mla_moe import (
    _layer, _router_logits, _sub, layer_checkpoint, moe, rms_norm, rope_tables, swiglu,
)

_PERIOD = ("full_attention", "sliding_attention", "sliding_attention", "sliding_attention")
_ROPE = {
    "full_attention": {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
        "original_max_position_embeddings": 8192, "beta_slow": 1, "beta_fast": 32,
        "attention_factor": 1.4852030263919618, "partial_rotary_factor": 0.5},
    "sliding_attention": {
        "rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1},
}


def _frozen(groups) -> tuple:
    """A dict of dicts as sorted tuples, so that a ``Config`` stays hashable."""
    if not isinstance(groups, dict):
        return groups
    return tuple(sorted((kind, tuple(sorted(keys.items()))) for kind, keys in groups.items()))


@dataclasses.dataclass(frozen=True)
class Config:
    """The published keys by their published names, what this chip holds of
    them, and how the products are computed."""

    hidden_size: int = 3072
    intermediate_size: int = 12288  # the dense layers' SwiGLU
    num_hidden_layers: int = 48
    num_attention_heads: int = 48  # the full layers'; a layer reads its own below
    num_key_value_heads: int = 8
    head_dim: int = 128
    attention_bias: bool = False
    rms_norm_eps: float = 1e-6
    num_experts: int = 256
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 1024
    shared_expert_intermediate_size: int = 1024
    norm_topk_prob: bool = True
    moe_routed_scaling_factor: float = 2.5
    moe_router_logit_softcapping: float = 0
    moe_apply_router_weight_on_input: bool = False
    gating: str = "per-head"
    sliding_window: int = 512
    #: a layer each, as published (the layers beyond ``num_hidden_layers``
    #: are not read)
    layer_types: tuple[str, ...] = _PERIOD * 12
    mlp_layer_types: tuple[str, ...] = ("dense",) + ("sparse",) * 47
    num_attention_heads_per_layer: tuple[int, ...] = (48, 72, 72, 72) * 12
    #: a layer kind's RoPE, the published group (given as a dict, kept as
    #: sorted tuples; :meth:`rope` gives a kind's keys back)
    rope_parameters: Any = _frozen(_ROPE)
    vocab_size: int = 100352
    #: (first, count) of the experts held here; the router keeps
    #: ``num_experts`` outputs whatever is held.
    experts_held: tuple[int, int] = (0, 256)
    #: rows of the vocabulary held here: token ids, logits and loss are over them.
    vocab_held: int = 100352
    #: matrices normal(0, ``init_std``) (the gate's too: gates start near
    #: 1/2), the embedding normal(0, 1) as the other decoders'
    init_std: float = 0.006
    compute_dtype: str = "bfloat16"
    attn_block: int = 512  # the scan's tile, where the fused kernels do not run
    loss_block: int = 2048  # tokens a block of logits
    expert_tile: int = 128  # rows a tile of one expert's tokens
    expert_spare: float = 1.5  # see mla_moe.Config.expert_spare

    def __post_init__(self):
        object.__setattr__(self, "rope_parameters", _frozen(self.rope_parameters))
        if (self.gating != "per-head" or self.moe_router_logit_softcapping
                or self.moe_apply_router_weight_on_input or self.attention_bias):
            raise ValueError("written: a per-head gate, no cap on the router's logits, "
                             "the weight on the expert's output, no bias")
        plans = (self.layer_types, self.mlp_layer_types, self.num_attention_heads_per_layer)
        if min(map(len, plans)) < self.num_hidden_layers:
            raise ValueError("a kind, an MLP kind and a head count a layer")

    @property
    def dtype(self):
        return jnp.dtype(self.compute_dtype)

    # the name mla_moe's expert loop reads (routed_experts)
    @property
    def n_routed_experts(self) -> int:
        return self.num_experts

    @property
    def expert_layers(self) -> int:
        return sum(not self.is_dense(i) for i in range(self.num_hidden_layers))

    def is_dense(self, layer: int) -> bool:
        return self.mlp_layer_types[layer] == "dense"

    def heads(self, layer: int) -> int:
        return self.num_attention_heads_per_layer[layer]

    def window(self, layer: int) -> int | None:
        return self.sliding_window if self.layer_types[layer] == "sliding_attention" else None

    def rope(self, kind: str) -> dict:
        return dict(dict(self.rope_parameters)[kind])


def param_shapes(cfg: Config) -> dict[str, tuple[int, ...]]:
    """Every leaf's checkpoint name and shape (``nn.Linear`` is [out, in])."""
    d, hd = cfg.hidden_size, cfg.head_dim
    shapes: dict[str, tuple[int, ...]] = {
        "model.embed_tokens.weight": (cfg.vocab_held, d),
        "model.norm.weight": (d,),
        "lm_head.weight": (cfg.vocab_held, d),
    }

    def mlp(prefix: str, width: int):
        shapes[prefix + "gate_proj.weight"] = (width, d)
        shapes[prefix + "up_proj.weight"] = (width, d)
        shapes[prefix + "down_proj.weight"] = (d, width)

    first, count = cfg.experts_held
    for i in range(cfg.num_hidden_layers):
        p, h = _layer(i), cfg.heads(i)
        shapes[p + "input_layernorm.weight"] = (d,)
        shapes[p + "post_attention_layernorm.weight"] = (d,)
        a = p + "self_attn."
        shapes[a + "q_proj.weight"] = (h * hd, d)
        shapes[a + "k_proj.weight"] = (cfg.num_key_value_heads * hd, d)
        shapes[a + "v_proj.weight"] = (cfg.num_key_value_heads * hd, d)
        shapes[a + "o_proj.weight"] = (d, h * hd)
        shapes[a + "g_proj.weight"] = (h, d)
        if cfg.is_dense(i):
            mlp(p + "mlp.", cfg.intermediate_size)
            continue
        shapes[p + "mlp.gate.weight"] = (cfg.num_experts, d)
        mlp(p + "mlp.shared_expert.", cfg.shared_expert_intermediate_size)
        for e in range(first, first + count):
            mlp(p + f"mlp.experts.{e}.", cfg.moe_intermediate_size)
    return shapes


init_params = partial(swa_moe.init_params, shapes=param_shapes)


def yarn_inv_freq(dim: int, theta: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN's ``dim // 2`` frequencies (the ``transformers`` convention for
    ``rope_type: yarn``), float32 from float64: pair ``i`` turns at ``f_i =
    theta^(-2i/dim)`` where it makes ``beta_fast`` turns or more over the
    ``original`` context (left as trained), at ``f_i / factor`` where it
    makes ``beta_slow`` or fewer (stretched with the context), and between
    the two pairs' indices ``lo`` and ``hi`` at the linear blend of both."""
    f = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    # the pair (a real number) that makes r turns over the original context
    pair = lambda r: dim * math.log(original / (2 * math.pi * r)) / (2 * math.log(theta))
    lo, hi = max(math.floor(pair(beta_fast)), 0), min(math.ceil(pair(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - lo) / max(hi - lo, 1e-3), 0, 1)
    return (f / factor * ramp + f * (1 - ramp)).astype(np.float32)


def layer_rope(cfg: Config, kind: str, n: int):
    """cos, sin ``[n, r / 2]`` of the layers of ``kind``, ``r`` =
    ``partial_rotary_factor`` x ``head_dim`` the width they turn."""
    keys = cfg.rope(kind)
    r = int(cfg.head_dim * keys.get("partial_rotary_factor", 1))
    if keys["rope_type"] == "default":
        return rope_tables(n, r, keys["rope_theta"])
    if keys["rope_type"] != "yarn":
        raise ValueError(f"rope_type {keys['rope_type']!r} is not written")
    inv = yarn_inv_freq(r, keys["rope_theta"], keys["factor"],
                        keys["original_max_position_embeddings"],
                        keys["beta_fast"], keys["beta_slow"])
    return rope_tables(n, r, keys["rope_theta"], inv, keys["attention_factor"])


def route(p: dict, u: jax.Array, cfg: Config):
    """The ``num_experts_per_tok`` experts of every token and their weights,
    ``[T, k]`` each: a float32 softmax over all ``num_experts`` logits, its
    top k, normalised over the k chosen and scaled."""
    w, idx = lax.top_k(jax.nn.softmax(_router_logits(u, p["gate.weight"]), axis=-1),
                       cfg.num_experts_per_tok)
    if cfg.norm_topk_prob:
        w = w / jnp.sum(w, axis=1, keepdims=True)
    return idx, w * cfg.moe_routed_scaling_factor


def block(p: dict, x: jax.Array, rope, cfg: Config, layer: int):
    """Layer ``layer`` with its leaves ``p``; the counters of an expert
    layer, else ``None``."""
    eps = cfg.rms_norm_eps
    with jax.named_scope("st.attn"):
        h = x + swa_moe.attention(
            _sub(p, "self_attn."), rms_norm(x, p["input_layernorm.weight"], eps), rope,
            cfg.window(layer), cfg, cfg.heads(layer))
    u = rms_norm(h, p["post_attention_layernorm.weight"], eps)
    if cfg.is_dense(layer):
        with jax.named_scope("st.ffn"):
            return h + swiglu(_sub(p, "mlp."), u, cfg.dtype), None
    with jax.named_scope("st.moe"):
        f, aux = moe(_sub(p, "mlp."), u, cfg, router=route, shared_name="shared_expert.")
    return h + f, aux


def trunk(params: dict, tokens: jax.Array, cfg: Config):
    """The last layer's output ``[T, hidden]`` (before ``model.norm``) of one
    sequence and the expert layers' counters."""
    kinds = cfg.layer_types[:cfg.num_hidden_layers]
    ropes = {kind: layer_rope(cfg, kind, tokens.shape[0]) for kind in dict.fromkeys(kinds)}
    with jax.named_scope("st.embed"):
        x = params["model.embed_tokens.weight"][tokens]
    auxes = []
    for i, kind in enumerate(kinds):
        fn = layer_checkpoint(partial(block, cfg=cfg, layer=i))
        x, aux = fn(_sub(params, _layer(i)), x, ropes[kind])
        auxes += [aux] if aux is not None else []
    return x, auxes


def loss_fn(params: dict, batch: jax.Array, cfg: Config,
            positions: jax.Array | None = None) -> tuple[jax.Array, Any]:
    """``(loss, aux)`` of ``batch [B, T]`` token ids: ``swa_moe.loss_fn`` over
    this trunk (the next-token cross-entropy over the held slice of the
    vocabulary; ``aux``'s ``moe_*`` one entry an expert layer)."""
    return swa_moe.loss_fn(params, batch, cfg, positions, trunk=trunk)
