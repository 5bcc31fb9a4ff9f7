"""A DeepSeek-V3-family decoder (JoyAI-LLM-Flash's ``config.json`` keys) as
pure functions on a parameter pytree: multi-head latent attention with
interleaved partial RoPE, one dense SwiGLU layer, then sigmoid/noaux_tc routed
expert layers with a shared expert, and one multi-token-prediction module.

The pytree is a flat dict keyed by the checkpoint's tensor names
(``model.layers.3.mlp.experts.5.up_proj.weight``), one leaf a tensor in the
checkpoint's ``[out, in]`` shape: a leaf is the sync codec's unit of scale, so
experts are stacked at trace time only. Equations: DeepSeek-V3,
arXiv:2412.19437 (sections 2.1, 2.2). TPU-first choices:

- master parameters are the table's float32; matrix products take
  ``compute_dtype`` operands (bfloat16) with float32 accumulation. The router,
  every RMSNorm, RoPE, softmax and the cross-entropy are float32.
- attention runs over the causal triangle in tiles of queries by keys with
  an online softmax, the loss in blocks of tokens, each layer under
  ``jax.checkpoint``: no ``[heads, T, T]`` tensor and no second ``[T, vocab]``
  array is alive. On the chip (a tpu backend or ``ST_CODEC=pallas``, bfloat16
  operands, a length of whole kernel tiles) the tiles are those of the fused
  kernels of ``ops/attention_pallas.py``, forward and backward, which keep a
  tile's scores in VMEM and size their own tiles; anywhere else (float32
  programs, CPU peers, other lengths) one ``lax.scan`` forward and one
  backward in tiles of ``attn_block``, the same arithmetic at the same
  precision and the kernels' oracle in tests.
- the expert layer is told which experts it holds (``experts_held``), routes
  over all of them and computes its own experts' part, dropless: (token,
  expert) pairs are sorted by expert into tiles of rows padded per expert, and
  a loop runs over the tiles the real counts need, so the work follows the
  pairs that exist. ``PodTrainer`` vmaps the loss over peers and a batched
  trip count would run every peer's loop as long as the longest;
  :func:`_per_example` keeps each peer's call its own program.
- both loops (the attention scan's tiles, the expert layer's) have one body:
  the compiler's time goes by distinct shapes (a second a large product), and
  16 key lengths a layer and five capacities of the expert layer made the step
  take five minutes to compile (chip-free compile, PR 29).
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
from jax.ad_checkpoint import checkpoint_name
from jax.custom_batching import custom_vmap

from ..ops import attention_pallas, moe_pallas
from ..utils.profiling import pod_tier

#: what a layer's checkpoint keeps, written once for every decoder: its
#: attention's ``o`` and ``lse`` and, heads first as the backward rule takes
#: them, its ``q``, ``k`` and ``v`` (:func:`_attention_tiles_fwd` names them)
ATTN_OUT, ATTN_QKV = "attn_out", "attn_qkv"


def layer_checkpoint(fn):
    """``fn`` (one decoder layer) under ``jax.checkpoint``: recomputed in the
    backward pass but for its attention's residuals, so neither the attention
    nor what makes q, k and v (their products, RoPE, the head-first layout)
    runs a second time."""
    return jax.checkpoint(
        fn, policy=jax.checkpoint_policies.save_only_these_names(ATTN_OUT, ATTN_QKV))


@dataclasses.dataclass(frozen=True)
class Config:
    """The published keys by their published names, what this chip holds of
    them, and how the products are computed."""

    hidden_size: int = 2048
    intermediate_size: int = 7168
    moe_intermediate_size: int = 768
    num_hidden_layers: int = 40
    first_k_dense_replace: int = 1
    num_attention_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 32e6
    rms_norm_eps: float = 1e-6
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    num_nextn_predict_layers: int = 1
    vocab_size: int = 129280
    #: (first, count) of the routed experts held here; the router keeps
    #: ``n_routed_experts`` outputs whatever is held.
    experts_held: tuple[int, int] = (0, 256)
    #: rows of the vocabulary held here: token ids, logits and loss are over them.
    vocab_held: int = 129280
    mtp_loss_weight: float = 0.3
    init_std: float = 0.006
    compute_dtype: str = "bfloat16"
    #: queries by keys a tile of the attention scan, the path taken where the
    #: fused kernels are not (``causal_attention``; the kernels size their own
    #: tiles from the shapes and read no field here). The scan on the chip at
    #: 8 192 tokens and 32 heads, forward + backward: 95.0 ms in tiles of 512,
    #: 79.6 in 1 024, 78.4 in 2 048 (my chip run, PR 29); but the step compiles
    #: in 113 s with 512 and in 146 s with 1 024 in the backward pass alone
    #: (chip-free compile)
    attn_block: int = 512
    loss_block: int = 2048  # tokens a block of logits
    expert_tile: int = 128  # rows a tile of one expert's tokens
    #: the expert layer runs at least the tiles of this many times the expected
    #: pairs (tokens x k x held / experts), the spare ones empty: a tile costs
    #: 0.19 ms of a step, and without a floor a step's time followed each
    #: batch's routing by 0.7 % from seed to seed (my chip run, PR 29)
    expert_spare: float = 1.5

    @property
    def dtype(self):
        return jnp.dtype(self.compute_dtype)

    @property
    def n_blocks(self) -> int:
        """Decoder blocks with parameters: the layers and the prediction
        modules (``model.layers.<num_hidden_layers + k>``)."""
        return self.num_hidden_layers + self.num_nextn_predict_layers

    def __post_init__(self):
        if self.num_nextn_predict_layers not in (0, 1):
            raise ValueError("one multi-token-prediction module at most")

    def is_moe(self, layer: int) -> bool:
        return layer >= self.first_k_dense_replace


def _layer(i: int) -> str:
    return f"model.layers.{i}."


def param_shapes(cfg: Config) -> dict[str, tuple[int, ...]]:
    """Every leaf's checkpoint name and shape (``nn.Linear`` is [out, in])."""
    d, h = cfg.hidden_size, cfg.num_attention_heads
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    shapes: dict[str, tuple[int, ...]] = {
        "model.embed_tokens.weight": (cfg.vocab_held, d),
        "model.norm.weight": (d,),
        "lm_head.weight": (cfg.vocab_held, d),
    }

    def mlp(prefix: str, width: int):
        shapes[prefix + "gate_proj.weight"] = (width, d)
        shapes[prefix + "up_proj.weight"] = (width, d)
        shapes[prefix + "down_proj.weight"] = (d, width)

    for i in range(cfg.n_blocks):
        p = _layer(i)
        shapes[p + "input_layernorm.weight"] = (d,)
        shapes[p + "post_attention_layernorm.weight"] = (d,)
        a = p + "self_attn."
        shapes[a + "q_a_proj.weight"] = (cfg.q_lora_rank, d)
        shapes[a + "q_a_layernorm.weight"] = (cfg.q_lora_rank,)
        shapes[a + "q_b_proj.weight"] = (h * qk, cfg.q_lora_rank)
        shapes[a + "kv_a_proj_with_mqa.weight"] = (
            cfg.kv_lora_rank + cfg.qk_rope_head_dim, d)
        shapes[a + "kv_a_layernorm.weight"] = (cfg.kv_lora_rank,)
        shapes[a + "kv_b_proj.weight"] = (
            h * (cfg.qk_nope_head_dim + cfg.v_head_dim), cfg.kv_lora_rank)
        shapes[a + "o_proj.weight"] = (d, h * cfg.v_head_dim)
        if cfg.is_moe(i):
            shapes[p + "mlp.gate.weight"] = (cfg.n_routed_experts, d)
            shapes[p + "mlp.gate.e_score_correction_bias"] = (cfg.n_routed_experts,)
            mlp(p + "mlp.shared_experts.", cfg.moe_intermediate_size * cfg.n_shared_experts)
            first, count = cfg.experts_held
            for e in range(first, first + count):
                mlp(p + f"mlp.experts.{e}.", cfg.moe_intermediate_size)
        else:
            mlp(p + "mlp.", cfg.intermediate_size)
        if i >= cfg.num_hidden_layers:  # a prediction module
            shapes[p + "enorm.weight"] = (d,)
            shapes[p + "hnorm.weight"] = (d,)
            shapes[p + "eh_proj.weight"] = (d, 2 * d)
            shapes[p + "shared_head.norm.weight"] = (d,)
    return shapes


def init_params(key: jax.Array, cfg: Config) -> dict[str, jax.Array]:
    """Matrices normal(0, ``init_std``), the embedding normal(0, 1), norms 1,
    the router's bias 0. With the embedding at ``init_std`` too the blocks'
    outputs (a running mean over the prefix) drown it, every token's hidden
    state is alike and every token routes alike, as no trained model's does."""
    shapes = param_shapes(cfg)
    params = {}
    for k, (name, shape) in zip(jax.random.split(key, len(shapes)), sorted(shapes.items())):
        if name.endswith("e_score_correction_bias"):
            params[name] = jnp.zeros(shape, jnp.float32)
        elif len(shape) == 1:
            params[name] = jnp.ones(shape, jnp.float32)
        else:
            std = 1.0 if name == "model.embed_tokens.weight" else cfg.init_std
            params[name] = std * jax.random.normal(k, shape, jnp.float32)
    return params


# --- the pieces -----------------------------------------------------------------


def _precision(dtype):
    """float32 operands ask for the product at full precision (on a TPU it
    is otherwise made of bfloat16 passes)."""
    return lax.Precision.HIGHEST if dtype == jnp.float32 else None


def _mm(x: jax.Array, w: jax.Array, dtype) -> jax.Array:
    """``x @ w.T`` for a checkpoint-shaped ``w [out, in]``: ``dtype``
    operands, float32 result."""
    dtype = jnp.dtype(dtype)
    return lax.dot_general(
        x.astype(dtype), w.astype(dtype), (((x.ndim - 1,), (1,)), ((), ())),
        precision=_precision(dtype), preferred_element_type=jnp.float32,
    )


def rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope_tables(n: int, dim: int, theta: float, inv_freq=None,
                scale: float = 1.0) -> tuple[jax.Array, jax.Array]:
    """cos, sin ``[n, dim // 2]`` of position x theta^(-2i/dim), or of
    position x ``inv_freq [dim // 2]`` where a model brings its own
    frequencies (stretched ones, say); both times ``scale`` where it is not 1
    (a stretched context's attention factor)."""
    if inv_freq is None:
        inv = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    else:
        inv = jnp.asarray(inv_freq, jnp.float32)
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * inv[None, :]
    if scale != 1.0:
        return jnp.cos(ang) * scale, jnp.sin(ang) * scale
    return jnp.cos(ang), jnp.sin(ang)


def rope_interleaved(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate the pairs ``(2i, 2i+1)`` of the last axis of ``x [T, ..., dim]``
    by position t's angles; the layout is kept."""
    shape = x.shape
    x = x.astype(jnp.float32).reshape(*shape[:-1], shape[-1] // 2, 2)
    extra = (1,) * (len(shape) - 2)
    c = cos.reshape(shape[0], *extra, -1)
    s = sin.reshape(shape[0], *extra, -1)
    a, b = x[..., 0], x[..., 1]
    return jnp.stack([a * c - b * s, a * s + b * c], axis=-1).reshape(shape)


def _tile_pairs(n: int, block: int, window: int | None):
    """The tiles ``(i, j <= i)`` of the causal triangle over ``n`` positions,
    row by row: query tile ``i`` meets key tiles ``0..i`` one after the
    other; with a ``window`` the band's tiles, each row from its diagonal
    back (the kernels' lists, ``attention_pallas.tile_list``)."""
    i, j = np.asarray(attention_pallas.tile_list(n, block, block, False, window), np.int32).T
    return jnp.asarray(i), jnp.asarray(j)


def _rows(x, i, block: int):
    """Tile ``i`` of ``block`` positions of ``x [H, T, ...]``."""
    return lax.dynamic_slice_in_dim(x, i * block, block, axis=1)


def _put_rows(x, rows, i, block: int):
    return lax.dynamic_update_slice_in_dim(x, rows, i * block, axis=1)


def _tile_scores(qi, kj, i, j, block: int, scale: float, window: int | None):
    """Scaled scores ``[H, block, block]`` of query tile ``i`` against key
    tile ``j``, float32, keys after their query (only the diagonal tile has
    any) and keys ``window`` or more before it at ``-inf``."""
    s = jnp.einsum("hqd,hkd->hqk", qi, kj, preferred_element_type=jnp.float32,
                   precision=_precision(qi.dtype)) * scale
    q_pos = i * block + lax.broadcasted_iota(jnp.int32, s.shape[1:], 0)
    k_pos = j * block + lax.broadcasted_iota(jnp.int32, s.shape[1:], 1)
    seen = k_pos <= q_pos
    if window is not None:
        seen &= q_pos - k_pos < window
    return jnp.where(seen, s, -jnp.inf)


def _of_groups(q, k, v):
    """K/V heads repeated to one a query head (query head ``h`` reads K/V
    head ``h // group``), for the scan, which batches over query heads."""
    group = q.shape[0] // k.shape[0]
    return (k, v) if group == 1 else (jnp.repeat(k, group, axis=0), jnp.repeat(v, group, axis=0))


def _attention_fwd_tiles(q, k, v, block: int, window: int | None = None):
    """``(o [H, T, dv], lse [H, T])`` of ``q [H, T, dq]``, ``k [H_kv, T,
    dq]``, ``v [H_kv, T, dv]``: one loop over the causal triangle's tiles
    (the band's, with a ``window``) with a running row maximum, denominator
    and numerator (the online softmax), so the program holds one tile's
    scores and one loop body whatever the length."""
    k, v = _of_groups(q, k, v)
    h, n, _ = q.shape
    scale = 1.0 / math.sqrt(q.shape[-1])
    prec = _precision(v.dtype)

    def tile(carry, ij):
        top, den, num = carry
        i, j = ij
        s = _tile_scores(_rows(q, i, block), _rows(k, j, block), i, j, block, scale, window)
        top_i, den_i, num_i = (_rows(a, i, block) for a in carry)
        # the maximum as an operation of its own: fused into the exp pass XLA
        # makes it a reduce-window over every element (47 ms a pass where the
        # pass takes 1.5; my chip run, PR 29)
        new_top = lax.optimization_barrier(jnp.maximum(top_i, jnp.max(s, axis=-1)))
        shrink = jnp.exp(top_i - new_top)
        e = jnp.exp(s - new_top[..., None])
        den_i = den_i * shrink + jnp.sum(e, axis=-1)
        num_i = num_i * shrink[..., None] + jnp.einsum(
            "hqk,hkd->hqd", e.astype(v.dtype), _rows(v, j, block),
            preferred_element_type=jnp.float32, precision=prec)
        carry = tuple(_put_rows(a, b, i, block) for a, b in
                      zip(carry, (new_top, den_i, num_i)))
        return carry, None

    start = (jnp.full((h, n), -jnp.inf, jnp.float32), jnp.zeros((h, n), jnp.float32),
             jnp.zeros((h, n, v.shape[-1]), jnp.float32))
    (top, den, num), _ = lax.scan(tile, start, _tile_pairs(n, block, window))
    return (num / den[..., None]).astype(v.dtype), top + jnp.log(den)


def _attention_bwd_tiles(q, k, v, o, lse, g, block: int, window: int | None = None):
    """Cotangents of ``(q, k, v)`` for the cotangent ``g`` of ``o``: the same
    loop, every tile's probabilities made again from ``lse``; a group's
    ``dk``, ``dv`` added in float32."""
    h_kv = k.shape[0]
    k, v = _of_groups(q, k, v)
    n = q.shape[1]
    scale = 1.0 / math.sqrt(q.shape[-1])
    prec = _precision(v.dtype)
    mm = partial(jnp.einsum, preferred_element_type=jnp.float32, precision=prec)
    # sum_k p dp, a row: what the softmax's normalisation takes back
    drop = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)

    def tile(carry, ij):
        dq, dk, dv = carry
        i, j = ij
        qi, kj, vj, gi = _rows(q, i, block), _rows(k, j, block), _rows(v, j, block), _rows(g, i, block)
        s = _tile_scores(qi, kj, i, j, block, scale, window)
        p = jnp.exp(s - _rows(lse, i, block)[..., None])
        dp = mm("hqd,hkd->hqk", gi, vj)
        ds = (p * (dp - _rows(drop, i, block)[..., None]) * scale).astype(q.dtype)
        dv = _put_rows(dv, _rows(dv, j, block) + mm("hqk,hqd->hkd", p.astype(v.dtype), gi), j, block)
        dq = _put_rows(dq, _rows(dq, i, block) + mm("hqk,hkd->hqd", ds, kj), i, block)
        dk = _put_rows(dk, _rows(dk, j, block) + mm("hqk,hqd->hkd", ds, qi), j, block)
        return (dq, dk, dv), None

    start = tuple(jnp.zeros(a.shape, jnp.float32) for a in (q, k, v))
    (dq, dk, dv), _ = lax.scan(tile, start, _tile_pairs(n, block, window))
    if h_kv != q.shape[0]:
        dk, dv = (jnp.sum(a.reshape(h_kv, -1, *a.shape[1:]), axis=1) for a in (dk, dv))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _attention_o_lse(q, k, v, block: int | None, window: int | None):
    """``(o, lse)`` by the scan in tiles of ``block``, or by the fused
    kernels (``ops/attention_pallas.py``, which size their own tiles) where
    ``block`` is None."""
    if block is None:
        return attention_pallas.attention_fwd(q, k, v, window=window)
    return _attention_fwd_tiles(q, k, v, block, window)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _attention_tiles(q, k, v, block: int | None, window: int | None, scope: str):
    return _attention_o_lse(q, k, v, block, window)[0]


def _attention_tiles_fwd(q, k, v, block, window, scope):
    # the residuals are what a layer's checkpoint keeps (:func:`layer_checkpoint`;
    # :func:`_saved_bytes` of them a layer), so that recomputing the layer runs
    # neither the attention a third time nor q's, k's and v's making a second
    q, k, v = (checkpoint_name(a, ATTN_QKV) for a in (q, k, v))
    o, lse = (checkpoint_name(a, ATTN_OUT) for a in _attention_o_lse(q, k, v, block, window))
    return o, (q, k, v, o, lse)


def _attention_tiles_bwd(block, window, scope, res, g):
    with jax.named_scope(scope):  # the caller's: a backward rule is traced outside it
        if block is None:
            return attention_pallas.attention_bwd(*res, g, window=window)
        return _attention_bwd_tiles(*res, g, block, window)


_attention_tiles.defvjp(_attention_tiles_fwd, _attention_tiles_bwd)


def _saved_bytes(q, k, v) -> int:
    """Bytes of :func:`_attention_tiles_fwd`'s residuals ``(q, k, v, o, lse)``
    by shape and dtype (the device pads a last dimension to whole lanes)."""
    o = jax.ShapeDtypeStruct((*q.shape[:2], v.shape[-1]), v.dtype)
    lse = jax.ShapeDtypeStruct(q.shape[:2], jnp.float32)
    return sum(a.size * a.dtype.itemsize for a in (q, k, v, o, lse))


def causal_attention(q, k, v, block: int, window: int | None = None,
                     scope: str = "st.mla.attn"):
    """softmax(q k^T / sqrt(dq)) v with a causal mask, ``q [T, H, .]`` and
    ``k, v [T, H_kv, .]`` (query head ``h`` reads K/V head ``h // (H //
    H_kv)``), over the causal triangle's tiles only or, with a ``window``
    shorter than the sequence, over the tiles of the band of each query's
    ``window`` newest keys, its own among them; each tile's scores are made
    again in the backward pass, so no ``[H, T, T]`` tensor exists. ``scope``
    is the ``jax.named_scope`` the caller wraps this call in, opened again
    around the backward pass. Two paths, one precision (bfloat16 operands,
    float32 accumulation and softmax):

    - the fused kernels of ``ops/attention_pallas.py`` where they run
      (:func:`attention_pallas.takes`: a tpu backend or ``ST_CODEC=pallas``,
      bfloat16 operands, whole kernel tiles): a tile's scores stay in VMEM;
    - else the scan in tiles of ``block`` queries by ``block`` keys, one loop
      forward and one backward: float32 programs, CPU peers, other lengths,
      and the kernels' oracle. The compiler sees one tile shape, not one a
      query block (16 key lengths at 8 192 tokens cost 100 s of compilation;
      chip-free compile, PR 29).

    Which one was traced is counted (``st_attn_traces_total{path}`` and
    ``{kind}``), and the tiles its forward pass lists
    (``st_attn_tiles_listed{kind}``) for how many query heads
    (``st_attn_heads{kind}``), and the bytes it names for its layer's
    checkpoint (``st_attn_saved_bytes{kind}``)."""
    n = q.shape[0]
    window = window if window is not None and window < n else None
    q, k, v = (jnp.swapaxes(a, 0, 1) for a in (q, k, v))  # heads first
    block = None if attention_pallas.takes(q, k, v) else min(block, n)
    if block and n % block:
        raise ValueError(f"{n} positions do not divide into tiles of {block}")
    bq, bk = (block, block) if block else attention_pallas._fwd_tiles(n)
    pod_tier().count_attention_trace(
        "scan" if block else "pallas", "full" if window is None else "window",
        len(attention_pallas.tile_list(n, bq, bk, False, window)), heads=q.shape[0],
        saved_bytes=_saved_bytes(q, k, v))
    return jnp.swapaxes(_attention_tiles(q, k, v, block, window, scope), 0, 1)


def mla(p: dict, x: jax.Array, rope, cfg: Config) -> jax.Array:
    """Multi-head latent attention of ``x [T, hidden]`` (already normed);
    ``p`` holds the ``self_attn.*`` leaves."""
    n, h, dt = x.shape[0], cfg.num_attention_heads, cfg.dtype
    nope, rd, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    cos, sin = rope
    with jax.named_scope("st.mla.proj"):
        c_q = rms_norm(_mm(x, p["q_a_proj.weight"], dt), p["q_a_layernorm.weight"],
                       cfg.rms_norm_eps)
        q = _mm(c_q, p["q_b_proj.weight"], dt).reshape(n, h, nope + rd)
        kv_a = _mm(x, p["kv_a_proj_with_mqa.weight"], dt)
        c_kv = rms_norm(kv_a[:, : cfg.kv_lora_rank], p["kv_a_layernorm.weight"],
                        cfg.rms_norm_eps)
        k_r = rope_interleaved(kv_a[:, None, cfg.kv_lora_rank:], cos, sin)
        kv = _mm(c_kv, p["kv_b_proj.weight"], dt).reshape(n, h, nope + vd)
        q = jnp.concatenate(
            [q[..., :nope], rope_interleaved(q[..., nope:], cos, sin)], axis=-1
        ).astype(dt)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_r, (n, h, rd))], axis=-1
        ).astype(dt)
        v = kv[..., nope:].astype(dt)
    with jax.named_scope("st.mla.attn"):
        o = causal_attention(q, k, v, cfg.attn_block)
    with jax.named_scope("st.mla.proj"):
        return _mm(o.reshape(n, h * vd), p["o_proj.weight"], dt)


def swiglu(p: dict, u: jax.Array, dt) -> jax.Array:
    """``down(silu(gate u) * up u)``; ``p`` holds the three ``*_proj.weight``."""
    a = _mm(u, p["gate_proj.weight"], dt)
    b = _mm(u, p["up_proj.weight"], dt)
    return _mm(jax.nn.silu(a) * b, p["down_proj.weight"], dt)


# --- the expert layer ---------------------------------------------------------


def _router_logits(u: jax.Array, w: jax.Array) -> jax.Array:
    return _mm(u, w, jnp.float32)


def route(p: dict, u: jax.Array, cfg: Config):
    """The ``num_experts_per_tok`` experts of every token and their weights,
    ``[T, k]`` each: sigmoid scores, the top k of score + bias (one group, so
    no group limit), weights normalised over all k chosen and scaled. The
    bias takes no gradient."""
    s = jax.nn.sigmoid(_router_logits(u, p["gate.weight"]))
    bias = lax.stop_gradient(p["gate.e_score_correction_bias"])
    _, idx = lax.top_k(s + bias, cfg.num_experts_per_tok)
    w = jnp.take_along_axis(s, idx, axis=1)
    if cfg.norm_topk_prob:
        w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20)
    return idx, w * cfg.routed_scaling_factor


def _per_example(fn):
    """``fn`` with its own rule under ``jax.vmap``: every example runs ``fn``
    unbatched (a mapped axis of one is squeezed, a longer one looped over).
    A batched trip count would make every example's loop run as long as the
    longest, each tile's slices gathers."""
    wrapped = custom_vmap(fn)

    @wrapped.def_vmap
    def _rule(axis_size, in_batched, *args):
        args = jax.tree.map(
            lambda a, b: a if b else jnp.broadcast_to(a, (axis_size, *a.shape)),
            args, tuple(in_batched))
        if axis_size == 1:
            out = fn(*jax.tree.map(lambda a: a[0], args))
            out = jax.tree.map(lambda o: o[None], out)
        else:
            out = lax.map(lambda a: fn(*a), args)
        return out, jax.tree.map(lambda _: True, out)

    return wrapped


def _expert_operand(x: jax.Array, dt) -> jax.Array:
    return x.astype(dt)


#: the gate's activation of a gated expert, by the name a configuration gives
#: it: SwiGLU's and ReGLU's
ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def _tile_ffn(x, wg, wu, wd, w_row, dt, act):
    """One expert's weighted gated unit (``down(act(gate x) * up x)``) of one
    tile's rows: ``x [tile, hidden]``, ``wg``/``wu [F, hidden]``, ``wd
    [hidden, F]``, ``w_row [tile]``."""
    a = _mm(_expert_operand(x, dt), _expert_operand(wg, dt), dt)
    b = _mm(_expert_operand(x, dt), _expert_operand(wu, dt), dt)
    y = _mm(_expert_operand(act(a) * b, dt), _expert_operand(wd, dt), dt)
    return y * w_row[:, None]


def _tiles_to_run(counts, n_tokens: int, cfg):
    """``(run, most)``: the tiles of ``expert_tile`` rows the expert layer's
    loop runs for these ``counts [held]`` (every expert's pairs padded to
    whole tiles; no fewer than ``expert_spare`` x the expected pairs would
    need), and the most any counts can need (every pair held)."""
    held, tile = counts.shape[0], cfg.expert_tile
    pairs = n_tokens * cfg.num_experts_per_tok
    most = -(-pairs // tile) + held
    least = min(most, math.ceil(
        cfg.expert_spare * pairs * held / cfg.n_routed_experts / tile) + held)
    return jnp.maximum(jnp.sum((counts + tile - 1) // tile), least), most


def _tile_table(w_pair, order, counts, n_tokens: int, cfg):
    """Where every row of every tile comes from. The (token, slot) pairs
    ``order [T*k]`` lists sorted by held expert (the pairs of absent experts
    last; ``counts [held]`` how many each expert has) are laid out in tiles of
    ``expert_tile`` rows, every expert's pairs padded to whole tiles, so one
    tile is one expert's. For the most tiles any counts can need (every pair
    held, every expert's last tile part empty): the tile's expert ``[tiles]``
    and, ``[tiles, tile]`` each, the row's pair, token, weight (0 on padding)
    and whether it is a pair at all (a tile's ``live`` pairs come first); and
    the tiles to run (:func:`_tiles_to_run`)."""
    held, tile = counts.shape[0], cfg.expert_tile
    run, most = _tiles_to_run(counts, n_tokens, cfg)
    tiles_of = (counts + tile - 1) // tile
    tile_end = jnp.cumsum(tiles_of)
    t = jnp.arange(most, dtype=jnp.int32)
    e = jnp.minimum(jnp.sum(t[:, None] >= tile_end[None, :], axis=1), held - 1).astype(jnp.int32)
    first_pair = jnp.cumsum(counts) - counts
    off = (t - (tile_end - tiles_of)[e])[:, None] * tile + jnp.arange(tile)[None, :]
    valid = (t < tile_end[-1])[:, None] & (off < counts[e][:, None])
    pair = order[jnp.where(valid, first_pair[e][:, None] + off, 0)]
    return dict(expert=e, pair=pair, token=pair // cfg.num_experts_per_tok,
                weight=jnp.where(valid, w_pair[pair], 0.0), valid=valid,
                live=jnp.sum(valid, axis=1, dtype=jnp.int32)), run


def _token_major(shape) -> tuple:
    """The shape the tile loop carries a ``[T, hidden]`` float32 accumulator
    in: ``[T, hidden / 128, 128]`` where ``hidden`` is whole lanes (and ``T``
    whole sublane groups, for the way back), so that a token's row is (8,
    128) tiles of its own. In two dimensions a row shares its tiles with the
    seven other rows of its sublane group: XLA's scatter-add reads and writes
    all eight to change one (0.139 ms a tile of 512 rows of 2 560 floats; my
    chip run, PR 34), and Mosaic refuses to copy one."""
    t, d = shape
    return (t, d // 128, 128) if d % 128 == 0 and t % 8 == 0 else (t, d)


def _two_dimensional(acc: jax.Array) -> jax.Array:
    """A :func:`_token_major` accumulator as ``[T, hidden]`` again, once,
    after the loop: eight tokens' ``[8, S, 128]`` turned to ``[S, 8, 128]``,
    which are ``[T, hidden]``'s own (8, 128) tiles in their order, so the one
    transposition is the whole relayout and the reshape after it a bitcast.
    Behind barriers: left to itself XLA goes through two transpositions of
    the whole array (tokens to the lanes and back), or moves the reshape past
    the sums that read the result and lays those out token-major too
    (chip-free compile, PR 34)."""
    if acc.ndim == 2:
        return acc
    t, s, lanes = acc.shape
    with jax.named_scope("st.moe.combine"):
        tiles = lax.optimization_barrier(acc.reshape(t // 8, 8, s, lanes).transpose(0, 2, 1, 3))
        return lax.optimization_barrier(tiles.transpose(0, 2, 1, 3).reshape(t, s * lanes))


def _add_rows(acc: jax.Array, y: jax.Array, row: dict) -> jax.Array:
    """``acc`` with one tile's rows ``y [tile, hidden]`` added to their tokens'
    rows, one add a row: by the kernel of ``ops/moe_pallas.py`` where it runs
    (a :func:`_token_major` accumulator where the codec's kernels run), else
    by XLA's scatter-add, the kernel's twin. A padding row adds a zero to
    the table's first token, or is skipped. Which was traced is counted
    (``st_moe_combine_traces_total{path}``)."""
    kernel = moe_pallas.takes(acc, y)
    pod_tier().count_combine_trace("pallas" if kernel else "xla")
    if kernel:
        return moe_pallas.combine_rows(acc, y, row["token"], row["live"])
    return acc.at[row["token"]].add(y.reshape(-1, *acc.shape[1:]))


def _routed_impl(cfg, act: str, u, wg, wu, wd, w_pair, order, counts):
    """The held experts' part of the layer for ``u [T, hidden]``:
    ``wg``/``wu [held, F, hidden]``, ``wd [held, hidden, F]``, ``w_pair
    [T*k]`` every pair's weight. A loop over the tiles the real counts need
    (:func:`_tile_table`), each tile a product with its own expert's weights:
    the work follows the pairs that exist, and the program holds one tile's
    shape. The sum is carried :func:`_token_major` and brought back to ``[T,
    hidden]`` once, after the loop."""
    with jax.named_scope("st.moe.dispatch"):
        table, n_tiles = _tile_table(w_pair, order, counts, u.shape[0], cfg)

    def tile(t, out):
        row = jax.tree.map(lambda a: a[t], table)
        with jax.named_scope("st.moe.dispatch"):
            x = u[row["token"]]
        with jax.named_scope("st.moe.experts"):
            e = row["expert"]
            y = _tile_ffn(x, wg[e], wu[e], wd[e], row["weight"], cfg.dtype, ACTIVATIONS[act])
        with jax.named_scope("st.moe.combine"):
            return _add_rows(out, y, row)

    return _two_dimensional(lax.fori_loop(
        0, n_tiles, tile, jnp.zeros(_token_major(u.shape), jnp.float32)))


def _routed_grad_impl(cfg, act: str, u, wg, wu, wd, w_pair, order, counts, g):
    """Cotangents of (u, wg, wu, wd, w_pair): the same loop, each tile
    differentiating itself and adding into its expert's and its tokens'
    rows."""
    with jax.named_scope("st.moe.dispatch"):
        table, n_tiles = _tile_table(w_pair, order, counts, u.shape[0], cfg)

    def tile(t, grads):
        du, dwg, dwu, dwd, dw_pair = grads
        row = jax.tree.map(lambda a: a[t], table)
        with jax.named_scope("st.moe.dispatch"):
            x, gy = u[row["token"]], g[row["token"]]
        with jax.named_scope("st.moe.experts"):
            e = row["expert"]
            dx, dg, du_, dd, dw_row = jax.vjp(
                partial(_tile_ffn, dt=cfg.dtype, act=ACTIVATIONS[act]),
                x, wg[e], wu[e], wd[e], row["weight"])[1](gy)
            dwg, dwu, dwd = dwg.at[e].add(dg), dwu.at[e].add(du_), dwd.at[e].add(dd)
        with jax.named_scope("st.moe.combine"):
            du = _add_rows(du, dx, row)
            # a padding row has pair 0 and no weight of its own
            dw_pair = dw_pair.at[row["pair"]].add(jnp.where(row["valid"], dw_row, 0.0))
        return du, dwg, dwu, dwd, dw_pair

    start = tuple(jnp.zeros(shape, jnp.float32) for shape in (
        _token_major(u.shape), wg.shape, wu.shape, wd.shape, w_pair.shape))
    du, *rest = lax.fori_loop(0, n_tiles, tile, start)
    return (_two_dimensional(du), *rest)


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def routed_experts(cfg, act: str, u, wg, wu, wd, w_pair, order, counts):
    """``sum_{i chosen and held} w_i E_i(u)`` for every token, ``[T, hidden]``
    float32, ``E`` gated by ``ACTIVATIONS[act]``. Dropless: every (token,
    chosen-and-held expert) pair is computed whatever the imbalance. ``cfg``
    is either decoder's ``Config``: what is read of it is ``expert_tile``,
    ``expert_spare``, ``num_experts_per_tok``, ``n_routed_experts``,
    ``dtype``."""
    return _per_example(partial(_routed_impl, cfg, act))(u, wg, wu, wd, w_pair, order, counts)


def _routed_fwd(cfg, act, *args):
    return _per_example(partial(_routed_impl, cfg, act))(*args), args


def _routed_bwd(cfg, act, args, g):
    with jax.named_scope("st.moe"):
        grads = _per_example(partial(_routed_grad_impl, cfg, act))(*args, g)
    return (*grads, None, None)


routed_experts.defvjp(_routed_fwd, _routed_bwd)


def held_experts(p: dict, u: jax.Array, idx, w, cfg, act: str,
                 names=("gate_proj", "up_proj", "down_proj")):
    """The held experts' part of an expert layer of ``u [T, hidden]`` for the
    router's ``idx, w [T, k]`` (every token's chosen experts of all
    ``n_routed_experts`` and their weights), and what
    :func:`expert_counters` counts (which pairs are held, how many each
    expert has). ``p`` holds ``experts.<e>.<name>.weight`` for the experts
    ``cfg.experts_held`` = (first, count) and the three ``names`` (gate, up,
    down); what absent experts would add is left out."""
    first, held = cfg.experts_held
    with jax.named_scope("st.moe.dispatch"):
        local = idx - first
        here = (local >= 0) & (local < held)
        key = jnp.where(here, local, held).reshape(-1)
        counts = jnp.sum(key[:, None] == jnp.arange(held)[None, :], axis=0,
                         dtype=jnp.int32)
        order = jnp.argsort(key).astype(jnp.int32)
        wg, wu, wd = (jnp.stack([
            p[f"experts.{e}.{name}.weight"] for e in range(first, first + held)])
            for name in names)
    routed = routed_experts(cfg, act, u, wg, wu, wd, w.reshape(-1), order, counts)
    return routed, (idx, here, counts)


def expert_counters(held, cfg) -> dict:
    """One expert layer's counters, from :func:`held_experts`' second
    result."""
    idx, here, counts = held
    pairs = jnp.sum(counts)
    mean = pairs.astype(jnp.float32) / counts.shape[0]
    return {
        "choices": idx,
        "moe_pairs_held": pairs,
        "moe_load_max_over_mean": jnp.max(counts) / jnp.maximum(mean, 1.0),
        "moe_tokens_unrouted_share": jnp.mean(~jnp.any(here, axis=1), dtype=jnp.float32),
        "moe_rows_executed": cfg.expert_tile * _tiles_to_run(counts, idx.shape[0], cfg)[0],
    }


def moe(p: dict, u: jax.Array, cfg, router=route, shared_name: str = "shared_experts."):
    """The expert layer of ``u [T, hidden]``: shared expert + the held
    experts' weighted part; ``p`` holds the ``mlp.*`` leaves, the shared
    expert's under the prefix ``shared_name``, and ``router(p, u, cfg)`` gives
    every token's experts and weights. Returns the output and this layer's
    counters."""
    with jax.named_scope("st.moe.router"):
        idx, w = router(p, u, cfg)
    routed, held = held_experts(p, u, idx, w, cfg, "silu")
    with jax.named_scope("st.moe.shared"):
        shared = swiglu({n: p[shared_name + n] for n in
                         ("gate_proj.weight", "up_proj.weight", "down_proj.weight")},
                        u, cfg.dtype)
    aux = expert_counters(held, cfg)
    return shared + routed, aux


# --- blocks, head, loss ---------------------------------------------------------


def _sub(params: dict, prefix: str) -> dict:
    return {k[len(prefix):]: v for k, v in params.items() if k.startswith(prefix)}


def block(p: dict, x: jax.Array, rope, cfg: Config, is_moe: bool):
    """``h = x + MLA(norm(x)); y = h + FFN(norm(h))`` with this layer's
    leaves ``p``; the counters of an expert layer, else ``None``."""
    eps = cfg.rms_norm_eps
    with jax.named_scope("st.mla"):
        h = x + mla(_sub(p, "self_attn."), rms_norm(x, p["input_layernorm.weight"], eps),
                    rope, cfg)
    u = rms_norm(h, p["post_attention_layernorm.weight"], eps)
    if is_moe:
        with jax.named_scope("st.moe"):
            f, aux = moe(_sub(p, "mlp."), u, cfg)
        return h + f, aux
    with jax.named_scope("st.ffn"):
        return h + swiglu(_sub(p, "mlp."), u, cfg.dtype), None


def _block(params: dict, i: int, x, rope, cfg: Config):
    """Layer ``i``, recomputed in the backward pass but for its attention's
    residuals."""
    fn = layer_checkpoint(partial(block, cfg=cfg, is_moe=cfg.is_moe(i)))
    return fn(_sub(params, _layer(i)), x, rope)


def head_logits(x, norm_w, head_w, cfg) -> jax.Array:
    """float32 logits over the held vocabulary of ``x [.., hidden]``."""
    return _mm(rms_norm(x, norm_w, cfg.rms_norm_eps), head_w, cfg.dtype)


def head_loss(x, norm_w, head_w, targets, weights, cfg) -> jax.Array:
    """Sum over tokens of ``weights x`` the float32 cross-entropy of
    ``targets``, the logits made (and made again in the backward pass) in
    blocks of ``loss_block`` tokens."""
    n = x.shape[0]
    blk = min(cfg.loss_block, n)
    if n % blk:
        raise ValueError(f"{n} tokens do not divide into blocks of {blk}")

    @jax.checkpoint
    def one(xb, tb, wb):
        logits = head_logits(xb, norm_w, head_w, cfg)
        nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
            logits, tb[:, None], axis=-1)[:, 0]
        return jnp.sum(nll * wb)

    def step(total, xs):
        return total + one(*xs), None

    shape = lambda a: a.reshape(n // blk, blk, *a.shape[1:])
    total, _ = lax.scan(step, jnp.float32(0), (shape(x), shape(targets), shape(weights)))
    return total


def trunk(params: dict, tokens: jax.Array, cfg: Config):
    """The last layer's output ``y_L [T, hidden]`` (before ``model.norm``) of
    one sequence, the RoPE tables and the expert layers' counters."""
    rope = rope_tables(tokens.shape[0], cfg.qk_rope_head_dim, cfg.rope_theta)
    with jax.named_scope("st.embed"):
        x = params["model.embed_tokens.weight"][tokens]
    auxes = []
    for i in range(cfg.num_hidden_layers):
        x, aux = _block(params, i, x, rope, cfg)
        auxes += [aux] if aux is not None else []
    return x, rope, auxes


def mtp_trunk(params: dict, y, tokens_next, rope, cfg: Config):
    """The prediction module's block output: ``[norm_e(Emb(t_{i+1})) |
    norm_h(y_i)] W_eh`` through one decoder block
    (``model.layers.<num_hidden_layers>``)."""
    p = _sub(params, _layer(cfg.num_hidden_layers))
    eps = cfg.rms_norm_eps
    with jax.named_scope("st.embed"):
        emb = params["model.embed_tokens.weight"][tokens_next]
    both = jnp.concatenate(
        [rms_norm(emb, p["enorm.weight"], eps), rms_norm(y, p["hnorm.weight"], eps)],
        axis=-1)
    x = _mm(both, p["eh_proj.weight"], cfg.dtype)
    return _block(params, cfg.num_hidden_layers, x, rope, cfg)


def forward(params: dict, tokens: jax.Array, cfg: Config) -> jax.Array:
    """float32 logits ``[T, vocab_held]`` of one sequence ``tokens [T]``:
    position i's row scores token i+1. (The loss never builds this array; it
    is for checks and small inputs.)"""
    y, _, _ = trunk(params, tokens, cfg)
    return head_logits(y, params["model.norm.weight"], params["lm_head.weight"], cfg)


def _sequence_loss(params: dict, tokens: jax.Array, cfg: Config):
    n = tokens.shape[0]
    pos = jnp.arange(n)
    y, rope, auxes = trunk(params, tokens, cfg)
    with jax.named_scope("st.head_loss"):
        ce_main = head_loss(
            y, params["model.norm.weight"], params["lm_head.weight"],
            jnp.roll(tokens, -1), (pos < n - 1).astype(jnp.float32), cfg) / (n - 1)
    loss, ce_mtp = ce_main, jnp.float32(0)
    if cfg.num_nextn_predict_layers:
        with jax.named_scope("st.mtp"):
            # position i joins y_i with token i+1 and scores token i+2; the
            # rolled-in last positions see only themselves (causal) and
            # carry no weight
            z, aux = mtp_trunk(params, y, jnp.roll(tokens, -1), rope, cfg)
            auxes += [aux] if aux is not None else []
            with jax.named_scope("st.head_loss"):
                norm_w = params[_layer(cfg.num_hidden_layers) + "shared_head.norm.weight"]
                ce_mtp = head_loss(
                    z, norm_w, params["lm_head.weight"], jnp.roll(tokens, -2),
                    (pos < n - 2).astype(jnp.float32), cfg) / (n - 2)
        loss = loss + cfg.mtp_loss_weight * ce_mtp
    aux = {"ce_main": ce_main, "ce_mtp": ce_mtp}
    if auxes:
        aux.update({name: jnp.stack([a[name] for a in auxes]) for name in auxes[0]})
    return loss, aux, y


def loss_fn(params: dict, batch: jax.Array, cfg: Config,
            positions: jax.Array | None = None) -> tuple[jax.Array, Any]:
    """``(loss, aux)`` of ``batch [B, T]`` token ids (documents packed, no
    mask between them): ``CE_main + mtp_loss_weight x CE_mtp``, the mean over
    the sequences. ``aux``: ``ce_main``, ``ce_mtp`` and, one entry an expert
    layer (the prediction module's last), ``moe_pairs_held``,
    ``moe_load_max_over_mean``, ``moe_tokens_unrouted_share``,
    ``moe_rows_executed``, summed (pairs, rows) or averaged over sequences.
    With ``positions`` also what a comparison with a reference needs, from
    the path the loss takes, a sequence each: ``ce_main_of [B]``, ``ce_mtp_of
    [B]``, ``logits [B, len(positions), vocab_held]`` at those positions and
    ``choices [B, layers, T, k]``."""
    outs = [_sequence_loss(params, batch[b], cfg) for b in range(batch.shape[0])]
    n = len(outs)
    mean = lambda name: sum(aux[name] for _, aux, _ in outs) / n
    aux = {name: mean(name) for name in outs[0][1] if name != "choices"}
    for name in {"moe_pairs_held", "moe_rows_executed"} & set(aux):
        aux[name] = sum(a[name] for _, a, _ in outs)
    if positions is not None:
        aux["logits"] = jnp.stack([head_logits(
            y[positions], params["model.norm.weight"], params["lm_head.weight"], cfg)
            for _, _, y in outs])
        aux.update({name + "_of": jnp.stack([a[name] for _, a, _ in outs])
                    for name in ("ce_main", "ce_mtp")})
        if "choices" in outs[0][1]:
            aux["choices"] = jnp.stack([a["choices"] for _, a, _ in outs])
    return sum(l for l, _, _ in outs) / n, aux
