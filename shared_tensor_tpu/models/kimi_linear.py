"""A hybrid decoder of Kimi Delta Attention (KDA) mixers, latent attention
without position encoding and sigmoid-routed expert layers
(Kimi-Linear-48B-A3B's ``config.json`` keys, arXiv:2510.26692) as pure
functions on a parameter pytree. Every layer is a mixer and a feed-forward
part, each behind its own RMSNorm: ``h = x + mixer(RMSNorm(x))``, ``y = h +
ffn(RMSNorm(h))``. Layer ``i`` (from 0) mixes by KDA where ``i + 1`` is in
``linear_attn_config["kda_layers"]``, else by MLA (``mla_moe.mla`` with one
``q_proj`` and no rotary turn: ``mla_use_nope``); its feed-forward part is a
dense SwiGLU below ``first_k_dense_replace``, else ``mla_moe.moe`` with the
published router (sigmoid scores, top k, renormalised, times
``routed_scaling_factor``) and one shared expert.

The KDA mixer, for ``u [T, hidden]`` (normed), H heads of d channels:

- ``q, k, v = silu(conv(u W_q)), silu(conv(u W_k)), silu(conv(u W_v))``, a
  depthwise causal convolution of 4 taps without bias
  (``nemotron_h.causal_conv``); q and k L2-normalised per head, q times
  ``d^-1/2``;
- the decay ``g = -exp(A_log[h]) softplus(f_b(f_a(u)) + dt_bias)`` per key
  channel, ``β = sigmoid(u W_b)`` per head;
- ``S_t = (I - β_t k_t k_tᵀ) Diag(exp g_t) S_{t-1} + β_t k_t v_tᵀ``, ``o_t =
  S_tᵀ q_t`` from ``S_{-1} = 0``;
- ``y = W_o [RMSNorm_d(o) w ⊙ sigmoid(g_b(g_a(u)))]``.

The recurrence runs in chunks of ``CHUNK`` positions (the WY / UT form): with
``Γ`` the cumulative log-decays from a chunk's start, per channel,
``A = tril(β K̃ K̂ᵀ, -1)`` and ``P = tril(Q̃ K̂ᵀ)`` (``K̃ = K e^Γ``, ``K̂ = K
e^-Γ``, ``Q̃ = Q e^Γ``), ``[W | U] = (I + A)^-1 [β K̃ | β V]`` with the
inverse made by forward substitution by blocks (:func:`_unit_lower_inverse`,
float32), then for the state S entering the chunk ``V' = U - W S``, ``O = Q̃ S
+ P V'`` and ``S_next = Diag(e^Γ_C) S + (K e^{Γ_C - Γ})ᵀ V'``.

**The safe form.** ``e^-Γ`` overflows float32 once a chunk's decays add up
past about -88 (g near -20 a step reaches it in five positions), so ``K̂`` is
never made. ``A`` and ``P`` hold ``sum_c x_{r,c} k_{s,c} e^{Γ_{r,c} -
Γ_{s,c}}`` for ``s <= r``, and every exponent written here is at most 0: a
chunk is cut into sub-chunks of ``SUB`` positions; between sub-chunks the
product is split at the first position ``n`` of the row's sub-chunk, ``(x_r
e^{Γ_r - Γ_n}) · (k_s e^{Γ_n - Γ_s})`` with ``s < n <= r``, one matrix
product a sub-chunk of rows; within a sub-chunk each pair's exponent is made
on its own (``SUB²`` pairs a sub-chunk, on the vector units). ``e^Γ``, ``e^{Γ_C
- Γ}`` and the decays of the state are at most 1 by themselves. A decay that
underflows to 0 drops what is below float32's range, as the recurrence would.

The chunks go through in groups of ``GROUP``: one ``lax.scan`` over the
groups carries the float32 state; in a group the parts that do not read the
state are made for all its chunks at once and an inner ``lax.scan`` carries
the state from chunk to chunk. The scan is a ``custom_vjp``: its forward
pass keeps the state entering each group beside its output (what a layer's
checkpoint keeps of the mixer, ``mla_moe.KDA_OUT``), and its backward rule
goes back over the groups, each group's chunked form made again and
differentiated (scope ``st.kda.scan``, forward and backward), so the
backward pass holds one group's intermediates at a time. Products take
``compute_dtype`` operands with float32 accumulation as every other product
here, and so do q, k, v and the output between the scan and the layer; the
cumulative decays, the pairs within a sub-chunk, the triangular inverse and
the states are float32.

What the other decoders have is theirs, given arguments: the latent
attention (``mla_moe.mla``), the expert loop and the router
(``mla_moe.moe``, ``mla_moe.route``), the convolution (``nemotron_h``),
norm, head, loss and the sequence loop (``swa_moe.loss_fn(trunk=, norm=)``),
the per-layer checkpoint.
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

from ..utils.profiling import pod_tier
from . import mla_moe, swa_moe
from .mla_moe import KDA_OUT, _mm, _precision, _sub, head_logits, layer_checkpoint, moe, rms_norm
from .nemotron_h import causal_conv

CHUNK = 64  # positions a chunk of the delta-rule scan
SUB = 16  # positions a sub-chunk: pairs within one are made one by one
GROUP = 32  # chunks a step of the pass over the sequence

_LINEAR = {
    "kda_layers": (1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22, 23, 25, 26),
    "full_attn_layers": (4, 8, 12, 16, 20, 24, 27),
    "head_dim": 128, "num_heads": 32, "short_conv_kernel_size": 4,
}
#: an expert's three leaves (gate, up, down), by the checkpoint's names
EXPERT = ("w1", "w3", "w2")
#: the decay's step at initialisation: log-uniform in [min, max], floored
TIME_STEP = (1e-3, 1e-1, 1e-4)


def _layer(i: int) -> str:
    return f"model.layers.{i}."


def _frozen(group) -> tuple:
    """A configuration group (a dict of numbers and lists) as sorted pairs of
    hashable values, so that the ``Config`` is hashable."""
    items = group.items() if isinstance(group, dict) else group
    return tuple(sorted((k, tuple(v) if isinstance(v, (list, tuple)) else v) for k, v in items))


@dataclasses.dataclass(frozen=True)
class Config:
    """The published keys by their published names, what this chip holds of
    them, and how the products are computed."""

    hidden_size: int = 2304
    num_hidden_layers: int = 27
    first_k_dense_replace: int = 1
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    rms_norm_eps: float = 1e-5
    hidden_act: str = "silu"
    #: the KDA layers (1-indexed) and their shape, as published
    linear_attn_config: Any = _frozen(_LINEAR)
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    q_lora_rank: int | None = None
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_use_nope: bool = True
    num_experts: int = 256
    num_experts_per_token: int = 8
    num_shared_experts: int = 1
    moe_renormalize: bool = True
    moe_router_activation_func: str = "sigmoid"
    routed_scaling_factor: float = 2.446
    num_expert_group: int = 1
    topk_group: int = 1
    moe_layer_freq: int = 1
    num_nextn_predict_layers: int = 0
    vocab_size: int = 163840
    tie_word_embeddings: bool = False
    #: (first, count) of the routed experts held here; the router keeps
    #: ``num_experts`` outputs whatever is held
    experts_held: tuple[int, int] = (0, 256)
    #: rows of the vocabulary held here: token ids, logits and loss are over them
    vocab_held: int = 163840
    #: matrices normal(0, ``init_std``), the embedding normal(0, 1), as the
    #: other decoders'; the KDA mixers' decay leaves by the Mamba-2 convention
    init_std: float = 0.006
    compute_dtype: str = "bfloat16"
    attn_block: int = 512  # the attention scan's tile, where the fused kernels do not run
    loss_block: int = 2048  # tokens a block of logits
    expert_tile: int = 128  # rows a tile of one expert's tokens
    expert_spare: float = 1.5  # see mla_moe.Config.expert_spare

    def __post_init__(self):
        object.__setattr__(self, "linear_attn_config", _frozen(self.linear_attn_config))
        if (self.q_lora_rank is not None or not self.mla_use_nope or self.tie_word_embeddings
                or (self.num_expert_group, self.topk_group, self.num_shared_experts,
                    self.moe_layer_freq, self.num_nextn_predict_layers) != (1, 1, 1, 1, 0)
                or (self.hidden_act, self.moe_router_activation_func) != ("silu", "sigmoid")):
            raise ValueError("written: queries from q_proj, no rotary turn, untied head, one "
                             "router group, one shared expert, every layer past the dense ones "
                             "an expert layer, no prediction module, silu, sigmoid scores")
        if self.num_key_value_heads != self.num_attention_heads:
            raise ValueError("latent attention has one K/V head a query head")

    @property
    def dtype(self):
        return jnp.dtype(self.compute_dtype)

    @property
    def linear(self) -> dict:
        return dict(self.linear_attn_config)

    # the names mla_moe's expert layer and router read
    @property
    def n_routed_experts(self) -> int:
        return self.num_experts

    @property
    def num_experts_per_tok(self) -> int:
        return self.num_experts_per_token

    @property
    def norm_topk_prob(self) -> bool:
        return self.moe_renormalize

    @property
    def kda_heads(self) -> int:
        return self.linear["num_heads"]

    @property
    def kda_head_dim(self) -> int:
        return self.linear["head_dim"]

    def is_kda(self, layer: int) -> bool:
        return layer + 1 in self.linear["kda_layers"]

    def is_moe(self, layer: int) -> bool:
        return layer >= self.first_k_dense_replace

    @property
    def plan(self) -> str:
        """One letter a layer: K for a KDA mixer, A for latent attention."""
        return "".join("K" if self.is_kda(i) else "A" for i in range(self.num_hidden_layers))

    @property
    def expert_layers(self) -> int:
        return sum(self.is_moe(i) for i in range(self.num_hidden_layers))


def param_shapes(cfg: Config) -> dict[str, tuple[int, ...]]:
    """Every leaf's checkpoint name and shape (``nn.Linear`` is [out, in]).
    The short convolutions' taps are held ``[kernel, channels]``, the
    checkpoint's ``[channels, 1, kernel]`` turned (a leaf of 4 floats a row
    made the TPU compiler lay a whole flat table out as ``[rows, 1, 4]``;
    ``nemotron_h.param_shapes``); ``A_log`` is held ``[heads]``, the
    checkpoint's ``[1, 1, heads, 1]``. The router's ``e_score_correction_bias``
    is no leaf: a buffer that the deployment's balancing loop moves and no
    gradient does, zero here (:func:`route`)."""
    d, h = cfg.hidden_size, cfg.num_attention_heads
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    heads, hd = cfg.kda_heads, cfg.kda_head_dim
    width, taps = heads * hd, cfg.linear["short_conv_kernel_size"]
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
        if cfg.is_kda(i):
            for name in "qkv":
                shapes[a + f"{name}_proj.weight"] = (width, d)
                shapes[a + f"{name}_conv1d.weight"] = (taps, width)
            shapes[a + "f_a_proj.weight"] = (hd, d)
            shapes[a + "f_b_proj.weight"] = (width, hd)
            shapes[a + "g_a_proj.weight"] = (hd, d)
            shapes[a + "g_b_proj.weight"] = (width, hd)
            shapes[a + "b_proj.weight"] = (heads, d)
            shapes[a + "A_log"] = (heads,)
            shapes[a + "dt_bias"] = (width,)
            shapes[a + "o_norm.weight"] = (hd,)
            shapes[a + "o_proj.weight"] = (d, width)
        else:
            shapes[a + "q_proj.weight"] = (h * qk, d)
            shapes[a + "kv_a_proj_with_mqa.weight"] = (cfg.kv_lora_rank + cfg.qk_rope_head_dim, d)
            shapes[a + "kv_a_layernorm.weight"] = (cfg.kv_lora_rank,)
            shapes[a + "kv_b_proj.weight"] = (
                h * (cfg.qk_nope_head_dim + cfg.v_head_dim), cfg.kv_lora_rank)
            shapes[a + "o_proj.weight"] = (d, h * cfg.v_head_dim)
        if cfg.is_moe(i):
            m = p + "block_sparse_moe."
            shapes[m + "gate.weight"] = (cfg.num_experts, d)
            shared = cfg.moe_intermediate_size * cfg.num_shared_experts
            shapes[m + "shared_experts.gate_proj.weight"] = (shared, d)
            shapes[m + "shared_experts.up_proj.weight"] = (shared, d)
            shapes[m + "shared_experts.down_proj.weight"] = (d, shared)
            for e in range(first, first + count):
                shapes[m + f"experts.{e}.w1.weight"] = (cfg.moe_intermediate_size, d)
                shapes[m + f"experts.{e}.w3.weight"] = (cfg.moe_intermediate_size, d)
                shapes[m + f"experts.{e}.w2.weight"] = (d, cfg.moe_intermediate_size)
        else:
            shapes[p + "mlp.gate_proj.weight"] = (cfg.intermediate_size, d)
            shapes[p + "mlp.up_proj.weight"] = (cfg.intermediate_size, d)
            shapes[p + "mlp.down_proj.weight"] = (d, cfg.intermediate_size)
    return shapes


def init_params(key: jax.Array, cfg: Config) -> dict[str, jax.Array]:
    """Matrices normal(0, ``init_std``), the embedding normal(0, 1), norms 1;
    a KDA mixer's ``A_log = log U[1, 16]``, ``dt_bias`` the inverse softplus
    of ``exp U[log 1e-3, log 1e-1]`` floored at 1e-4 (:data:`TIME_STEP`), its
    convolutions' taps U(-1/2, 1/2) as a PyTorch ``Conv1d`` of fan-in 4 (the
    Mamba-2 convention, as ``nemotron_h``)."""
    shapes = param_shapes(cfg)
    params = {}
    (lo, hi), floor = map(math.log, TIME_STEP[:2]), TIME_STEP[2]
    bound = 1.0 / math.sqrt(cfg.linear["short_conv_kernel_size"])
    for k, (name, shape) in zip(jax.random.split(key, len(shapes)), sorted(shapes.items())):
        leaf = name.rsplit(".", 1)[-1]
        if leaf == "A_log":
            params[name] = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
        elif leaf == "dt_bias":
            dt = jnp.maximum(jnp.exp(jax.random.uniform(k, shape, jnp.float32, lo, hi)), floor)
            params[name] = dt + jnp.log(-jnp.expm1(-dt))
        elif "conv1d" in name:
            params[name] = jax.random.uniform(k, shape, jnp.float32, -bound, bound)
        elif len(shape) == 1:
            params[name] = jnp.ones(shape, jnp.float32)
        else:
            std = 1.0 if name == "model.embed_tokens.weight" else cfg.init_std
            params[name] = std * jax.random.normal(k, shape, jnp.float32)
    return params


# --- the chunked delta-rule scan -------------------------------------------------------


def _masks():
    """Static masks of a chunk in sub-chunks: which (row, column) pairs lie
    in an earlier sub-chunk than the row's (``[blocks, SUB, CHUNK]``), and
    which pairs of one sub-chunk are at or below the diagonal (``[SUB,
    SUB]``)."""
    rows = np.arange(CHUNK).reshape(CHUNK // SUB, SUB, 1)
    earlier = (np.arange(CHUNK)[None, None, :] // SUB) < (rows // SUB)
    return jnp.asarray(earlier), jnp.asarray(np.tril(np.ones((SUB, SUB), bool)))


def _decayed_products(q, k, gam, dtype):
    """``P [R, H, C, C]`` with ``P[r, s] = sum_c q_{r,c} k_{s,c} e^{Γ_{r,c} -
    Γ_{s,c}}`` for ``s <= r`` (0 above the diagonal) and the same of k with
    itself, ``Akk``, for ``s < r``: ``q, k, gam [R, C, H, d]`` (``gam`` the
    cumulative log-decays from each chunk's start, non-increasing along C).
    Every exponent made is at most 0 (the module's safe form)."""
    r_, c_, h_, d_ = q.shape
    nb, sub = c_ // SUB, SUB
    earlier, lower = _masks()
    mm = partial(jnp.einsum, preferred_element_type=jnp.float32, precision=_precision(dtype))
    gam_b = gam.reshape(r_, nb, sub, h_, d_)
    ref = gam_b[:, :, :1]  # Γ at each sub-chunk's first position [R, nb, 1, H, d]
    # between sub-chunks: rows after the reference, columns before it
    # (a pair of one position is e^0 by its form: no gradient flows through
    # it, where two that cancel would leave float32's rounding)
    first = (jnp.arange(sub) == 0)[:, None, None]
    rel = jnp.exp(jnp.where(first, 0.0, gam_b - ref))
    rows = jnp.stack([q.reshape(gam_b.shape) * rel, k.reshape(gam_b.shape) * rel])
    cols = k[:, None] * jnp.exp(jnp.minimum(ref - gam[:, None], 0.0))  # [R, nb, C, H, d]
    off = mm("xrbihd,rbshd->xrhbis", rows.astype(dtype), cols.astype(dtype))
    off = jnp.where(earlier, off, 0.0)
    # within a sub-chunk: every pair's own exponent
    gap = gam_b[:, :, :, None] - gam_b[:, :, None, :]  # [R, nb, i, j, H, d]
    gap = jnp.where(jnp.eye(sub, dtype=bool)[:, :, None, None], 0.0, gap)
    decay = jnp.exp(jnp.where(lower[:, :, None, None], gap, -jnp.inf))
    qk = jnp.stack([q.reshape(gam_b.shape), k.reshape(gam_b.shape)])
    diag = jnp.sum(qk[:, :, :, :, None] * k.reshape(gam_b.shape)[None, :, :, None] * decay[None],
                   axis=-1)  # [x, R, nb, i, j, H]
    diag = jnp.moveaxis(diag, -1, 2)  # [x, R, H, nb, i, j]
    eye = jnp.eye(nb, dtype=jnp.float32)
    within = (diag[..., :, :, None, :] * eye[:, None, :, None]).reshape(2, r_, h_, c_, c_)
    both = off.reshape(2, r_, h_, c_, c_) + within
    strict = jnp.tril(jnp.ones((c_, c_), bool), -1)
    return both[0], jnp.where(strict, both[1], 0.0)


def _unit_lower_inverse(a):
    """``(I + a)^-1`` for strictly lower triangular ``a [..., C, C]`` (C a
    power of two), float32: forward substitution by blocks, from blocks of
    one position up, each level one batched product for all blocks,
    ``[[T11, 0], [T21, T22]]`` with ``T21 = -T22 a21 T11`` (``a21`` the block
    under the diagonal of a pair of blocks). ``log2 C`` levels of products,
    where a substitution row by row is C dependent steps."""
    *batch, c, _ = a.shape
    mm = partial(jnp.einsum, precision=lax.Precision.HIGHEST, preferred_element_type=jnp.float32)
    t = jnp.ones((*batch, c, 1, 1), jnp.float32)  # the inverses of the 1 x 1 blocks
    s = 1
    while s < c:
        n = c // (2 * s)  # pairs of blocks of s
        pairs = t.reshape(*batch, n, 2, s, s)
        t11, t22 = pairs[..., 0, :, :], pairs[..., 1, :, :]
        under = a.reshape(*batch, n, 2, s, n, 2, s)[..., :, 1, :, :, 0, :]  # [.., n, s, n, s]
        a21 = jnp.sum(under * jnp.eye(n, dtype=a.dtype)[:, None, :, None], axis=-2)
        t21 = -mm("...ij,...jk,...kl->...il", t22, a21, t11)
        t = jnp.concatenate([jnp.concatenate([t11, jnp.zeros_like(t11)], axis=-1),
                             jnp.concatenate([t21, t22], axis=-1)], axis=-2)
        s *= 2
    return t.reshape(*batch, c, c)


def _group(state, xs, dtype):
    """One group of R chunks from the state ``[H, d, dv]`` entering it:
    ``xs`` = (q, k, v, g ``[R, C, H, d]``, beta ``[R, C, H]``). Returns the
    state leaving the group and the output ``[R, C, H, dv]`` in v's dtype."""
    q, k, v, g, beta = xs
    f32 = jnp.float32
    mm = partial(jnp.einsum, preferred_element_type=f32, precision=_precision(dtype))
    cast = lambda a: a.astype(dtype)
    gam = jnp.cumsum(g, axis=1)
    p_qk, a_kk = _decayed_products(q, k, gam, dtype)
    b_h = jnp.moveaxis(beta, 2, 1)  # [R, H, C]
    a_kk = a_kk * b_h[..., None]
    up = jnp.exp(gam)
    last = gam[:, -1:]
    # [W | U] = (I + A)^-1 [β K̃ | β V], float32: W and U are what the state
    # is corrected by, chunk after chunk
    rhs = jnp.concatenate([k * up, v], axis=-1) * beta[..., None]
    wu = jnp.einsum("rhcs,rshe->rhce", _unit_lower_inverse(a_kk), rhs,
                    precision=lax.Precision.HIGHEST, preferred_element_type=f32)
    w, u = wu[..., :k.shape[-1]], wu[..., k.shape[-1]:]  # [R, H, C, d], [R, H, C, dv]
    k_out = jnp.moveaxis(k * jnp.exp(last - gam), 2, 1)  # [R, H, C, d]
    fade = jnp.exp(last[:, 0])  # [R, H, d]

    def carry(s, at):
        w_c, u_c, k_c, fade_c = at
        vp = u_c - mm("hcd,hdv->hcv", cast(w_c), cast(s))
        s_next = fade_c[..., None] * s + mm("hcd,hcv->hdv", cast(k_c), cast(vp))
        return s_next, (s, vp)

    state, (entering, vp) = lax.scan(carry, state, (w, u, k_out, fade))
    q_in = jnp.moveaxis(q * up, 2, 1)
    o = mm("rhcd,rhdv->rhcv", cast(q_in), cast(entering)) + mm(
        "rhcs,rhsv->rhcv", cast(p_qk), cast(vp))
    return state, jnp.moveaxis(o, 1, 2).astype(v.dtype)


def _in_groups(a, n: int, groups: int, per: int):
    """``a [T, ...]`` padded with zeros to ``groups x per x CHUNK``
    positions and cut into ``[groups, per, CHUNK, ...]``."""
    pad = groups * per * CHUNK - n
    a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
    return a.reshape(groups, per, CHUNK, *a.shape[1:])


def _layout(n: int) -> tuple[int, int]:
    """``(groups, chunks a group)`` for ``n`` positions."""
    chunks = -(-n // CHUNK)
    per = min(GROUP, chunks)
    return -(-chunks // per), per


def _kda_chunks(q, k, v, g, beta, dtype):
    """``(o [T, H, dv], states [groups, H, d, dv])``: the output of the
    recurrence from a zero state and the state entering each group of
    chunks. Positions past T that fill the last group carry g = 0, β = 0 and
    zero q, k, v: they add nothing."""
    n, h, d = q.shape
    groups, per = _layout(n)
    xs = tuple(_in_groups(a, n, groups, per) for a in (q, k, v, g, beta))
    start = jnp.zeros((h, d, v.shape[-1]), jnp.float32)

    def step(s, x):
        s_next, o = _group(s, x, dtype)
        return s_next, (o, s)

    _, (o, states) = lax.scan(step, start, xs)
    return o.reshape(-1, h, v.shape[-1])[:n], states


@partial(jax.custom_vjp, nondiff_argnums=(5,))
def _kda(q, k, v, g, beta, dtype):
    return _kda_chunks(q, k, v, g, beta, dtype)[0]


def _kda_fwd(q, k, v, g, beta, dtype):
    # the output and the states entering the groups are what a layer's
    # checkpoint keeps (``mla_moe.layer_checkpoint``): the recomputed layer
    # reads them and runs the scan no second time
    o, states = (checkpoint_name(a, KDA_OUT) for a in _kda_chunks(q, k, v, g, beta, dtype))
    return o, (q, k, v, g, beta, states)


def _kda_bwd(dtype, res, do):
    with jax.named_scope("st.kda.scan"):  # a backward rule is traced outside the caller's scope
        *ins, states = res
        n = ins[0].shape[0]
        groups, per = _layout(n)
        xs = tuple(_in_groups(a, n, groups, per) for a in ins)

        def back(ds, at):
            x, s, do_g = at
            _, vjp = jax.vjp(partial(_group, dtype=dtype), s, x)
            ds_in, dx = vjp((ds, do_g))
            return ds_in, dx

        _, dxs = lax.scan(back, jnp.zeros_like(states[0]),
                          (xs, states, _in_groups(do, n, groups, per)), reverse=True)
        return tuple(d.reshape(-1, *a.shape[1:])[:n] for d, a in zip(dxs, ins))


_kda.defvjp(_kda_fwd, _kda_bwd)


def kda_scan(q, k, v, g, beta, dtype):
    """The chunked delta-rule scan (:func:`_kda_chunks`) with its backward
    rule: ``q, k, v, g [T, H, d]`` (q and k normalised, g the float32
    log-decays), ``beta [T, H]``; returns ``o [T, H, d]`` in v's dtype.
    Counted as traced (``st_kda_traces_total``), with the heads it holds,
    the chunks it runs and the bytes its layer's checkpoint keeps of it (the
    output and the float32 states entering the groups)."""
    n, h, d = q.shape
    groups, _ = _layout(n)
    dv = v.shape[-1]
    pod_tier().count_kda_trace(heads=h, chunks=-(-n // CHUNK), saved_bytes=(
        n * h * dv * v.dtype.itemsize + groups * h * d * dv * 4))
    return _kda(q, k, v, g, beta, jnp.dtype(dtype))


def l2_normalised(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    """``x / sqrt(sum x² + eps)`` over the last axis."""
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


@partial(jax.checkpoint, static_argnums=(3, 4))
def _scan_inputs(p: dict, qkv, f, heads: int, dtype):
    """The scan's operands from the projections' outputs ``qkv`` (three
    ``[T, H d]``) and ``f`` (the decay's, ``[T, H d]``): q, k, v through the
    short convolutions and silu, q and k L2-normalised (q times ``d^-1/2``),
    the three in ``dtype`` (the scan's products take them so), and the
    float32 log-decays ``g``. Made again where their cotangents are taken, so
    the backward pass holds the projections' outputs and the scan's operands
    and none of the steps between (a mixer's fwd + bwd at 16 384 tokens: 6.34
    GB of temporaries without, 4.73 with; chip-free compile)."""
    n = f.shape[0]
    with jax.named_scope("st.kda.conv"):
        q, k, v = (jax.nn.silu(causal_conv(a, p[f"{name}_conv1d.weight"])).reshape(n, heads, -1)
                   for a, name in zip(qkv, "qkv"))
    with jax.named_scope("st.kda.gate"):
        g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
            (f + p["dt_bias"]).reshape(n, heads, -1))
    with jax.named_scope("st.kda.scan"):
        q = l2_normalised(q) * q.shape[-1] ** -0.5
        return q.astype(dtype), l2_normalised(k).astype(dtype), v.astype(dtype), g


def kda_mixer(p: dict, u: jax.Array, cfg: Config) -> jax.Array:
    """The Kimi Delta Attention mixer of ``u [T, hidden]`` (already normed);
    ``p`` holds the ``self_attn.*`` leaves."""
    n, dt = u.shape[0], cfg.dtype
    h, d = cfg.kda_heads, cfg.kda_head_dim
    with jax.named_scope("st.kda.proj"):
        qkv = [_mm(u, p[f"{name}_proj.weight"], dt) for name in "qkv"]
        f = _mm(_mm(u, p["f_a_proj.weight"], dt), p["f_b_proj.weight"], dt)
        b = _mm(u, p["b_proj.weight"], dt)
        gate = _mm(_mm(u, p["g_a_proj.weight"], dt), p["g_b_proj.weight"], dt)
    q, k, v, g = _scan_inputs(p, qkv, f, h, dt)
    with jax.named_scope("st.kda.gate"):
        beta = jax.nn.sigmoid(b)
    with jax.named_scope("st.kda.scan"):
        o = kda_scan(q, k, v, g, beta, dt)
    with jax.named_scope("st.kda.norm"):
        o = rms_norm(o, p["o_norm.weight"], cfg.rms_norm_eps) * jax.nn.sigmoid(
            gate.reshape(n, h, d))
    with jax.named_scope("st.kda.proj"):
        return _mm(o.reshape(n, h * d), p["o_proj.weight"], dt)


# --- layers, trunk, loss --------------------------------------------------------------


def route(p: dict, u: jax.Array, cfg: Config):
    """``mla_moe.route`` with the correction bias at zero (see
    :func:`param_shapes`): sigmoid scores, the top ``num_experts_per_token``,
    weights normalised over them and times ``routed_scaling_factor``."""
    bias = jnp.zeros((cfg.num_experts,), jnp.float32)
    return mla_moe.route({**p, "gate.e_score_correction_bias": bias}, u, cfg)


def block(p: dict, x: jax.Array, cfg: Config, layer: int):
    """Layer ``layer`` with its leaves ``p``: ``h = x + mixer(RMSNorm(x))``,
    ``y = h + ffn(RMSNorm(h))``; the counters of an expert layer, else
    ``None``."""
    eps = cfg.rms_norm_eps
    a = _sub(p, "self_attn.")
    u = rms_norm(x, p["input_layernorm.weight"], eps)
    if cfg.is_kda(layer):
        with jax.named_scope("st.kda"):
            h = x + kda_mixer(a, u, cfg)
    else:
        with jax.named_scope("st.mla"):
            h = x + mla_moe.mla(a, u, None, cfg)
    u = rms_norm(h, p["post_attention_layernorm.weight"], eps)
    if cfg.is_moe(layer):
        with jax.named_scope("st.moe"):
            f, aux = moe(_sub(p, "block_sparse_moe."), u, cfg, router=route, names=EXPERT)
        return h + f, aux
    with jax.named_scope("st.ffn"):
        return h + mla_moe.swiglu(_sub(p, "mlp."), u, cfg.dtype), None


def trunk(params: dict, tokens: jax.Array, cfg: Config):
    """The last layer's output ``[T, hidden]`` (before ``model.norm``) of one
    sequence and the expert layers' counters."""
    with jax.named_scope("st.embed"):
        x = params["model.embed_tokens.weight"][tokens]
    auxes = []
    for i in range(cfg.num_hidden_layers):
        x, aux = layer_checkpoint(partial(block, cfg=cfg, layer=i))(_sub(params, _layer(i)), x)
        auxes += [aux] if aux is not None else []
    return x, auxes


def forward(params: dict, tokens: jax.Array, cfg: Config) -> jax.Array:
    """float32 logits ``[T, vocab_held]`` of one sequence ``tokens [T]``:
    position i's row scores token i+1 (for checks and small inputs)."""
    y, _ = trunk(params, tokens, cfg)
    return head_logits(y, params["model.norm.weight"], params["lm_head.weight"], cfg)


def loss_fn(params: dict, batch: jax.Array, cfg: Config,
            positions: jax.Array | None = None) -> tuple[jax.Array, Any]:
    """``(loss, aux)`` of ``batch [B, T]`` token ids: ``swa_moe.loss_fn`` over
    this trunk and ``model.norm`` (the next-token cross-entropy over the held
    slice of the vocabulary; ``aux``'s ``moe_*`` one entry an expert
    layer)."""
    return swa_moe.loss_fn(params, batch, cfg, positions, trunk=trunk, norm="model.norm.weight")
