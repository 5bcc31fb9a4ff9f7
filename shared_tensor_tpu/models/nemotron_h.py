"""A hybrid decoder of Mamba-2 mixers, grouped-query attention and latent
expert layers (NVIDIA-Nemotron-3-Super-120B-A12B's ``config.json`` keys, the
Nemotron-H family) as pure functions on a parameter pytree. Every layer is one
mixer or one feed-forward part behind its own RMSNorm, ``h = h + f(RMSNorm(h))``,
and ``hybrid_override_pattern`` gives each layer's ``f``:

- ``M``, a Mamba-2 mixer over the heads held here (``mamba_heads_held``, whole
  groups): ``[z | xBC | dt] = n W_in``; ``xBC = silu(conv(xBC) + b)``, a causal
  depthwise convolution, split into ``x [T, H, P]``, ``B``, ``C [T, G, N]``
  (head h reads group h // (H / G)); ``Δ = softplus(dt + dt_bias)``, ``A =
  -exp(A_log)``; ``S_t = exp(Δ_t A) S_{t-1} + Δ_t x_t ⊗ B_t``, ``y_t = S_t C_t
  + D x_t``; ``y = RMSNorm_group(y * silu(z)) w``; the mixer returns ``y W_out``.
- ``E``, the latent expert layer: ``mla_moe.route`` (sigmoid scores, top k,
  renormalised and scaled) on the stream, the held experts' part ``W_fc2 sum_e
  w_e down_e(relu(up_e(u W_fc1))^2)`` in the latent, and an ungated shared
  expert ``down(relu(up u)^2)`` at full width.
- ``*``, grouped-query attention without position encoding over the heads held
  here (``attention_heads_held``, on the K/V heads they read).

The scan runs in chunks of ``chunk_size`` (the SSD form, arXiv:2405.21060
section 6): within a chunk a quadratic form (``C Bᵀ`` a group, masked by the
decays, times ``Δ x`` a head), the states each chunk leaves, and one pass over
those states, each chunk's entering state read out by its ``C``. Decays, ``Δ``,
``A`` and the states passed between chunks are float32; the four products
take ``compute_dtype`` operands with float32 accumulation, as every other
product here. The scan is a ``custom_vjp`` whose backward rule differentiates
the chunked form again from its inputs (scope ``st.ssm.scan``, forward and
backward); its output is what a layer's checkpoint keeps of a mixer.

What the other decoders have is theirs, given arguments: the expert loop and
the router (``mla_moe.moe(act="relu2", names=(up, down), latent=)``), the
attention (``swa_moe.attention(heads=, kv_heads=)`` with no tables), norm,
head, loss and the sequence loop (``swa_moe.loss_fn(trunk=, norm=)``), the
per-layer checkpoint. What is new is the mixer, the layer plan and the shares
of heads.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..utils.profiling import pod_tier
from . import mla_moe, swa_moe
from .mla_moe import SSM_OUT, _mm, _precision, _sub, head_logits, layer_checkpoint, moe, rms_norm

_PATTERN = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
            "EMEMEMEM*EMEMEMEME")
#: the latent expert layer's leaves: into the latent, and back
LATENT = ("fc1_latent_proj.weight", "fc2_latent_proj.weight")


def _layer(i: int) -> str:
    return f"backbone.layers.{i}."


@dataclasses.dataclass(frozen=True)
class Config:
    """The published keys by their published names, what this chip holds of
    them, and how the products are computed."""

    hidden_size: int = 4096
    num_hidden_layers: int = 88
    #: one letter a layer, as published (the layers beyond
    #: ``num_hidden_layers`` are not read): M mixer, E experts, * attention
    hybrid_override_pattern: str = _PATTERN
    layer_norm_epsilon: float = 1e-5
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    use_conv_bias: bool = True
    mamba_proj_bias: bool = False
    mamba_hidden_act: str = "silu"
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    attention_bias: bool = False
    n_routed_experts: int = 512
    num_experts_per_tok: int = 22
    moe_intermediate_size: int = 2688
    moe_latent_size: int = 1024
    moe_shared_expert_intermediate_size: int = 5376
    n_shared_experts: int = 1
    mlp_hidden_act: str = "relu2"
    mlp_bias: bool = False
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 5.0
    n_group: int = 1
    topk_group: int = 1
    vocab_size: int = 131072
    tie_word_embeddings: bool = False
    #: (first, count) of the routed experts held here; the router keeps
    #: ``n_routed_experts`` outputs whatever is held
    experts_held: tuple[int, int] = (0, 512)
    #: (first, count) of the mixers' heads held here: whole groups
    mamba_heads_held: tuple[int, int] = (0, 128)
    #: (first, count) of the attention's query heads held here, with the K/V
    #: heads they read
    attention_heads_held: tuple[int, int] = (0, 32)
    #: rows of the vocabulary held here: token ids, logits and loss are over them
    vocab_held: int = 131072
    #: matrices normal(0, ``init_std``), the embedding normal(0, 1), as the
    #: other decoders'; the mixers' own leaves by the Mamba-2 convention
    init_std: float = 0.006
    compute_dtype: str = "bfloat16"
    attn_block: int = 512  # the attention scan's tile, where the fused kernels do not run
    loss_block: int = 2048  # tokens a block of logits
    expert_tile: int = 128  # rows a tile of one expert's tokens
    expert_spare: float = 1.5  # see mla_moe.Config.expert_spare

    def __post_init__(self):
        if (not self.use_conv_bias or self.mamba_proj_bias or self.attention_bias
                or self.mlp_bias or self.tie_word_embeddings
                or (self.n_group, self.topk_group, self.n_shared_experts) != (1, 1, 1)
                or (self.mamba_hidden_act, self.mlp_hidden_act) != ("silu", "relu2")):
            raise ValueError("written: a conv bias and no other, untied head, one router "
                             "group, one shared expert, silu mixers and relu2 experts")
        if len(self.hybrid_override_pattern) < self.num_hidden_layers or set(
                self.plan) - set("ME*"):
            raise ValueError("a letter of M, E or * a layer")
        first, count = self.mamba_heads_held
        if first % self.heads_per_group or count % self.heads_per_group or not count:
            raise ValueError("the mixers' heads are held in whole groups")
        first, count = self.attention_heads_held
        group = self.num_attention_heads // self.num_key_value_heads
        whole = first % group == count % group == 0
        if not (whole or first // group == (first + count - 1) // group):
            raise ValueError("the query heads held read whole K/V heads, or one")

    @property
    def dtype(self):
        return jnp.dtype(self.compute_dtype)

    # the name mla_moe's head reads (head_logits)
    @property
    def rms_norm_eps(self) -> float:
        return self.layer_norm_epsilon

    @property
    def plan(self) -> str:
        return self.hybrid_override_pattern[:self.num_hidden_layers]

    @property
    def expert_layers(self) -> int:
        return self.plan.count("E")

    @property
    def heads_per_group(self) -> int:
        return self.mamba_num_heads // self.n_groups

    @property
    def mamba_groups_held(self) -> tuple[int, int]:
        first, count = self.mamba_heads_held
        return first // self.heads_per_group, count // self.heads_per_group

    @property
    def kv_heads_held(self) -> tuple[int, int]:
        first, count = self.attention_heads_held
        group = self.num_attention_heads // self.num_key_value_heads
        return first // group, (first + count - 1) // group - first // group + 1


def param_shapes(cfg: Config) -> dict[str, tuple[int, ...]]:
    """Every leaf's checkpoint name and shape (``nn.Linear`` is [out, in]).
    The convolution's taps are held ``[kernel, channels]``, ``nn.Conv1d``'s
    ``[channels, 1, kernel]`` turned: a leaf of 4 floats a row made the TPU
    compiler lay the whole flat table out as ``[rows, 1, 4]`` rows of 128
    lanes where a program unflattens it (99 GB; my chip run, PR 40). The
    router's ``e_score_correction_bias`` is no leaf: a buffer that the
    deployment's balancing loop moves and no gradient does, zero here
    (``route``)."""
    d = cfg.hidden_size
    shapes: dict[str, tuple[int, ...]] = {
        "backbone.embeddings.weight": (cfg.vocab_held, d),
        "backbone.norm_f.weight": (d,),
        "lm_head.weight": (cfg.vocab_held, d),
    }
    heads, groups = cfg.mamba_heads_held[1], cfg.mamba_groups_held[1]
    inner, gn = heads * cfg.mamba_head_dim, groups * cfg.ssm_state_size
    q_heads, kv_heads = cfg.attention_heads_held[1], cfg.kv_heads_held[1]
    first, count = cfg.experts_held
    for i, kind in enumerate(cfg.plan):
        shapes[_layer(i) + "norm.weight"] = (d,)
        m = _layer(i) + "mixer."
        if kind == "M":
            shapes[m + "in_proj.weight"] = (2 * inner + 2 * gn + heads, d)
            shapes[m + "conv1d.weight"] = (cfg.conv_kernel, inner + 2 * gn)
            shapes[m + "conv1d.bias"] = (inner + 2 * gn,)
            for name in ("A_log", "D", "dt_bias"):
                shapes[m + name] = (heads,)
            shapes[m + "norm.weight"] = (inner,)
            shapes[m + "out_proj.weight"] = (d, inner)
        elif kind == "*":
            shapes[m + "q_proj.weight"] = (q_heads * cfg.head_dim, d)
            shapes[m + "k_proj.weight"] = (kv_heads * cfg.head_dim, d)
            shapes[m + "v_proj.weight"] = (kv_heads * cfg.head_dim, d)
            shapes[m + "o_proj.weight"] = (d, q_heads * cfg.head_dim)
        else:
            latent, width = cfg.moe_latent_size, cfg.moe_intermediate_size
            shapes[m + "gate.weight"] = (cfg.n_routed_experts, d)
            shapes[m + LATENT[0]] = (latent, d)
            shapes[m + LATENT[1]] = (d, latent)
            shared = cfg.moe_shared_expert_intermediate_size
            shapes[m + "shared_experts.up_proj.weight"] = (shared, d)
            shapes[m + "shared_experts.down_proj.weight"] = (d, shared)
            for e in range(first, first + count):
                shapes[m + f"experts.{e}.up_proj.weight"] = (width, latent)
                shapes[m + f"experts.{e}.down_proj.weight"] = (latent, width)
    return shapes


def init_params(key: jax.Array, cfg: Config) -> dict[str, jax.Array]:
    """Matrices normal(0, ``init_std``), the embedding normal(0, 1), norms 1;
    a mixer's ``A_log = log U[1, 16]``, ``dt_bias`` the inverse softplus of
    ``exp U[log time_step_min, log time_step_max]`` floored at
    ``time_step_floor``, ``D = 1``, its convolution's taps and bias U(-1/2,
    1/2) as a PyTorch ``Conv1d`` of fan-in ``conv_kernel`` (the Mamba-2
    convention)."""
    shapes = param_shapes(cfg)
    params = {}
    lo, hi = math.log(cfg.time_step_min), math.log(cfg.time_step_max)
    bound = 1.0 / math.sqrt(cfg.conv_kernel)
    for k, (name, shape) in zip(jax.random.split(key, len(shapes)), sorted(shapes.items())):
        leaf = name.rsplit(".", 1)[-1] if name.startswith("backbone.layers.") else name
        if leaf == "A_log":
            params[name] = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
        elif leaf == "dt_bias":
            dt = jnp.maximum(jnp.exp(jax.random.uniform(k, shape, jnp.float32, lo, hi)),
                             cfg.time_step_floor)
            params[name] = dt + jnp.log(-jnp.expm1(-dt))
        elif leaf == "D" or len(shape) == 1 and "conv1d" not in name:
            params[name] = jnp.ones(shape, jnp.float32)
        elif "conv1d" in name:
            params[name] = jax.random.uniform(k, shape, jnp.float32, -bound, bound)
        else:
            std = 1.0 if name == "backbone.embeddings.weight" else cfg.init_std
            params[name] = std * jax.random.normal(k, shape, jnp.float32)
    return params


# --- the mixer ---------------------------------------------------------------------


def causal_conv(x: jax.Array, w: jax.Array, b: jax.Array | None = None) -> jax.Array:
    """The depthwise causal convolution of ``x [T, C]`` by the taps ``w [K,
    C]`` (a ``Conv1d`` of ``groups = C``, padded by K - 1 and cut to T):
    ``out_t = b + sum_k w[k] x_{t - K + 1 + k}``, float32; with ``b`` None a
    convolution without bias."""
    n, k = x.shape[0], w.shape[0]
    xp = jnp.pad(x.astype(jnp.float32), ((k - 1, 0), (0, 0)))
    taps = sum(w[i] * xp[i:i + n] for i in range(k))
    return taps if b is None else b + taps


def gated_group_norm(y: jax.Array, z: jax.Array, w: jax.Array, groups: int,
                     eps: float) -> jax.Array:
    """RMSNorm of ``y * silu(z)`` (the gate first: ``norm_before_gate``
    false) over each of ``groups`` groups of the last axis, times ``w``."""
    v = y * jax.nn.silu(z)
    v = v.reshape(*v.shape[:-1], groups, -1)
    v = v * lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True) + eps)
    return v.reshape(*y.shape) * w


def _ssd_chunks(x, delta, a, b, c, chunk: int, dtype):
    """``y [T, H, P]`` of the recurrence ``S_t = exp(Δ_t A) S_{t-1} + Δ_t x_t
    ⊗ B_t``, ``y_t = S_t C_t`` from a zero state, in chunks of ``chunk``
    positions: ``x [T, H, P]``, ``delta [T, H]``, ``a [H]`` (negative), ``b, c
    [T, G, N]``; head h reads group h // (H / G). The positions past T that
    make whole chunks carry Δ = 0 and x = 0: they add nothing."""
    n, h, p = x.shape
    g, ns = b.shape[1:]
    pad = -n % chunk
    x, delta, b, c = (jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1)) for v in (x, delta, b, c))
    k, j = (n + pad) // chunk, h // g
    f32 = jnp.float32
    mm = partial(jnp.einsum, preferred_element_type=f32, precision=_precision(dtype))
    cast = lambda v: v.astype(dtype)
    # a chunk's positions first, heads by group: [chunks, l, g, j, ...]
    # log decay from the chunk's start
    cum = jnp.cumsum((delta * a).reshape(k, chunk, g, j), axis=1)
    xd = (x * delta[..., None]).reshape(k, chunk, g, j, p)
    b, c = b.reshape(k, chunk, g, ns), c.reshape(k, chunk, g, ns)
    # within a chunk: y_l = sum_{s <= l} (C_l . B_s) exp(cum_l - cum_s) Δ_s x_s
    cb = mm("klgn,ksgn->kgls", cast(c), cast(b))
    seen = jnp.arange(chunk)[:, None] >= jnp.arange(chunk)[None, :]
    gap = jnp.moveaxis(cum, 1, 3)[..., :, None] - jnp.moveaxis(cum, 1, 3)[..., None, :]
    decay = jnp.exp(jnp.where(seen, gap, -jnp.inf))  # [k, g, j, l, s]
    y = mm("kgjls,ksgjp->klgjp", cast(decay * cb[:, :, None]), cast(xd))
    # the state each chunk leaves from its own positions, and the pass over
    # the chunks: the state entering chunk k, float32
    tail = jnp.exp(cum[:, -1:] - cum)
    left = mm("klgn,klgjp->kgjpn", cast(b), cast(xd * tail[..., None]))

    def carry(state, chunk_k):
        left_k, cum_k = chunk_k
        return jnp.exp(cum_k)[..., None, None] * state + left_k, state

    _, entering = lax.scan(carry, jnp.zeros((g, j, p, ns), f32), (left, cum[:, -1]))
    y = y + mm("klgn,kgjpn->klgjp", cast(c), cast(entering)) * jnp.exp(cum)[..., None]
    return y.reshape(k * chunk, h, p)[:n]


@partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _ssd(x, delta, a, b, c, chunk: int, dtype):
    return _ssd_chunks(x, delta, a, b, c, chunk, dtype)


def _ssd_fwd(x, delta, a, b, c, chunk, dtype):
    # the output is what a layer's checkpoint keeps (``mla_moe.layer_checkpoint``):
    # the recomputed layer reads it and runs the scan no second time; the
    # inputs come again from the projection and the convolution, whose own
    # backward needs them made again anyway
    y = checkpoint_name(_ssd_chunks(x, delta, a, b, c, chunk, dtype), SSM_OUT)
    return y, (x, delta, a, b, c)


def _ssd_bwd(chunk, dtype, res, g):
    with jax.named_scope("st.ssm.scan"):  # a backward rule is traced outside the caller's scope
        return jax.vjp(partial(_ssd_chunks, chunk=chunk, dtype=dtype), *res)[1](g)


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd_scan(x, delta, a, b, c, chunk: int, dtype):
    """The chunked scan (:func:`_ssd_chunks`) with its backward rule: the
    chunked form differentiated again from its inputs. Counted as traced
    (``st_ssm_traces_total``), with the heads it holds, the chunks it runs and
    the bytes of the output its layer's checkpoint keeps."""
    n, h, p = x.shape
    pod_tier().count_ssm_trace(heads=h, chunks=-(-n // chunk), saved_bytes=n * h * p * 4)
    return _ssd(x, delta, a, b, c, chunk, jnp.dtype(dtype))


def mamba_mixer(p: dict, x: jax.Array, cfg: Config) -> jax.Array:
    """The Mamba-2 mixer of ``x [T, hidden]`` (already normed) over the heads
    held here; ``p`` holds the ``mixer.*`` leaves."""
    n, dt = x.shape[0], cfg.dtype
    heads, groups = cfg.mamba_heads_held[1], cfg.mamba_groups_held[1]
    hp, ns = cfg.mamba_head_dim, cfg.ssm_state_size
    inner = heads * hp
    with jax.named_scope("st.ssm.proj"):
        z, xbc, dt_raw = jnp.split(_mm(x, p["in_proj.weight"], dt),
                                   [inner, 2 * inner + 2 * groups * ns], axis=-1)
    with jax.named_scope("st.ssm.conv"):
        xbc = jax.nn.silu(causal_conv(xbc, p["conv1d.weight"], p["conv1d.bias"]))
    xs, b, c = jnp.split(xbc, [inner, inner + groups * ns], axis=-1)
    xs = xs.reshape(n, heads, hp)
    with jax.named_scope("st.ssm.scan"):
        delta = jax.nn.softplus(dt_raw + p["dt_bias"])
        y = ssd_scan(xs, delta, -jnp.exp(p["A_log"]), b.reshape(n, groups, ns),
                     c.reshape(n, groups, ns), cfg.chunk_size, dt)
        y = y + p["D"][:, None] * xs
    with jax.named_scope("st.ssm.norm"):
        y = gated_group_norm(y.reshape(n, inner), z, p["norm.weight"], groups,
                             cfg.layer_norm_epsilon)
    with jax.named_scope("st.ssm.proj"):
        return _mm(y, p["out_proj.weight"], dt)


# --- layers, trunk, loss --------------------------------------------------------------


def route(p: dict, u: jax.Array, cfg: Config):
    """``mla_moe.route`` with the correction bias at zero (see
    :func:`param_shapes`): sigmoid scores, the top ``num_experts_per_tok``,
    weights normalised over them and times ``routed_scaling_factor``."""
    bias = jnp.zeros((cfg.n_routed_experts,), jnp.float32)
    return mla_moe.route({**p, "gate.e_score_correction_bias": bias}, u, cfg)


def block(p: dict, x: jax.Array, cfg: Config, kind: str):
    """Layer ``h + f(RMSNorm(h))`` of ``kind`` (a letter of the pattern) with
    its leaves ``p``; the counters of an expert layer, else ``None``."""
    u = rms_norm(x, p["norm.weight"], cfg.layer_norm_epsilon)
    m = _sub(p, "mixer.")
    if kind == "M":
        with jax.named_scope("st.ssm"):
            return x + mamba_mixer(m, u, cfg), None
    if kind == "*":
        with jax.named_scope("st.attn"):
            return x + swa_moe.attention(m, u, None, None, cfg, cfg.attention_heads_held[1],
                                         cfg.kv_heads_held[1]), None
    with jax.named_scope("st.moe"):
        f, aux = moe(m, u, cfg, router=route, act=cfg.mlp_hidden_act,
                     names=("up_proj", "down_proj"), latent=LATENT)
    return x + f, aux


def trunk(params: dict, tokens: jax.Array, cfg: Config):
    """The last layer's output ``[T, hidden]`` (before ``norm_f``) of one
    sequence and the expert layers' counters."""
    with jax.named_scope("st.embed"):
        x = params["backbone.embeddings.weight"][tokens]
    auxes = []
    for i, kind in enumerate(cfg.plan):
        x, aux = layer_checkpoint(partial(block, cfg=cfg, kind=kind))(
            _sub(params, _layer(i)), x)
        auxes += [aux] if aux is not None else []
    return x, auxes


def forward(params: dict, tokens: jax.Array, cfg: Config) -> jax.Array:
    """float32 logits ``[T, vocab_held]`` of one sequence ``tokens [T]``:
    position i's row scores token i+1 (for checks and small inputs)."""
    y, _ = trunk(params, tokens, cfg)
    return head_logits(y, params["backbone.norm_f.weight"], params["lm_head.weight"], cfg)


def loss_fn(params: dict, batch: jax.Array, cfg: Config,
            positions: jax.Array | None = None) -> tuple[jax.Array, Any]:
    """``(loss, aux)`` of ``batch [B, T]`` token ids: ``swa_moe.loss_fn`` over
    this trunk and ``norm_f`` (the next-token cross-entropy over the held
    slice of the vocabulary; ``aux``'s ``moe_*`` one entry an expert layer)."""
    return swa_moe.loss_fn(params, batch, cfg, positions, trunk=trunk,
                           norm="backbone.norm_f.weight")
