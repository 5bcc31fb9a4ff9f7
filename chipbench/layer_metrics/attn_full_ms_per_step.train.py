"""Device self time of every ``st.attn.full`` scope (the attention of the
full-attention layers of models/swa_moe.py, forward and backward: the two
kernels over the whole causal triangle's tiles and what XLA does around
them), in ms per step, all such layers together, from the traced window and
the compiled step's text (chipbench/scope_reduce.py). Layer models."""

from chipbench import scope_reduce


def read(obs):
    return scope_reduce.under(obs.get("scopes"), "st.attn.full")
