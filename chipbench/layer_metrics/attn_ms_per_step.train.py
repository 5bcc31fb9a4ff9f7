"""Device self time of every ``st.attn`` scope (the four projections, RoPE and
the attention of every layer of models/swa_moe.py, window and full alike), in
ms per step, from the traced window and the compiled step's text
(chipbench/scope_reduce.py). Layer models."""

from chipbench import scope_reduce


def read(obs):
    return scope_reduce.under(obs.get("scopes"), "st.attn")
