"""Device self time of every ``st.kda`` scope (the Kimi Delta Attention
mixers of models/kimi_linear.py: their projections, short convolutions,
gates, chunked scan and gated output norm, forward and backward), in ms per
step, all mixers together, from the traced window and the compiled step's
text (chipbench/scope_reduce.py). Layer models."""

from chipbench import scope_reduce


def read(obs):
    return scope_reduce.under(obs.get("scopes"), "st.kda")
