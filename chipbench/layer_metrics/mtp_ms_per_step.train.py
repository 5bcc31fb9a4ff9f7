"""Device self time of the whole ``st.mtp`` scope (the multi-token-prediction
module: its projection, its block's ``st.mla`` and ``st.moe``, its head and
loss), in ms per step (chipbench/scope_reduce.py). Layer models."""

from chipbench import scope_reduce


def read(obs):
    return scope_reduce.under(obs.get("scopes"), "st.mtp")
