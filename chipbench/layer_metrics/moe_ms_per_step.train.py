"""Device self time of every ``st.moe`` scope (router, dispatch, experts,
combine and shared expert of every expert layer, the prediction module's
among them), in ms per step (chipbench/scope_reduce.py). Layer models."""

from chipbench import scope_reduce


def read(obs):
    return scope_reduce.under(obs.get("scopes"), "st.moe")
