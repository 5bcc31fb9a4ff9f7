"""Device self time of every ``st.mla`` scope (the projections and the
attention of every block, the prediction module's among them), in ms per
step, from the traced window and the compiled step's text
(chipbench/scope_reduce.py). Layer models."""

from chipbench import scope_reduce


def read(obs):
    return scope_reduce.under(obs.get("scopes"), "st.mla")
