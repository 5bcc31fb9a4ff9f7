"""Device self time of every ``st.head_loss`` scope (final norm, logits in
blocks and the float32 cross-entropy, for the main loss and the prediction
module's), in ms per step (chipbench/scope_reduce.py). Layer models."""

from chipbench import scope_reduce


def read(obs):
    return scope_reduce.under(obs.get("scopes"), "st.head_loss")
