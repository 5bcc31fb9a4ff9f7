"""Device self time of every ``st.attn.gate`` scope (the per-head output gate
of models/gated_swa_moe.py's attention, as models/swa_moe.py::attention
computes it: the gate's product with the layer's input, the sigmoid and the
multiply into the heads' outputs, forward and backward, all layers together),
in ms per step, from the traced window and the compiled step's text
(chipbench/scope_reduce.py). None where the program has no such scope. Layer
models."""

from chipbench import scope_reduce


def read(obs):
    return scope_reduce.under(obs.get("scopes"), "st.attn.gate")
