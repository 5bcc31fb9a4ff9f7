"""Device self time of every ``st.kda.scan`` scope (the KDA mixers' chunked
delta-rule scan: the L2 norms of q and k, the decayed products within each
chunk, the triangular solve, the pass over the chunks' states and the
output, forward and in its backward rule), in ms per step, all mixers
together (chipbench/scope_reduce.py). Layer models."""

from chipbench import scope_reduce


def read(obs):
    return scope_reduce.under(obs.get("scopes"), "st.kda.scan")
