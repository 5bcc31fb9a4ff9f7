"""Device self time of the sync inside the fused train step, in ms per step:
the scopes ``st.add_updates``, ``st.codec_send`` and ``st.codec_apply`` with
everything under them (chipbench/scope_reduce.py). The in-program reading of
what ``sync_share_of_step`` times from outside with a second trainer. Layer
parallel.ici."""

from chipbench import scope_reduce

SYNC = ("st.add_updates", "st.codec_send", "st.codec_apply")


def read(obs):
    return scope_reduce.total_of(obs.get("scopes"), lambda parts: parts[0] in SYNC)
