"""Host time inside the benchmark's ``add_scaled`` (the public
``add_updates_raw`` under one jit) plus ``sync_step`` until both calls
return, per step, in ms: summed over the untraced arm's steps and divided by
their number. Layer parallel.ici; moves sync_equiv_rate."""


def read(obs):
    host = obs.get("host") or {}
    if not host.get("dispatch_calls"):
        return None
    return 1e3 * host["dispatch_s"] / host["dispatch_calls"]
