"""``peak_bytes_in_use`` after the window, largest over the devices used, in
GB: the replica, the residual, one resident update and the sync step's
temporaries. It decides how deep a table fits (ROADMAP S6). Layer device."""


def read(obs):
    peak = (obs.get("memory") or {}).get("peak_bytes")
    return peak / 1e9 if peak else None
