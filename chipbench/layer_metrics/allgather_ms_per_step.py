"""Device time of the collectives (the all-gather of packed words and of
scales over the peer axis) per step, in ms, averaged over the devices. An
async pair counts from the start of its ``-start`` to the end of its
``-done``. Layer parallel.ici."""


def read(obs):
    t = obs.get("trace")
    if not t or not t.get("steps") or not t.get("collective_s"):
        return None
    return 1e3 * t["collective_s"] / t["steps"]
