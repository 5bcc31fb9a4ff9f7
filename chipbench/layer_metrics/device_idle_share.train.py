"""1 - (union of device operation intervals) / (traced window), in %,
averaged over the devices used. The breakdown's ``idle_gaps`` says what the
benchmark's loop was doing in the gaps. Layer device."""


def read(obs):
    t = obs.get("trace")
    if not t or not t.get("window_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
