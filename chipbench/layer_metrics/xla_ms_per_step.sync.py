"""Device time per step outside the two kernels and the collectives, in ms:
``add_updates_raw``, ``_leaf_scales``, the transposes and the lane-padded
temporaries around the kernels (ROADMAP S3's and S6's levers). Layer
parallel.ici."""


def read(obs):
    t = obs.get("trace")
    if not t or not t.get("steps"):
        return None
    return 1e3 * t["xla_s"] / t["steps"]
