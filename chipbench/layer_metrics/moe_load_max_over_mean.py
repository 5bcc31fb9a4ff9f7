"""The largest held expert's load over the mean held expert's, the worst
expert layer and peer of the newest step's ``aux`` (the program's counter,
computed on the device in the fused step; 1 is perfect balance). Layer
models."""


def read(obs):
    ratios = (obs.get("aux") or {}).get("moe_load_max_over_mean")
    if not ratios:
        return None
    flat = [r for peer in ratios for r in (peer if isinstance(peer, list) else [peer])]
    return max(flat) if flat else None
