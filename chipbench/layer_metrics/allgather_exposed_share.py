"""The part of collective time during which no kernel and no other XLA
operation ran on that device, in %. Near 100 in the fused program, where
nothing is scheduled beside the all-gather; the overlap arm (ROADMAP S4)
exists to lower it. Layer parallel.ici."""


def read(obs):
    t = obs.get("trace")
    if not t or not t.get("collective_s"):
        return None
    return 100.0 * t["collective_exposed_s"] / t["collective_s"]
