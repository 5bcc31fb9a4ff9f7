"""1 - (median step of ``PodTrainer(sync=False)``) / (median step of the
default compressed fused program), in %: both through the same entry point
in the traced run's untraced arms, each median over groups of steps spanning
250 ms or more. An outside timing: the tracing issue replaces it with one
read from scopes inside the step. Layer parallel.ici."""


def read(obs):
    host = obs.get("host") or {}
    if not host.get("step_ms") or not host.get("step_ms_nosync"):
        return None
    return 100.0 * (1.0 - host["step_ms_nosync"] / host["step_ms"])
