"""Host time inside ``PodTrainer.step`` until the call returns, per step, in
ms: summed over every call of the untraced arm and divided by their number
(one call is well under the host clock's half millisecond). Layer
train.async_sgd; moves train_samples_per_s once a step is short enough for
the host to hold the chip back."""


def read(obs):
    host = obs.get("host") or {}
    if not host.get("dispatch_calls"):
        return None
    return 1e3 * host["dispatch_s"] / host["dispatch_calls"]
