"""The longest collection of Python's cyclic collector, in ms, among the
``st:gc`` events (a pause of 1 ms or more each) that overlap the untraced
arm's interval (``chipbench/pod_spans.py``); 0.0 when none did. Layer
train.async_sgd; moves train_step_p95_ms."""

from chipbench import pod_spans


def read(obs):
    arm = pod_spans.arm_steps(obs)
    if not arm:
        return None
    return max(pod_spans.pauses_ns(arm), default=0) / 1e6
