"""The longest of the untraced arm's ``st:train.step`` spans in ms
(``chipbench/pod_spans.py``): a call of ``PodTrainer.step`` that blocked, on
a full queue, a build or a collection of Python's. Layer train.async_sgd;
moves train_step_p95_ms."""

from chipbench import pod_spans


def read(obs):
    arm = pod_spans.arm_steps(obs)
    if not arm:
        return None
    return max(r.t1_ns - r.t0_ns for r in arm) / 1e6
