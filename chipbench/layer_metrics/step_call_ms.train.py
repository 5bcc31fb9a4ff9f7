"""Mean duration in ms of the untraced arm's ``st:train.step`` spans: the
host time inside ``PodTrainer.step`` as the program's own span log has it
(``chipbench/pod_spans.py``), the in-program twin of ``dispatch_ms.train``.
Layer train.async_sgd; moves train_samples_per_s once a step is short enough
for the host to hold the chip back."""

from chipbench import pod_spans


def read(obs):
    arm = pod_spans.arm_steps(obs)
    if not arm:
        return None
    return sum(r.t1_ns - r.t0_ns for r in arm) / len(arm) / 1e6
