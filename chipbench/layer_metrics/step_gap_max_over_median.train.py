"""Over the untraced arm, the longest time from one ``st:train.step`` span's
end to the next one's start over the median such time
(``chipbench/pod_spans.py``): 1.0 when the caller only waits for the step in
flight, 3 for a one-second pause on a 0.5 s step. What the host did between
two calls, which no span of the program covers. Layer train.async_sgd; moves
train_step_p95_ms."""

import statistics

from chipbench import pod_spans


def read(obs):
    arm = pod_spans.arm_steps(obs)
    gaps = pod_spans.gaps_ns(arm) if arm else []
    if not gaps or statistics.median(gaps) <= 0:
        return None
    return max(gaps) / statistics.median(gaps)
