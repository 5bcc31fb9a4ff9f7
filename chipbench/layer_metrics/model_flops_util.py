"""Analytic forward + backward FLOPs a sample (chipbench/counts.py) times the
samples a second a chip of the traced run's untraced arm, over the chip's
bf16 peak (chipbench/peaks.json), in %. An end-to-end utilization of the
MXU's peak, not a kernel's roofline share: it counts what the model must do,
whatever the step spends on the sync. Layer models."""


def read(obs):
    host, peaks = obs.get("host") or {}, obs.get("peaks")
    if not peaks or not host.get("samples_per_s_per_chip"):
        return None
    flops = obs["counts"]["train_flops_per_sample"]
    return 100.0 * flops * host["samples_per_s_per_chip"] / peaks["bf16_flops_per_s"]
