"""Share of the MXU's bf16 peak the two attention kernels (``st_attn_fwd``,
``st_attn_bwd`` of ops/attention_pallas.py) reach together, in %: the FLOPs
they must do a step by exact (query, key) pairs (the configuration's counts
module, ``attention_kernel_flops``: 2 products forward, 5 backward, a pair a
head; a masked or skipped pair counts nothing, so visiting fewer tiles reads
higher) over the chip's bf16 peak (chipbench/peaks.json), over the kernels'
device time a step, read from every such event of the traced window by name.
Both kernels are bound by the MXU and the instruction issue around it, not by
HBM. Layer models."""


def read(obs):
    t, peaks = obs.get("attn_kernels"), obs.get("peaks")
    flops = (obs.get("counts") or {}).get("attn_kernel_flops_per_step")
    if not t or not peaks or not flops or not t["fwd"] + t["bwd"]:
        return None
    return 100.0 * flops / peaks["bf16_flops_per_s"] / (t["fwd"] + t["bwd"])
