"""Share of its roofline the KDA mixers' chunked delta-rule scan reaches, in
%: the larger of its FLOPs over the chip's bf16 peak and its bytes over the
chip's HBM bandwidth (chipbench/peaks.json), a step, over the device self
time of every ``st.kda.scan`` scope of the traced window
(chipbench/scope_reduce.py). The FLOPs and bytes are the configuration's
counts module's (``kda_scan_flops``: the chunked algorithm's products forward
and their cotangents; ``kda_scan_bytes``: the scan's operands and results by
shape and dtype). The scan is XLA's, not a named kernel, so its time is read
by scope. Layer models.

The job hands a reader the FLOPs a sample of the whole step
(``counts.train_flops_per_sample``) and not the configuration, so the
configuration and length are found as the cell of ``BENCHMARK.json`` (at its
timed or its rehearsal sizes) whose counts module gives that same number."""

import importlib

from chipbench import harness, scope_reduce


def scan_counts(flops_per_sample):
    """``(FLOPs, bytes)`` of the scan a step, of the cell whose configuration
    and length give the job's ``flops_per_sample``; None where none does."""
    manifest = harness.load_json(harness.ROOT / "BENCHMARK.json")
    for entry in manifest["workloads"]:
        cfg = harness.load_json(harness.config_file(manifest, entry["config"]))
        if "counts_module" not in cfg:
            continue
        counts = importlib.import_module(cfg["counts_module"])
        if not hasattr(counts, "kda_scan_flops"):
            continue
        cell = harness.load_json(harness.ROOT / "chipbench" / "workloads" / f"{entry['name']}.json")
        per_step = cell["per_peer_batch"] * cell["mesh"][0] / cell["chips"]
        for m, seq in ((cfg, cell["sequence_length"]),
                       (cfg["rehearsal"]["model"], cell["rehearsal"]["sequence_length"])):
            if counts.train_flops_per_sequence(m, seq) == flops_per_sample:
                return (per_step * counts.kda_scan_flops(m, seq),
                        per_step * counts.kda_scan_bytes(m, seq))
    return None


def read(obs):
    ms, peaks = scope_reduce.under(obs.get("scopes"), "st.kda.scan"), obs.get("peaks")
    flops_per_sample = (obs.get("counts") or {}).get("train_flops_per_sample")
    if not ms or not peaks or not flops_per_sample:
        return None
    found = scan_counts(flops_per_sample)
    if found is None:
        return None
    flops, moved = found
    bound_s = max(flops / peaks["bf16_flops_per_s"], moved / peaks["hbm_bytes_per_s"])
    return 100.0 * bound_s / (ms / 1e3)
