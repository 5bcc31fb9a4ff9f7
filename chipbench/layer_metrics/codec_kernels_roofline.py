"""Roofline share of the two codec kernels (``quantize_rows`` and
``apply_rows_batch``) together, in %: the bytes they must move
by their shapes (chipbench/counts.py::sync_step_kernel_bytes: residual and
values read and written once in float32, one bit an element and frame for
the words, scales and row counts at their true size) over the chip's HBM
peak, over the kernels' device time. Both kernels are bound by HBM bandwidth:
they do a compare, a pack and an add an element. Layer ops.codec_pallas."""


def read(obs):
    t, peaks = obs.get("trace"), obs.get("peaks")
    if not t or not peaks or not t.get("kernel_s"):
        return None
    least_s = obs["counts"]["kernel_bytes_per_step"] / peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (t["kernel_s"] / t["steps"])
