"""Device time of the Mosaic custom calls (ops/codec_pallas.py:
``quantize_rows`` and ``apply_rows_batch``) per step, in ms: the union of
their events on a device's ``XLA Ops`` line inside the traced window,
averaged over the devices, over the steps traced. Layer ops.codec_pallas."""


def read(obs):
    t = obs.get("trace")
    if not t or not t.get("steps"):
        return None
    return 1e3 * t["kernel_s"] / t["steps"]
