"""Device time of the ``apply_rows_batch`` Mosaic kernel (ops/codec_pallas.py,
``name="st_apply_rows_batch"``) per step, in ms: the seconds per device of its
custom call among the ten longest operations of the traced window
(``trace["device_ops"]``), over the steps traced. ``None`` where no operation
carries the name (a program from before the kernels had names, the CPU
rehearsal) or the kernel is not among the ten longest. Layer ops.codec_pallas."""

import re

LABEL = re.compile(r"^st_apply_rows_batch(\.\d+)? = .* custom-call$")


def read(obs):
    t = obs.get("trace")
    if not t or not t.get("steps"):
        return None
    seconds = [s for label, s in t.get("device_ops", ()) if LABEL.match(label)]
    if not seconds:
        return None
    return 1e3 * sum(seconds) / t["steps"]
