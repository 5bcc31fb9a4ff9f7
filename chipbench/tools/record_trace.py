"""Cut the plain form of a chip trace (``run.py --keep-trace DIR`` writes
``DIR/<cell>/plain.json``) down to a few steps and keep it under
``chipbench/testdata/`` with what the reduction reads from it today.

    python3 chipbench/tools/record_trace.py DIR/<cell>/plain.json <cell> <steps> "<origin>"

The new window runs from the start of one ``chipbench:dispatch`` annotation
to the start of the one ``steps`` later; times are re-based to 0 and rounded
to whole nanoseconds. tests/chipbench/test_chipbench_reduce.py holds the
reduction to the recorded figures and to an independent sweep.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def main(argv) -> int:
    from chipbench import harness, trace_reduce as tr

    src, cell, steps, origin = argv[0], argv[1], int(argv[2]), argv[3]
    with open(src) as f:
        plain = json.load(f)
    dispatch = sorted(s for n, s, _ in plain["host"] if n == "chipbench:dispatch")
    first = len(dispatch) // 2  # mid-window: the pipeline is full
    lo, hi = dispatch[first], dispatch[first + steps]
    cut = {"devices": {}, "host": [["chipbench:window", 0.0, float(round(hi - lo))]]}
    for n, s, d in plain["host"]:
        if n != tr.WINDOW and s + d > lo and s < hi:
            cut["host"].append([n, float(round(s - lo)), float(round(d))])
    for dev, events in plain["devices"].items():
        cut["devices"][dev] = [
            [n, float(round(s - lo)), float(round(d)), c]
            for n, s, d, c in events if s + d > lo and s < hi
        ]
    summary = tr.reduce(cut, steps, harness.kernel_patterns())
    keys = ("devices", "busy_s", "kernel_s", "collective_s", "collective_exposed_s",
            "xla_s", "window_s")
    out = {"origin": origin, "cell": cell, "steps": steps, "trace": cut,
           "expect": {k: summary[k] for k in keys}}
    dst = ROOT / "chipbench" / "testdata" / f"trace_{cell}.json"
    with open(dst, "w") as f:
        json.dump(out, f, separators=(",", ":"))
    print(dst, dst.stat().st_size, "bytes", json.dumps(out["expect"]))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
