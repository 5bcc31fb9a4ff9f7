"""The program's own step log, read after the job has run.

``PodTrainer.step`` keeps every call as a host span ``st:train.step``
(``program=sync`` on the beat that exchanges, ``local`` else) in the
process's flight recorder, and Python's collector leaves a ``st:gc`` event a
pause of a millisecond or more (``shared_tensor_tpu.utils.profiling.PodTier``,
since PR 38). The readers here run in the program's process after
``job.run(ctx)``, so they read that log and edit no job. A program that
keeps no spans (older than PR 38, or ``ST_OBS=0``) and a cell that drives no
``PodTrainer`` give an empty log, and every reader gives ``None``.
"""

from __future__ import annotations

import json

STEP, GC = "st:train.step", "st:gc"
_printed = False


def rows() -> list:
    """The program's spans by start: ``(name, t0_ns, t1_ns, parent, step,
    attrs)`` rows, ``[]`` where the program keeps none. The first call that
    finds any prints ``PodTier.span_table()`` (per name: calls, total and
    self seconds; the set-up's spans are in it) as one ``[chipbench] st
    spans`` line."""
    global _printed
    from shared_tensor_tpu.utils import profiling

    pod = profiling.pod_tier()
    found = pod.spans() if hasattr(pod, "spans") else []
    if found and not _printed:
        _printed = True
        table = {name: [t["calls"], round(t["total_s"], 6), round(t["self_s"], 6)]
                 for name, t in pod.span_table().items()}
        print("[chipbench] st spans " + json.dumps(table), flush=True)
    return found


def arm_steps(obs: dict) -> list | None:
    """The ``st:train.step`` spans of program ``sync`` that belong to the
    traced run's untraced arm: of the newest ``trace.steps`` +
    ``host.dispatch_calls`` such spans, all but the last ``trace.steps``
    (the traced window runs last; in ``jobs/train.py`` the ``sync=False``
    arm between the two runs program ``local`` and falls out by its
    label). ``None`` where the log holds none of them."""
    traced = int((obs.get("trace") or {}).get("steps") or 0)
    calls = int((obs.get("host") or {}).get("dispatch_calls") or 0)
    if not calls:
        return None
    steps = [r for r in rows() if r.name == STEP and r.attrs.get("program") == "sync"]
    newest = steps[-(traced + calls):]
    return newest[:len(newest) - traced] or None


def gaps_ns(arm: list) -> list[int]:
    """From one step span's end to the next one's start: what the caller
    did between two calls (in a closed loop, wait for the step in flight)."""
    return [b.t0_ns - a.t1_ns for a, b in zip(arm, arm[1:])]


def pauses_ns(arm: list) -> list[int]:
    """The durations of the ``st:gc`` events that overlap the arm's
    interval, first span's start to last span's end."""
    lo, hi = arm[0].t0_ns, arm[-1].t1_ns
    return [r.t1_ns - r.t0_ns for r in rows()
            if r.name == GC and r.t1_ns > lo and r.t0_ns < hi]
