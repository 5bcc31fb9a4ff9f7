"""From a ``jax.profiler`` trace to the numbers the per-layer metrics read.

The benchmark brings its own reduction, so every PR computes the same number
in the same way and no PR that claims a gain can change it. Two stages:

- :func:`load_xplane` reads the profiler's ``.xplane.pb`` with nothing but JAX
  (``jax.profiler.ProfileData``) into plain lists: the device planes' operation
  lines and the ``chipbench:*`` annotations of the host's python line. That
  plain form is what ``chipbench/testdata/`` keeps a small recording of.
- :func:`reduce` turns the plain form into seconds: per device the union of
  operation intervals (busy), kernel, collective and other-XLA time, the part
  of collective time with no compute running, the operations that took most
  time and the longest idle gaps by what the benchmark's loop was doing.

How the trace of a TPU v5e looks today (PERF.md, "Reading a trace", has the
listing this was written against): one plane ``/device:TPU:<n>`` per chip
with the lines ``XLA Modules``, ``XLA Ops``, ``Steps`` and a few more; every
event of ``XLA Ops`` is one HLO instruction, named by its whole HLO text. The Mosaic
kernels carry no ``name=`` in the program yet; they are told by their custom
call's target (:data:`KERNEL_CATEGORY`). A PR that gives kernels names the
reduction should know adds a pattern file (``chipbench/kernel_names/``), not
an edit here (``extra_kernel_patterns``).
"""

from __future__ import annotations

import re
from typing import Iterable

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
HOST_PLANE = "/host:CPU"
ANNOTATION_PREFIX = "chipbench:"
WINDOW = ANNOTATION_PREFIX + "window"

#: HLO opcodes of collectives; ``-start`` and ``-done`` make an async pair.
COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast",
)
#: The category of a Mosaic kernel: a custom call whose target is Pallas's.
#: (XLA's own custom calls, such as the concatenation in the train step's
#: flatten, are ``custom-call:<other target>`` and count as XLA time.)
KERNEL_CATEGORY = "custom-call:tpu_custom_call"

_HLO = re.compile(r"^%(\S+) = (.*)$", re.S)
_OPCODE = re.compile(r"(?:^| )([a-z][a-z0-9\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_LAYOUT = re.compile(r"\{[^{}]*\}")


def parse_op(name: str) -> tuple[str, str]:
    """(label, category) of one ``XLA Ops`` event. Today's trace names an
    event by its whole HLO instruction, ``%fusion.3 = f32[201]{...}
    fusion(...), kind=...``: the label is ``fusion.3 = f32[201] fusion`` (name,
    result shape without layouts, opcode; at most 96 characters) and the
    category the opcode, with the target for a custom call. A name that is
    not HLO text is its own label, and its category its name less a trailing
    ``.N``."""
    m = _HLO.match(name)
    if not m:
        return name[:96], re.sub(r"\.\d+$", "", name)
    short, rest = m.group(1), m.group(2)
    op = _OPCODE.search(rest)
    opcode = op.group(1) if op else ""
    category = opcode
    if opcode == "custom-call":
        target = _TARGET.search(rest)
        category = f"custom-call:{target.group(1) if target else ''}"
    shape = _LAYOUT.sub("", rest[: op.start()] if op else "").strip()
    return f"{short} = {shape} {opcode}"[:96], category


# --- loading ---------------------------------------------------------------


def load_xplane(path: str, rehearsal: bool = False) -> dict:
    """The profiler's file as plain data::

        {"devices": {"0": [[label, start_ns, dur_ns, category], ...], ...},
         "async": {"0": [...the same, of the line "Async XLA Ops"...]},
         "host": [[name, start_ns, dur_ns], ...]}

    ``devices`` holds the ``XLA Ops`` line of every ``/device:TPU:<n>`` plane
    and ``async`` the collectives of its ``Async XLA Ops`` line (an event
    there spans an operation from its start to its done). On the CPU (the
    rehearsal) there is no device plane; the host threads' events that carry
    an ``hlo_op`` stat stand in for one device ``"0"``, so the same code
    runs. ``host`` holds the ``chipbench:*`` annotations."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict[str, list] = {}
    asyncs: dict[str, list] = {}
    host: list = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name not in (OPS_LINE, ASYNC_LINE):
                    continue
                events = [_event(e) for e in line.events]
                if line.name == OPS_LINE:
                    devices[m.group(1)] = events
                else:
                    asyncs[m.group(1)] = [e for e in events if _collective(e[3])]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(ANNOTATION_PREFIX):
                        host.append(
                            [e.name, float(e.start_ns), float(e.duration_ns)]
                        )
                    elif rehearsal and e.duration_ns > 0 and _stat(e, "hlo_op"):
                        devices.setdefault("0", []).append(_event(e))
    return {"devices": devices, "async": asyncs, "host": host}


def _event(e) -> list:
    label, category = parse_op(e.name)
    return [label, float(e.start_ns), float(e.duration_ns), category]


def _stat(event, key: str) -> str:
    for k, v in event.stats:
        if k == key:
            return str(v)
    return ""


def describe(path: str, names_per_line: int = 12) -> dict:
    """Every plane and line of a profiler file with its event count, the
    stats its events carry and its most frequent names: what one looks at by
    hand before writing a pattern against a trace."""
    import collections

    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        lines = {}
        for line in plane.lines:
            names = collections.Counter()
            stats = collections.Counter()
            dur = collections.Counter()
            n = 0
            for e in line.events:
                n += 1
                names[e.name] += 1
                dur[e.name] += e.duration_ns
                for k, _ in e.stats:
                    stats[k] += 1
            lines[line.name] = {
                "events": n,
                "stats": sorted(stats),
                "top_by_time": [
                    [k, v / 1e9, names[k]] for k, v in dur.most_common(names_per_line)
                ],
            }
        out[plane.name] = lines
    return out


# --- interval arithmetic ---------------------------------------------------


def union(intervals: Iterable[tuple[float, float]]) -> list[tuple[float, float]]:
    """Disjoint sorted intervals covering the same points."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(intervals: Iterable[tuple[float, float]]) -> float:
    return sum(b - a for a, b in intervals)


def clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals if min(b, hi) > max(a, lo)]


def subtract(a: list[tuple[float, float]], b: list[tuple[float, float]]):
    """The part of the disjoint sorted intervals ``a`` that ``b`` (disjoint,
    sorted) does not cover."""
    out = []
    j = 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


# --- classification --------------------------------------------------------


def _collective(category: str) -> str | None:
    """``"sync"``, ``"start"`` or ``"done"`` for a collective's category."""
    for base in COLLECTIVES:
        if category == base:
            return "sync"
        if category in (base + "-start", base + "-done"):
            return category[len(base) + 1:]
    return None


def classify(label: str, category: str, extra_kernel_patterns=()) -> str:
    """``collective``, ``kernel`` (a Mosaic custom call) or ``xla``."""
    if _collective(category):
        return "collective"
    if category == KERNEL_CATEGORY:
        return "kernel"
    for pat in extra_kernel_patterns:
        if re.search(pat, label):
            return "kernel"
    return "xla"


def _collective_intervals(events) -> list[tuple[float, float]]:
    """One interval per collective: a sync op's own, or from the start of a
    ``-start`` to the end of the next ``-done`` of the same opcode."""
    spans = []
    open_starts: dict[str, list[float]] = {}
    for _, start, dur, category in sorted(events, key=lambda e: e[1]):
        kind = _collective(category)
        if kind is None:
            continue
        base = category[: -len(kind) - 1] if kind != "sync" else category
        if kind == "start":
            open_starts.setdefault(base, []).append(start)
            spans.append((start, start + dur))
        elif kind == "done" and open_starts.get(base):
            spans.append((open_starts[base].pop(0), start + dur))
        else:
            spans.append((start, start + dur))
    return union(spans)


# --- the reduction ---------------------------------------------------------


def window_of(trace: dict) -> tuple[float, float]:
    """The traced window on the trace's clock: the ``chipbench:window``
    annotation, which the loop opens on an idle device and closes after
    ``block_until_ready``."""
    spans = [(s, s + d) for n, s, d in trace["host"] if n == WINDOW]
    if not spans:
        raise ValueError("trace has no chipbench:window annotation")
    return min(s for s, _ in spans), max(e for _, e in spans)


def reduce(trace: dict, steps: int, extra_kernel_patterns=()) -> dict | None:
    """Seconds per kind, averaged over the devices that ran anything inside
    the window; ``None`` when no device plane has an operation there."""
    lo, hi = window_of(trace)
    per_device = []
    op_time: dict[str, float] = {}
    gaps_by: dict[str, float] = {}
    host = [
        (n[len(ANNOTATION_PREFIX):], s, s + d)
        for n, s, d in trace["host"] if n != WINDOW
    ]
    for dev, events in sorted(trace["devices"].items()):
        inside = [e for e in events if e[1] + e[2] > lo and e[1] < hi]
        if not inside:
            continue
        kinds = {"kernel": [], "collective": [], "xla": []}
        for name, start, dur, cat in inside:
            kind = classify(name, cat, extra_kernel_patterns)
            kinds[kind].append((start, start + dur))
            op_time[name] = op_time.get(name, 0.0) + dur / 1e9
        compute = clip(union(kinds["kernel"] + kinds["xla"]), lo, hi)
        coll = clip(_collective_intervals(
            inside + [e for e in trace.get("async", {}).get(dev, [])
                      if e[1] + e[2] > lo and e[1] < hi]), lo, hi)
        # an async collective is in flight, and the device at work, from its
        # -start to its -done
        busy = union(compute + coll)
        kernel = clip(union(kinds["kernel"]), lo, hi)
        # XLA time excludes what a kernel or a collective also covers (a
        # while loop's own event spans its body)
        xla_only = subtract(
            clip(union(kinds["xla"]), lo, hi), union(kernel + coll)
        )
        per_device.append({
            "device": dev,
            "busy_s": length(busy) / 1e9,
            "kernel_s": length(kernel) / 1e9,
            "collective_s": length(coll) / 1e9,
            "collective_exposed_s": length(subtract(coll, compute)) / 1e9,
            "xla_s": length(xla_only) / 1e9,
            "ops": len(inside),
        })
        for a, b in subtract([(lo, hi)], busy):
            doing = _attribute(a, b, host)
            gaps_by[doing] = gaps_by.get(doing, 0.0) + (b - a) / 1e9
    if not per_device:
        return None
    n = len(per_device)
    mean = lambda key: sum(d[key] for d in per_device) / n
    top = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {
        "window_s": (hi - lo) / 1e9,
        "steps": steps,
        "devices": n,
        "busy_s": mean("busy_s"),
        "kernel_s": mean("kernel_s"),
        "collective_s": mean("collective_s"),
        "collective_exposed_s": mean("collective_exposed_s"),
        "xla_s": mean("xla_s"),
        "per_device": per_device,
        # summed over devices, divided by their number: seconds per device
        "device_ops": [[k, v / n] for k, v in top(op_time)],
        "idle_gaps": [[k, v / n] for k, v in top(gaps_by)],
    }


def _attribute(a: float, b: float, host) -> str:
    """What the benchmark's loop was doing for most of the gap [a, b)."""
    best, best_cover = "unattributed", 0.0
    for name, s, e in host:
        cover = min(b, e) - max(a, s)
        if cover > best_cover:
            best, best_cover = name, cover
    return best
