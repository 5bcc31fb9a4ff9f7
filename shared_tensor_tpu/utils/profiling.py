"""Where a step's time goes, and the pod tier's counters and host spans.

The chip path names its own work: ``jax.named_scope("st.<name>")`` in
parallel/ici.py, ops/table.py and train/async_sgd.py, ``name="st_<kernel>"``
on the Pallas calls of ops/codec_pallas.py, ``st:<name>`` host spans in
``PodTrainer`` and parallel/ici.py. This module captures a trace and reads
those names back; it knows the ``st.`` / ``st:`` prefixes and no single name.

A host span is one call of :meth:`PodTier.span`: a name, a start and an end
on CLOCK_MONOTONIC, the span that was open around it on its thread, the
trainer's step and a few attributes. It is kept as one event of the
process's flight recorder (``obs.hub()``, the timeline the host tiers
share), whether a profiler runs or not; a program's trace, lowering and
compilation (from ``jax.monitoring``) and a collection of Python's cyclic
collector are such events too. ``pod_tier().spans()`` gives them back,
``pod_tier().span_table()`` sums them by name with self times, and
``hub().export_timeline(path)`` at the end of a run followed by
``python -m shared_tensor_tpu.utils.profiling --timeline path`` prints the
same table in another process: where a set-up's seconds went.

- :func:`trace` — the capture: a ``jax.profiler`` session around whatever
  runs inside. ``chipbench/run.py --keep-trace DIR`` writes the same files.
- :func:`scope_map` — ``{(module, instruction): scope}`` from the compiled
  programs' text: the join that gives a traced operation its scope (a
  v5e's trace event carries the instruction's text and no JAX name).
- :func:`scope_times` — the one reduction: per device and step, the self
  time of every traced operation summed by scope, the rest as ``unscoped``,
  the Mosaic kernels by name, the ``st:*`` host spans, and each idle gap
  put down to the host span that covers it.
  ``python -m shared_tensor_tpu.utils.profiling <dir> [--hlo PATH]`` prints
  it (:func:`main`).
- :func:`span_rows`, :func:`span_table` — the host spans of a timeline
  (the hub's, or an exported file's) as rows, and per name their calls,
  total and self seconds.
- :func:`pod_registry` — the pod tier's counters (``st_pod_*`` in
  obs/schema.py) in one :class:`~shared_tensor_tpu.obs.registry.Registry`:
  steps by program, and compilations stamped with the step they fell in.
- :class:`RateMeter`, :func:`effective_bits` — rates from cumulative
  counters and bits per element per frame from a residual trajectory (the
  host tiers' health plane).
"""

from __future__ import annotations

import bisect
import contextlib
import gc
import glob
import math
import os
import re
import threading
import time
import weakref
from collections import deque
from typing import Iterable, Iterator, NamedTuple

import jax

SCOPE_PREFIX = "st."
SPAN_PREFIX = "st:"
SPAN_PREFIXES = (SPAN_PREFIX, "chipbench:")
UNSCOPED = "unscoped"
#: An idle gap shorter than this is the device's own turn-around between
#: two operations, not something the host did.
IDLE_GAP_NS = 50_000

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
#: the three phases of a program's build, as child spans
BUILD_SPANS = {
    TRACE_EVENT: "build.trace", LOWER_EVENT: "build.lower", COMPILE_EVENT: "build.compile",
}
GC_SPAN = SPAN_PREFIX + "gc"
#: a collection shorter than this is counted and not logged
GC_EVENT_NS = 1_000_000


@contextlib.contextmanager
def trace(log_dir: str) -> Iterator[None]:
    """Profiler session around any jitted region (sync step, train loop);
    :func:`scope_times` and this module's command line read ``log_dir``."""
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


# --- the pod tier's counters and host spans -----------------------------------


class _Span:
    """One open span of :meth:`PodTier.span`."""

    __slots__ = ("_tier", "_name", "_attrs", "_ann", "_t0", "_parent")

    def __init__(self, tier: "PodTier", name: str, step_num, attrs: dict):
        self._tier, self._name, self._attrs = tier, name, attrs
        self._ann = (
            jax.profiler.TraceAnnotation(SPAN_PREFIX + name)
            if step_num is None
            else jax.profiler.StepTraceAnnotation(SPAN_PREFIX + name, step_num=step_num)
        )

    def __enter__(self):
        open_spans = self._tier._open_spans()
        self._parent = open_spans[-1] if open_spans else ""
        open_spans.append(SPAN_PREFIX + self._name)
        self._ann.__enter__()
        self._t0 = time.monotonic_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.monotonic_ns()
        self._ann.__exit__(*exc)
        self._tier._open_spans().pop()
        self._tier._close(self._name, self._t0, t1, self._parent, self._attrs)
        return False


class PodTier:
    """The pod tier's registry, its host spans, and what feeds them.

    *Counters.* ``PodTrainer.step`` stamps the step it is in and counts it
    when it returns; one ``jax.monitoring`` listener counts backend
    compilations (a load from the persistent cache is one too, and its
    seconds are kept apart) and stamps each with that step, so "which step
    recompiled" is a gauge an operator reads.

    *Spans.* ``with pod_tier().span("train.step", step_num=n, program="sync"):``
    is a host span ``st:train.step``: the same ``jax.profiler`` annotation
    as ever (on the profiler's clock beside the device's operations when a
    trace is being taken), two reads of CLOCK_MONOTONIC, and at its end one
    event in the process's flight recorder, ``obs.hub()``: ``name`` the
    span's, ``t_ns`` its end, ``arg`` its duration in ns (start = ``t_ns -
    arg``), ``extra`` :attr:`step_now` (what every span of one step
    shares), ``detail`` ``parent=<the span open around it on this thread>``
    and the attributes as ``key=value``. With ``ST_OBS=0`` the event is
    left out; the annotation and the two series ``st_pod_span_seconds_total{span=}``
    / ``st_pod_span_calls_total{span=}`` stay. A program's build is three
    such events made from JAX's own (``st:build.trace``, ``.lower``,
    ``.compile``, ``program=<the jitted function's name>``), children of
    whatever span is open on the thread that builds; a phase inside another
    (``multiply`` traced inside ``sync_step``'s trace) is the outer one's. A
    collection of Python's cyclic collector that lasts 1 ms or more is an
    event ``st:gc`` (``generation=``), every one adds to
    ``st_pod_gc_seconds_total``, and while it runs a ``st:gc`` annotation is
    open for a trace to show.

    *Getting them out.* :meth:`spans` (rows), :meth:`span_table` (per name:
    calls, total and self seconds); ``hub().export_timeline(path)`` and this
    module's ``--timeline path`` for another process; ``hub().dump(reason)``
    carries the registry too. The recorder keeps the newest events
    (``ST_OBS_RECORDER_EVENTS``, 4 096 unless set): a long run's set-up
    spans roll out of it, the two series do not."""

    def __init__(self):
        from .. import obs
        from ..obs.registry import Registry
        from ..obs.schema import SCHEMA, label_key

        self.registry = Registry()
        self.step_now = 0
        self._steps = {True: 0, False: 0}
        self._mu = threading.Lock()
        keys = {
            synced: label_key("st_pod_steps_total", "program", program)
            for synced, program in ((True, "sync"), (False, "local"))
        }
        self.registry.register_collector(
            lambda: {keys[synced]: n for synced, n in self._steps.items()}
        )
        instrument = lambda make, name: make(name, SCHEMA[name][1])
        self._compiles = instrument(self.registry.counter, "st_pod_compiles_total")
        self._compile_s = instrument(
            self.registry.counter, "st_pod_compile_seconds_total"
        )
        self._cache_load_s = instrument(
            self.registry.counter, "st_pod_cache_load_seconds_total"
        )
        self._last_compile_step = instrument(
            self.registry.gauge, "st_pod_last_compile_step"
        )
        self._tiled_share = instrument(self.registry.gauge, "st_table_tiled_share")
        self._trace_s = instrument(self.registry.counter, "st_pod_trace_seconds_total")
        self._lower_s = instrument(self.registry.counter, "st_pod_lower_seconds_total")
        # spans: the recorder they go to, this thread's open ones, the series
        self._obs = obs
        self._hub = obs.hub()
        self._hub.register_registry("pod", self.registry)
        self._thread = threading.local()
        self._span_ns: dict[str, int] = {}
        self._span_calls: dict[str, int] = {}

        def span_series() -> dict:
            with self._mu:
                ns, calls = dict(self._span_ns), dict(self._span_calls)
            return {
                **{label_key("st_pod_span_seconds_total", "span", k): v / 1e9
                   for k, v in ns.items()},
                **{label_key("st_pod_span_calls_total", "span", k): v
                   for k, v in calls.items()},
            }

        self.registry.register_collector(span_series)
        jax.monitoring.register_event_time_span_listener(self._on_time_span)
        # the collector's callback runs wherever an allocation lands, inside
        # the recorder's lock too: it touches plain attributes only, and a
        # pause's event waits in _gc_pending for the next span to log it
        self._gc_ns = 0
        self._gc_max_ns = 0
        self._gc_open = None
        self._gc_pending: list = []
        self.registry.register_collector(lambda: {
            "st_pod_gc_seconds_total": self._gc_ns / 1e9,
            "st_pod_gc_pause_seconds_max": self._gc_max_ns / 1e9,
        })
        gc.callbacks.append(self._on_gc)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        self._trainer = lambda: None
        self.registry.register_collector(self._moe)
        # one count a traced call under each of its two labels, and the
        # newest traced call's gauges a kind
        self._attn_traces = {
            ("path", "pallas"): 0, ("path", "scan"): 0, ("kind", "full"): 0, ("kind", "window"): 0,
        }
        self._attn_newest = {
            (name, kind): 0
            for name in ("st_attn_tiles_listed", "st_attn_heads", "st_attn_saved_bytes")
            for kind in ("full", "window")
        }
        attn_keys = {lv: label_key("st_attn_traces_total", *lv) for lv in self._attn_traces}
        newest_keys = {nk: label_key(nk[0], "kind", nk[1]) for nk in self._attn_newest}
        self.registry.register_collector(lambda: {
            **{attn_keys[lv]: n for lv, n in self._attn_traces.items()},
            **{newest_keys[nk]: n for nk, n in self._attn_newest.items()},
        })

        # the state-space mixers' scan: one count a traced call, and the
        # newest traced call's gauges
        self._ssm = {"st_ssm_traces_total": 0, "st_ssm_heads": 0, "st_ssm_chunks": 0,
                     "st_ssm_saved_bytes": 0}
        self.registry.register_collector(lambda: dict(self._ssm))
        # the delta-rule mixers' chunked scan, the same way
        self._kda = {"st_kda_traces_total": 0, "st_kda_heads": 0, "st_kda_chunks": 0,
                     "st_kda_saved_bytes": 0}
        self.registry.register_collector(lambda: dict(self._kda))

        self._combine_traces = {"pallas": 0, "xla": 0}
        combine_keys = {
            path: label_key("st_moe_combine_traces_total", "path", path)
            for path in self._combine_traces
        }
        self.registry.register_collector(
            lambda: {combine_keys[p]: n for p, n in self._combine_traces.items()}
        )
        self._codec_traces = {"quantize_rows": 0, "apply_rows_batch": 0}
        codec_keys = {
            kernel: label_key("st_codec_kernel_traces_total", "kernel", kernel)
            for kernel in self._codec_traces
        }
        self.registry.register_collector(
            lambda: {codec_keys[k]: n for k, n in self._codec_traces.items()}
        )
        self._leaves_per_block = instrument(
            self.registry.gauge, "st_codec_leaves_per_block_max"
        )
        self._words_rows = {kernel: 0 for kernel in self._codec_traces}
        words_keys = {
            kernel: label_key("st_codec_words_rows_per_block", "kernel", kernel)
            for kernel in self._words_rows
        }
        self.registry.register_collector(
            lambda: {words_keys[k]: n for k, n in self._words_rows.items()}
        )

    def watch(self, trainer) -> None:
        """The trainer whose ``aux`` the expert layers' gauges read (the
        newest made; held weakly), and whose table's share of live elements
        in tile order ``st_table_tiled_share`` reads."""
        self._trainer = weakref.ref(trainer)
        self._tiled_share.set(trainer.spec.tiled_share)

    def _moe(self) -> dict:
        """The newest step's expert-layer counters, fetched from the device
        when the registry is read and never inside ``step``: over all peers
        and expert layers the pairs held, the worst load ratio and the mean
        unrouted share. Empty unless the loss function reports them."""
        aux = getattr(self._trainer(), "aux", None)
        if not isinstance(aux, dict) or "moe_pairs_held" not in aux:
            return {}
        got = jax.device_get({k: aux[k] for k in (
            "moe_pairs_held", "moe_load_max_over_mean", "moe_tokens_unrouted_share")})
        return {
            "st_moe_pairs_held_total": float(got["moe_pairs_held"].sum()),
            "st_moe_load_max_over_mean": float(got["moe_load_max_over_mean"].max()),
            "st_moe_tokens_unrouted_share": float(got["moe_tokens_unrouted_share"].mean()),
        }

    def count_step(self, synced: bool) -> None:
        """One completed ``PodTrainer.step``; ``synced`` says whether the
        program it ran held the exchange (the sync beat) or not."""
        with self._mu:
            self._steps[synced] += 1

    def count_attention_trace(
        self, path: str, kind: str, tiles: int, heads: int, saved_bytes: int
    ) -> None:
        """One traced call of ``models/mla_moe.py``'s causal attention: the
        path it took, ``pallas`` (the fused kernels) or ``scan``; its kind,
        ``full`` (the whole causal triangle) or ``window`` (a band of it);
        the tiles its forward pass lists (the band against the triangle);
        its query ``heads`` (a
        model may give its layer kinds different counts); and the
        ``saved_bytes`` of ``q, k, v, o, lse`` it names for its layer's
        checkpoint (times the layer plan: what the policy holds from the
        forward pass to the backward). The choice is made
        while a program is traced, so this counts traces, not steps."""
        with self._mu:
            self._attn_traces["path", path] += 1
            self._attn_traces["kind", kind] += 1
            self._attn_newest["st_attn_tiles_listed", kind] = tiles
            self._attn_newest["st_attn_heads", kind] = heads
            self._attn_newest["st_attn_saved_bytes", kind] = saved_bytes

    def count_ssm_trace(self, heads: int, chunks: int, saved_bytes: int) -> None:
        """One traced call of ``models/nemotron_h.py``'s chunked scan: the
        ``heads`` it holds, the ``chunks`` it runs, and the ``saved_bytes``
        (by shape and dtype) of its output, which its layer's checkpoint
        keeps (times the layer plan: what the policy holds of the mixers
        from the forward pass to the backward). Traces, not steps."""
        with self._mu:
            self._ssm["st_ssm_traces_total"] += 1
            self._ssm.update(st_ssm_heads=heads, st_ssm_chunks=chunks,
                             st_ssm_saved_bytes=saved_bytes)

    def count_kda_trace(self, heads: int, chunks: int, saved_bytes: int) -> None:
        """One traced call of ``models/kimi_linear.py``'s chunked delta-rule
        scan: the ``heads`` it holds, the ``chunks`` it runs, and the
        ``saved_bytes`` (by shape and dtype) of its output and the states
        entering its groups of chunks, which its layer's checkpoint keeps.
        Traces, not steps."""
        with self._mu:
            self._kda["st_kda_traces_total"] += 1
            self._kda.update(st_kda_heads=heads, st_kda_chunks=chunks,
                             st_kda_saved_bytes=saved_bytes)

    def count_combine_trace(self, path: str) -> None:
        """One traced add of a tile's rows into an expert loop's accumulator
        (``models/mla_moe.py``): by the kernel of ``ops/moe_pallas.py``
        (``pallas``) or by XLA's scatter-add (``xla``). Two a layer's
        gradient (the forward loop's and the backward's). Traces, not steps."""
        with self._mu:
            self._combine_traces[path] += 1

    def count_codec_kernel_trace(
        self, kernel: str, leaves_per_block: int, words_rows_per_block: int
    ) -> None:
        """One traced call of a codec kernel of ``ops/codec_pallas.py``
        (``quantize_rows`` or ``apply_rows_batch``), the most leaves one
        of its grid blocks meets (the worst trip count of the kernel's loop
        over leaves) and the 128-lane rows of packed words a grid step
        takes (32 at a block of 1 024 table rows: the dense words layout
        at that block). Traces, not steps."""
        with self._mu:
            self._codec_traces[kernel] += 1
            self._words_rows[kernel] = words_rows_per_block
        self._leaves_per_block.set(leaves_per_block)

    def _on_duration(self, event: str, seconds: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            self._compiles.inc()
            self._compile_s.inc(max(0.0, seconds))
            self._last_compile_step.set(self.step_now)
        elif event == CACHE_LOAD_EVENT:
            self._cache_load_s.inc(max(0.0, seconds))

    # --- host spans -----------------------------------------------------------

    def span(self, name: str, step_num: int | None = None, **attrs) -> _Span:
        """``with pod_tier().span(name, **attrs):`` is the host span
        ``st:<name>`` (the class docstring says what it keeps and where).
        ``step_num`` makes its annotation a ``StepTraceAnnotation``: the
        profiler's step marker."""
        return _Span(self, name, step_num, attrs)

    def _open_spans(self) -> list:
        """This thread's open spans by name, outermost first."""
        try:
            return self._thread.spans
        except AttributeError:
            self._thread.spans = []
            return self._thread.spans

    def _close(self, name: str, t0_ns: int, t1_ns: int, parent: str, attrs: dict) -> None:
        """One finished span: into the two series, and into the recorder."""
        with self._mu:
            self._span_ns[name] = self._span_ns.get(name, 0) + t1_ns - t0_ns
            self._span_calls[name] = self._span_calls.get(name, 0) + 1
        if not self._obs.obs_enabled():
            return
        self._flush_gc()
        self._log(SPAN_PREFIX + name, t0_ns, t1_ns, parent, self.step_now, attrs)

    def _log(self, name: str, t0_ns: int, t1_ns: int, parent: str, step: int, attrs: dict) -> None:
        detail = [f"parent={parent}"] if parent else []
        detail += [f"{k}={str(v).replace(' ', '_')}" for k, v in attrs.items()]
        self._hub.emit(
            name, arg=t1_ns - t0_ns, detail=" ".join(detail), extra=step, t_ns=t1_ns
        )

    def _on_time_span(self, event: str, start: float, end: float, fun_name: str = "", **_kw) -> None:
        """A program's trace, lowering or compilation as a child span of
        whatever is open on this thread (JAX's two times are the wall
        clock's: their difference is laid back from now). A phase that ends
        while its thread is still tracing (``multiply`` traced inside
        ``sync_step``'s trace; what Pallas's interpreter traces inside a
        lowering) is the outer one's: not logged, not counted."""
        name = BUILD_SPANS.get(event)
        if name is None or not jax.core.trace_ctx.is_top_level():
            return
        seconds = max(0.0, end - start)
        if event == TRACE_EVENT:
            self._trace_s.inc(seconds)
        elif event == LOWER_EVENT:
            self._lower_s.inc(seconds)
        t1 = time.monotonic_ns()
        program = fun_name[4:-1] if fun_name.startswith("jit(") else fun_name
        open_spans = self._open_spans()
        self._close(name, t1 - int(1e9 * seconds), t1,
                    open_spans[-1] if open_spans else "", {"program": program})

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            ann = jax.profiler.TraceAnnotation(GC_SPAN)
            ann.__enter__()
            self._gc_open = (time.monotonic_ns(), ann)
        elif self._gc_open is not None:
            (t0, ann), self._gc_open = self._gc_open, None
            t1 = time.monotonic_ns()
            ann.__exit__(None, None, None)
            self._gc_ns += t1 - t0
            self._gc_max_ns = max(self._gc_max_ns, t1 - t0)
            if t1 - t0 >= GC_EVENT_NS and self._obs.obs_enabled():
                open_spans = self._open_spans()
                self._gc_pending.append((
                    t0, t1, open_spans[-1] if open_spans else "", self.step_now,
                    {"generation": info.get("generation", -1)},
                ))

    def _flush_gc(self) -> None:
        if self._gc_pending:
            pending, self._gc_pending = self._gc_pending, []
            for t0, t1, parent, step, attrs in pending:
                self._log(GC_SPAN, t0, t1, parent, step, attrs)

    def spans(self, since_ns: int | None = None) -> list["Span"]:
        """The host spans the recorder holds, by start (:func:`span_rows`);
        ``since_ns`` keeps those that ended at or after that CLOCK_MONOTONIC
        time. Empty with ``ST_OBS=0``."""
        self._flush_gc()
        return span_rows((e.as_dict() for e in self._hub.recorder.timeline()), since_ns)

    def span_table(self) -> dict[str, dict]:
        """:func:`span_table` of :meth:`spans`."""
        return span_table(self.spans())


class Span(NamedTuple):
    """One host span, as :func:`span_rows` reads it back."""

    name: str  # "st:train.step"
    t0_ns: int  # CLOCK_MONOTONIC
    t1_ns: int
    parent: str  # the span open around it on its thread, "" for none
    step: int  # PodTier.step_now when it ended
    attrs: dict  # program=, generation=


def span_rows(timeline: Iterable[dict], since_ns: int | None = None) -> list[Span]:
    """The ``st:*`` events of a timeline (``Event.as_dict()`` entries: the
    hub's, or the ``timeline`` list of a file ``hub().export_timeline()``
    wrote) as :class:`Span` rows, by start and the longer first."""
    rows = []
    for e in timeline:
        if not e["name"].startswith(SPAN_PREFIX) or e.get("tier") != "py":
            continue
        if since_ns is not None and e["t_ns"] < since_ns:
            continue
        attrs = dict(kv.split("=", 1) for kv in e.get("detail", "").split() if "=" in kv)
        rows.append(Span(e["name"], e["t_ns"] - e.get("arg", 0), e["t_ns"],
                         attrs.pop("parent", ""), e.get("extra", 0), attrs))
    rows.sort(key=lambda r: (r.t0_ns, -r.t1_ns))
    return rows


def span_table(rows: list[Span]) -> dict[str, dict]:
    """``{name: {"calls", "total_s", "self_s"}}`` of :func:`span_rows`' rows,
    the largest total first; a span that names a program is listed under
    ``<name> program=<program>``. Self time is a span's duration minus what
    its children cover: a row is the child of the nearest row of its
    ``parent``'s name that was open when it started."""
    covered: dict[int, list] = {}  # row -> the intervals its children cover
    open_rows: list[int] = []
    for i, r in enumerate(rows):
        open_rows = [j for j in open_rows if rows[j].t1_ns > r.t0_ns]
        for j in reversed(open_rows):
            if rows[j].name == r.parent:
                covered.setdefault(j, []).append((r.t0_ns, min(r.t1_ns, rows[j].t1_ns)))
                break
        open_rows.append(i)
    table: dict[str, dict] = {}
    for i, r in enumerate(rows):
        key = f"{r.name} program={r.attrs['program']}" if "program" in r.attrs else r.name
        t = table.setdefault(key, {"calls": 0, "total_s": 0.0, "self_s": 0.0})
        t["calls"] += 1
        t["total_s"] += (r.t1_ns - r.t0_ns) / 1e9
        # a collection inside a build is a child of the same span as the
        # build: what two children both cover counts once
        children = sum(b - a for a, b in _union(covered.get(i, ())))
        t["self_s"] += (r.t1_ns - r.t0_ns - children) / 1e9
    return dict(sorted(table.items(), key=lambda kv: -kv[1]["total_s"]))


def format_span_table(table: dict[str, dict]) -> str:
    """:func:`span_table`'s result as the table PERF.md prints."""
    out = [f"{'calls':>7}  {'total s':>10}  {'self s':>10}  span"]
    out += [
        f"{t['calls']:>7}  {t['total_s']:10.3f}  {t['self_s']:10.3f}  {name}"
        for name, t in table.items()
    ]
    return "\n".join(out)


_pod_tier: PodTier | None = None
_pod_tier_mu = threading.Lock()


def pod_tier() -> PodTier:
    """The process's one :class:`PodTier`, made on first use (importing this
    module registers no listener)."""
    global _pod_tier
    if _pod_tier is None:
        with _pod_tier_mu:
            if _pod_tier is None:
                _pod_tier = PodTier()
    return _pod_tier


def pod_registry():
    """The pod tier's :class:`~shared_tensor_tpu.obs.registry.Registry`;
    ``pod_registry().prometheus_text()`` is the operator's exporter."""
    return pod_tier().registry


# --- from the compiled program's text: which scope an instruction is in -------

_SCOPE = re.compile(r"\bst(?:\.[A-Za-z_][A-Za-z0-9_]*)+")
_MODULE = re.compile(r"^HloModule (\S+?),", re.M)
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([^\s(]+) \(.*\) -> .*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(ROOT )?%?(\S+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLEES = re.compile(
    r"\b(?:calls|to_apply|body|condition|true_computation|false_computation)=%?([^\s,)}]+)"
)
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_NAME = re.compile(r"%?([A-Za-z_][\w.\-]*)")


def scope_of(op_name: str) -> str:
    """The ``st.*`` scopes of a JAX ``op_name`` (``jit(_step)/vmap(st.grads)/
    st.flatten/pad``), outermost first and joined by ``/``:
    ``st.grads/st.flatten``. The last component is the innermost scope.
    Empty when there is none. The name's own last component is the
    primitive, or a whole argument's path (``st.values`` where a caller
    named its state ``st``), and is no scope. A scope's name may hold dots
    (``st.mla.attn``, inside ``st.mla``). Of metadata XLA merged
    (``a;b``) the first name that has a scope counts."""
    for name in op_name.split(";"):
        # a backward or recomputed operation repeats its forward's scopes
        # (``st.grads/.../transpose(jvp(st.grads))/st.mla``): each counts once
        scope = "/".join(dict.fromkeys(_SCOPE.findall(name.rpartition("/")[0])))
        if scope:
            return scope
    return ""


def innermost(scope: str) -> str:
    return scope.rsplit("/", 1)[-1]


def _parse_hlo(text: str):
    """``(module, {computation: [(instruction, op_name, callees, is_root,
    operands)]})`` of one module's text, as ``compiled.as_text()`` or an XLA
    dump prints it (with or without ``%`` before names). ``operands`` are
    the instructions of the same computation the line names."""
    m = _MODULE.search(text)
    module = m.group(1) if m else ""
    comps: dict[str, list] = {}
    current = None
    for line in text.splitlines():
        if current is None:
            c = _COMPUTATION.match(line)
            if c:
                current = comps.setdefault(c.group(1), [])
            continue
        if line.startswith("}"):
            current = None
            continue
        i = _INSTRUCTION.match(line)
        if not i:
            continue
        # a Mosaic call's backend_config is the whole kernel: names are
        # looked for in front of it
        head = line.split("backend_config=", 1)[0] if len(line) > 8192 else line
        op = _OP_NAME.search(line)
        callees = _CALLEES.findall(head)
        for group in _BRANCHES.findall(head):
            callees += [c.strip().lstrip("%") for c in group.split(",") if c.strip()]
        current.append((
            i.group(2), op.group(1) if op else "", callees, bool(i.group(1)),
            _NAME.findall(head[i.end():]),
        ))
    for instrs in comps.values():
        names = {name for name, *_ in instrs}
        for k, (name, op, callees, root, words) in enumerate(instrs):
            instrs[k] = (name, op, callees, root,
                         [w for w in words if w in names and w != name])
    return module, comps


def scope_map(*compiled_or_hlo_text) -> dict[tuple[str, str], str]:
    """``{(module, instruction): scope}`` for every instruction of the given
    programs (``jit(f).lower(...).compile()`` objects or their
    ``as_text()``), read from ``metadata={op_name=...}``; the scope is
    :func:`scope_of`'s path. An instruction whose own metadata names no
    ``st.`` scope takes, in this order: the scope of the computation it
    calls (a fusion's or a ``while`` body's root, else that computation's
    most frequent scope); the scope of the instruction its computation is
    called from; and, for what the compiler put in with no name at all (a
    copy to another memory, a layout change, a loop around a collective),
    the scope of the first instruction that uses its result, else of the
    first whose result it uses. Instructions that no rule reaches are left
    out."""
    out: dict[tuple[str, str], str] = {}
    for src in compiled_or_hlo_text:
        text = src if isinstance(src, str) else src.as_text()
        module, comps = _parse_hlo(text)
        own = {  # (scope, callees, has no metadata at all)
            (comp, name): (scope_of(op), callees, not op)
            for comp, instrs in comps.items()
            for name, op, callees, _, _ in instrs
        }
        called_from: dict[str, tuple[str, str]] = {}
        for (comp, name), (_, callees, _) in own.items():
            for callee in callees:
                called_from.setdefault(callee, (comp, name))
        up_memo: dict[tuple[str, str], str] = {}
        comp_memo: dict[str, str] = {}

        def up(comp: str, name: str) -> str:
            """Own scope, else what the called computations give."""
            key = (comp, name)
            if key not in up_memo:
                scope, callees, _ = own[key]
                up_memo[key] = scope  # HLO's call graph has no cycle; be safe
                for callee in () if scope else callees:
                    scope = of_computation(callee)
                    if scope:
                        break
                up_memo[key] = scope
            return up_memo[key]

        def of_computation(comp: str) -> str:
            if comp not in comp_memo:
                comp_memo[comp] = ""
                scopes = [(up(comp, n), root) for n, _, _, root, _ in comps.get(comp, ())]
                rooted = [s for s, root in scopes if root and s]
                named = [s for s, _ in scopes if s]
                if rooted:
                    comp_memo[comp] = rooted[0]
                elif named:
                    comp_memo[comp] = max(named, key=named.count)
            return comp_memo[comp]

        found = {key: up(*key) for key in own if up(*key)}
        users: dict[tuple[str, str], list[str]] = {}
        operands_of: dict[tuple[str, str], list[str]] = {}
        for comp, instrs in comps.items():
            for name, _, _, _, operands in instrs:
                operands_of[(comp, name)] = operands
                for o in operands:
                    users.setdefault((comp, o), []).append(name)
        for near in (users, operands_of):  # consumers settle before producers
            changed = True
            while changed:
                changed = False
                for comp, name in own:
                    if (comp, name) in found:
                        continue
                    at, scope = comp, ""
                    while not scope and at in called_from:
                        at, caller = called_from[at]
                        scope = found.get((at, caller), "")
                    if not scope and own[(comp, name)][2]:
                        scope = next(
                            (found[(comp, o)] for o in near.get((comp, name), ())
                             if (comp, o) in found),
                            "",
                        )
                    if scope:
                        found[(comp, name)] = scope
                        changed = True
        out.update({(module, name): scope for (_, name), scope in found.items()})
    return out


def hlo_texts(path: str) -> list[str]:
    """The program texts under ``path``: one file, or a directory that
    ``--xla_dump_to`` wrote (its ``*after_optimizations.txt`` files)."""
    if os.path.isdir(path):
        files = sorted(glob.glob(os.path.join(path, "*after_optimizations.txt")))
    else:
        files = [path]
    texts = []
    for f in files:
        with open(f, errors="replace") as fh:
            texts.append(fh.read())
    return texts


# --- from a trace: whose every microsecond is ----------------------------------

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_HOST_PLANE = "/host:CPU"
_OPS_LINE = "XLA Ops"
_MODULES_LINE = "XLA Modules"
_HLO_EVENT = re.compile(r"^%(\S+) = (.*)$", re.S)
_OPCODE = re.compile(r"(?:^| )([a-z][a-z0-9\-]*)\(")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'


def newest_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not found:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def _instruction_of(event_name: str) -> tuple[str, str, str]:
    """(instruction, label, kernel name or "") of one operation event. A TPU
    trace names an event by its whole HLO text (``%fusion.3 = f32[201]{...}
    fusion(...)``): the label is ``fusion.3 = f32[201] fusion``. A CPU trace
    names it by the instruction alone."""
    m = _HLO_EVENT.match(event_name)
    if not m:
        return event_name, event_name, ""
    name, rest = m.group(1), m.group(2)
    op = _OPCODE.search(rest)
    shape = _LAYOUT.sub("", rest[: op.start()] if op else "").strip()
    label = f"{name} = {shape} {op.group(1) if op else ''}"[:96]
    kernel = re.sub(r"\.\d+$", "", name) if _KERNEL_TARGET in rest else ""
    return name, label, kernel


def self_times(events: list) -> list[float]:
    """Self time of each ``(start, end)`` of one line, in the order given:
    an event that encloses others (a ``while`` and its body's operations)
    keeps only what they do not cover."""
    order = sorted(range(len(events)), key=lambda i: (events[i][0], -events[i][1]))
    own = [e[1] - e[0] for e in events]
    stack: list[int] = []
    for i in order:
        start, end = events[i]
        while stack and events[stack[-1]][1] <= start:
            stack.pop()
        if stack:
            own[stack[-1]] -= min(end, events[stack[-1]][1]) - start
        stack.append(i)
    return own


def _union(intervals: list) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        elif b > a:
            out.append([a, b])
    return [(a, b) for a, b in out]


def attribute_gap(a: float, b: float, spans: list) -> str:
    """The host span ``(name, start, end)`` that covers most of the idle gap
    ``[a, b)``; of two that cover the same, the shorter (the inner one)."""
    best, best_key = "unattributed", (0.0, 0.0)
    for name, s, e in spans:
        cover = min(b, e) - max(a, s)
        if cover > 0 and (cover, s - e) > best_key:
            best, best_key = name, (cover, s - e)
    return best


def _read_trace(path: str):
    """(operation lines, module runs, host spans) of a profiler file.

    ``lines`` is ``{device: [[(start, end, module, instruction, label,
    kernel)]]}``, one inner list per profiler line (self time is taken
    within a line): the ``XLA Ops`` line of every ``/device:TPU:<n>`` plane,
    whose events name no module and get the program running at the time
    (``XLA Modules``), or, on the CPU, the host threads' events that carry
    ``hlo_op``, by ``device_ordinal``. ``runs`` is ``{device: [(start, end,
    module)]}``; ``spans`` the ``st:*`` / ``chipbench:*`` host spans as
    ``(name, start, end, step_num or None)``."""
    from jax.profiler import ProfileData

    lines: dict[str, list] = {}
    runs: dict[str, list] = {}
    spans: list = []
    planes = list(ProfileData.from_file(path).planes)
    for plane in planes:
        dev = _DEVICE_PLANE.match(plane.name)
        for line in plane.lines if dev else ():
            if line.name == _MODULES_LINE:
                runs[dev.group(1)] = sorted(
                    (e.start_ns, e.start_ns + e.duration_ns,
                     re.sub(r"\(-?\d+\)$", "", e.name))
                    for e in line.events
                )
    for plane in planes:
        dev = _DEVICE_PLANE.match(plane.name)
        if dev:
            ran = runs.get(dev.group(1), [])
            starts = [a for a, _, _ in ran]
            for line in plane.lines:
                if line.name != _OPS_LINE:
                    continue
                evs = []
                for e in line.events:
                    k = bisect.bisect_right(starts, e.start_ns) - 1
                    module = ran[k][2] if k >= 0 and e.start_ns < ran[k][1] else ""
                    evs.append((e.start_ns, e.start_ns + e.duration_ns, module,
                                *_instruction_of(e.name)))
                lines.setdefault(dev.group(1), []).append(evs)
        elif plane.name == _HOST_PLANE:
            for line in plane.lines:
                by_dev: dict[str, list] = {}
                for e in line.events:
                    st = dict(e.stats)
                    if e.name.startswith(SPAN_PREFIXES):
                        spans.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                                      st.get("step_num")))
                    elif e.duration_ns > 0 and "hlo_op" in st:
                        op = str(st["hlo_op"])
                        by_dev.setdefault(str(st.get("device_ordinal", 0)), []).append(
                            (e.start_ns, e.start_ns + e.duration_ns,
                             str(st.get("hlo_module", "")), op, op, "")
                        )
                for d, evs in by_dev.items():
                    lines.setdefault(d, []).append(evs)
    return lines, runs, spans


def _pick_programs(maps, lines: dict) -> dict:
    """One map from several: for every module name, the entries of the map
    that knows most of the instructions the trace shows under that name."""
    traced: dict[str, set] = {}
    for dev_lines in lines.values():
        for evs in dev_lines:
            for _, _, module, instr, _, _ in evs:
                traced.setdefault(module, set()).add(instr)
    best: dict[str, tuple[int, dict]] = {}
    for m in maps:
        by_module: dict[str, dict] = {}
        for (module, instr), scope in m.items():
            by_module.setdefault(module, {})[(module, instr)] = scope
        for module, entries in by_module.items():
            hits = sum((module, i) in entries for i in traced.get(module, ()))
            if module not in best or hits > best[module][0]:
                best[module] = (hits, entries)
    return {k: v for _, entries in best.values() for k, v in entries.items()}


def _tally(into: dict, name: str, seconds: float, count: int = 1) -> None:
    t = into.setdefault(name, {"count": 0, "total_s": 0.0})
    t["count"] += count
    t["total_s"] += seconds


def scope_times(trace_dir: str, scope_map=None, steps: int | None = None) -> dict:
    """Device time by scope, from the newest profiler file under
    ``trace_dir`` (what :func:`trace` or ``chipbench/run.py --keep-trace``
    wrote). Every figure in seconds; ``scopes``, ``unscoped``, ``kernels``
    and ``ops`` are per device and per step (``steps``: how many the trace
    holds; by default the runs of the program that took most device time,
    1 where the trace has no ``XLA Modules`` line).

    A v5e's trace events carry no JAX name, so an operation's scope comes
    from ``scope_map`` (:func:`scope_map`) by the event's module and
    instruction; without an entry it is ``unscoped``. ``scope_map`` may be a
    list of maps, one a program: of several programs of one name (a
    trainer's sync and off-beat steps are both ``jit__step``, and XLA
    numbers their instructions alike) the one that holds most of that
    module's traced instructions is taken. Self times: an event that
    encloses others on its line counts only what they do not cover, so
    scopes and ``unscoped`` add up to ``self_s``."""
    path = newest_xplane(trace_dir)
    lines, runs, spans = _read_trace(path)
    if not isinstance(scope_map, dict):
        scope_map = _pick_programs(scope_map or (), lines)
    if steps is None:
        module_s: dict[str, float] = {}
        module_runs: dict[str, int] = {}
        for ran in runs.values():
            for a, b, module in ran:
                module_s[module] = module_s.get(module, 0.0) + b - a
                module_runs[module] = module_runs.get(module, 0) + 1
        steps = 1
        if module_s:
            steps = max(1, module_runs[max(module_s, key=module_s.get)] // len(runs))
    host = [(n, s, e) for n, s, e, _ in spans]
    per_device = {}
    ops: dict[tuple[str, str], float] = {}
    joined = 0
    for dev in sorted(lines):
        by_scope: dict[str, float] = {}
        kernels: dict[str, float] = {}
        intervals = []
        for evs in lines[dev]:
            for (start, end, module, instr, label, kernel), own in zip(
                evs, self_times([(e[0], e[1]) for e in evs])
            ):
                intervals.append((start, end))
                scope = scope_map.get((module, instr), UNSCOPED)
                joined += scope is not UNSCOPED
                by_scope[scope] = by_scope.get(scope, 0.0) + own / 1e9
                if kernel:
                    kernels[kernel] = kernels.get(kernel, 0.0) + own / 1e9
                ops[(label, scope)] = ops.get((label, scope), 0.0) + own / 1e9
        busy = _union(intervals)
        lo, hi = busy[0][0], busy[-1][1]
        # the device also idles from the start of the longest host span (a
        # benchmark's window) to its first operation, and from its last
        # operation to that span's end
        around = max(
            ((s, e) for _, s, e in host if s < hi and e > lo),
            key=lambda se: se[1] - se[0], default=(lo, hi),
        )
        edges = (
            [(around[0], around[0])] * (around[0] < lo)
            + busy
            + [(around[1], around[1])] * (around[1] > hi)
        )
        inner = [h for h in host if (h[1], h[2]) != around]
        gaps: dict[str, dict] = {}
        for (_, a), (b, _) in zip(edges, edges[1:]):
            if b - a > IDLE_GAP_NS:
                # that enclosing span says nothing: it counts only where no
                # span inside it covers any of the gap
                doing = attribute_gap(a, b, inner)
                if doing == "unattributed":
                    doing = attribute_gap(a, b, host)
                _tally(gaps, doing, (b - a) / 1e9)
        per_device[dev] = {
            "self_s": sum(by_scope.values()),
            "busy_s": sum(b - a for a, b in busy) / 1e9,
            "scopes": by_scope,
            "kernels": kernels,
            "idle_gaps": gaps,
        }
    if not per_device:
        raise ValueError(f"{path} holds no device operation")
    n = len(per_device)

    def mean_of(key: str) -> dict[str, float]:
        total: dict[str, float] = {}
        for d in per_device.values():
            for k, v in d[key].items():
                total[k] = total.get(k, 0.0) + v
        return {k: v / n / steps for k, v in sorted(total.items(), key=lambda kv: -kv[1])}

    scopes = mean_of("scopes")
    unscoped = scopes.pop(UNSCOPED, 0.0)
    host_spans: dict[str, dict] = {}
    for name, s, e, _ in spans:
        _tally(host_spans, name, (e - s) / 1e9)
    idle: dict[str, dict] = {}
    for d in per_device.values():
        for name, g in d["idle_gaps"].items():
            _tally(idle, name, g["total_s"] / n, g["count"])
    return {
        "file": path,
        "devices": n,
        "steps": steps,
        "self_s": sum(d["self_s"] for d in per_device.values()) / n / steps,
        "busy_s": sum(d["busy_s"] for d in per_device.values()) / n / steps,
        "scopes": scopes,
        "unscoped": unscoped,
        "kernels": mean_of("kernels"),
        "ops": [
            [label, scope, v / n / steps]
            for (label, scope), v in sorted(ops.items(), key=lambda kv: -kv[1])
        ],
        "operations": sum(len(evs) for dev_lines in lines.values() for evs in dev_lines),
        "operations_scoped": joined,
        "host_spans": host_spans,
        "step_spans": sorted(
            (step, s, e) for name, s, e, step in spans if step is not None
        ),
        "idle_gaps": idle,
        "per_device": per_device,
    }


def format_table(t: dict, top_ops: int = 24) -> str:
    """:func:`scope_times`' result as the table PERF.md prints."""
    ms = lambda s: f"{1e3 * s:10.3f}"
    share = lambda s: f"{100 * s / t['self_s']:6.2f} %" if t["self_s"] else ""
    out = [
        f"{t['file']}",
        f"devices {t['devices']}, steps {t['steps']}: ms per device and step "
        f"(self time {ms(t['self_s']).strip()}, busy {ms(t['busy_s']).strip()}); "
        f"{t['operations_scoped']} of {t['operations']} operations scoped",
        f"{'ms/step':>10}  {'share':>8}  scope",
    ]
    for scope, v in t["scopes"].items():
        out.append(f"{ms(v)}  {share(v)}  {scope}")
    out.append(f"{ms(t['unscoped'])}  {share(t['unscoped'])}  {UNSCOPED}")
    if t["kernels"]:
        out.append("kernels (Mosaic custom calls, by name):")
        out += [f"{ms(v)}  {share(v)}  {k}" for k, v in t["kernels"].items()]
    out.append(f"operations, the {top_ops} longest:")
    out += [f"{ms(v)}  {share(v)}  {label}  [{scope}]" for label, scope, v in t["ops"][:top_ops]]
    if t["host_spans"]:
        out.append("host spans (count, total ms):")
        out += [
            f"{h['count']:>10}  {ms(h['total_s'])}  {name}"
            for name, h in sorted(t["host_spans"].items())
        ]
    if t["idle_gaps"]:
        out.append(f"idle gaps over {IDLE_GAP_NS // 1000} us per device (count, total ms), by host span:")
        out += [
            f"{g['count']:>10}  {ms(g['total_s'])}  {name}"
            for name, g in sorted(t["idle_gaps"].items(), key=lambda kv: -kv[1]["total_s"])
        ]
    return "\n".join(out)


def main(argv=None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser(
        prog="python -m shared_tensor_tpu.utils.profiling",
        description="Device time by st.* scope from a kept profiler trace, or host "
        "time by st:* span from an exported timeline.",
    )
    ap.add_argument("trace_dir", nargs="?",
                    help="what profiling.trace() or run.py --keep-trace wrote")
    ap.add_argument("--timeline", metavar="FILE",
                    help="what obs.hub().export_timeline(FILE) wrote: print its host "
                    "spans by name (calls, total and self seconds) and stop")
    ap.add_argument("--hlo", action="append", default=[], metavar="PATH",
                    help="compiled program text, or an --xla_dump_to directory, for "
                    "the join by instruction name (repeatable)")
    ap.add_argument("--steps", type=int, default=None, help="steps the trace holds")
    ap.add_argument("--ops", type=int, default=24, help="operations to list")
    ap.add_argument("--json", action="store_true", help="print the whole result as JSON")
    args = ap.parse_args(argv)
    if args.timeline:
        with open(args.timeline) as f:
            table = span_table(span_rows(json.load(f)["timeline"]))
        print(json.dumps(table) if args.json else format_span_table(table))
        return 0
    if not args.trace_dir:
        ap.error("give a trace directory, or --timeline FILE")
    maps = [scope_map(t) for path in args.hlo for t in hlo_texts(path)]
    table = scope_times(args.trace_dir, maps, steps=args.steps)
    if args.json:
        del table["per_device"]
        print(json.dumps(table))
    else:
        print(format_table(table, args.ops))
    return 0


# --- the host tiers' rate meters ----------------------------------------------


class RateMeter:
    """Sliding-window rates from cumulative counters.

    >>> meter = RateMeter()
    >>> meter.update(frames=st.frames_in, wire_bytes=stats.bytes_in)
    >>> meter.rates()  # {"frames": f/s, "wire_bytes": B/s}
    """

    def __init__(self, window_sec: float = 10.0):
        self.window = window_sec
        self._samples: deque[tuple[float, dict[str, float]]] = deque()

    def update(self, **counters: float) -> None:
        self.update_at(time.monotonic(), **counters)

    def update_at(self, now: float, **counters: float) -> None:
        """`update` with an explicit timestamp — the testable entry point
        (r18 satellite), and the one for callers replaying recorded
        counter trajectories."""
        # Wall-clock-jump tolerance (r18 satellite): a sample stamped
        # EARLIER than the previous one (suspend/resume replay, a caller
        # switching time sources, test replays) would give a negative dt
        # and an inverted window. Re-anchor exactly like a counter reset:
        # the old timeline is unusable, the new one starts here.
        if self._samples and now < self._samples[-1][0]:
            self._samples.clear()
        # Counter-reset tolerance (r08 satellite): cumulative counters can
        # legitimately restart from ~0 — a link re-graft hands the stream
        # to a FRESH link id (new LinkStats), an engine peer is re-created
        # after a crash-point kill, a compat peer reconnects, a process
        # restores from checkpoint with zeroed registries. A window
        # spanning the reset would then report a huge NEGATIVE rate (new
        # minus old counter). Detect any counter going backwards and drop
        # the pre-reset history: the meter re-anchors at the reset point
        # and reports rates for the new stream only.
        if self._samples:
            _, last = self._samples[-1]
            if any(
                counters[k] < last[k] for k in counters if k in last
            ):
                self._samples.clear()
        self._samples.append((now, dict(counters)))
        cutoff = now - self.window
        # Evict while the SECOND-oldest sample is already at/past the window
        # edge — keeping exactly one sample at or before it, so rates() spans
        # the full window rather than just the last update interval.
        while len(self._samples) > 2 and self._samples[1][0] <= cutoff:
            self._samples.popleft()

    def rates(self) -> dict[str, float]:
        """Per-second rates over (at most) the trailing window.

        The oldest retained sample can be far older than the window (it is
        kept as the at-or-before-the-edge anchor; after an idle gap it may
        predate the edge by the whole gap). Using its raw timestamp would
        dilute the rate over the gap, so the counter value AT the window
        edge is linearly interpolated between the two samples bracketing it
        and the rate taken from there.
        """
        if len(self._samples) < 2:
            return {}
        t1, c1 = self._samples[-1]
        cutoff = t1 - self.window
        t0, c0 = self._samples[0]
        if t0 < cutoff:
            i = 1
            while i < len(self._samples) - 1 and self._samples[i][0] < cutoff:
                i += 1
            (ta, ca), (tb, cb) = self._samples[i - 1], self._samples[i]
            w = min(1.0, (cutoff - ta) / max(tb - ta, 1e-9))
            c0 = {
                k: ca.get(k, 0.0) + (cb.get(k, 0.0) - ca.get(k, 0.0)) * w
                for k in cb
            }
            t0 = min(cutoff, tb)
        dt = max(t1 - t0, 1e-9)
        # Clamped at zero: resets/rewinds re-anchor the window above, so a
        # negative delta here can only be float noise at the interpolated
        # edge — and a rate is a non-negative quantity by definition.
        return {
            k: max(0.0, (c1.get(k, 0.0) - c0.get(k, 0.0)) / dt) for k in c1
        }


def effective_bits(rms_trajectory: Iterable[float]) -> float:
    """Average bits of precision gained per element per frame, from a
    residual-RMS trajectory (one entry per frame). The reference codec
    achieves 1.0 on homogeneous data (RMS halves per frame, BASELINE.md)
    and ~0.15 on 1000:1 mixed magnitudes — the failure per-leaf scales fix."""
    traj = [float(x) for x in rms_trajectory]
    if len(traj) < 2 or traj[0] <= 0:
        return 0.0
    first, last = traj[0], traj[-1]
    if last <= 0:  # exact convergence: count bits down to fp32 epsilon
        last = first * 2.0**-24
    return math.log2(first / last) / (len(traj) - 1)


if __name__ == "__main__":
    import sys

    sys.exit(main())
