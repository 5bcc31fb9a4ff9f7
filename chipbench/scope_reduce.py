"""Device self time by the program's ``st.*`` scopes, from a traced window's
plain form (``harness.TraceWindow.trace``) and the compiled step's text.

A v5e's trace events carry no JAX name: an event is one HLO instruction, and
its scope is the ``op_name`` that instruction has in the compiled program's
text. The join (which scope an instruction with no name of its own takes) and
the self times (a ``while`` keeps only what its body's operations do not
cover) are the program's own, ``shared_tensor_tpu.utils.profiling.scope_map``
and ``self_times``, so that the benchmark and the program's command line
print the same table. Where the program has neither (an older program), there
is nothing to read and :func:`by_scope` returns None.
"""

from __future__ import annotations

UNSCOPED = "unscoped"


def by_scope(trace: dict, text: str, steps: int) -> dict | None:
    """``{scope path: ms per step}`` (``st.grads/st.mla/st.mla.attn``;
    ``unscoped`` for what the join does not reach), the mean over the devices
    of ``trace["devices"]``; the values add up to the devices' busy self
    time. ``text`` is the traced program's ``compiled.as_text()``."""
    try:
        from shared_tensor_tpu.utils.profiling import scope_map, self_times
    except ImportError:
        return None
    if not trace or not trace.get("devices") or not steps:
        return None
    scope_of = {instr: scope for (_, instr), scope in scope_map(text).items()}
    total: dict[str, float] = {}
    for events in trace["devices"].values():
        spans = [(start, start + dur) for _, start, dur, _ in events]
        for (label, *_), own in zip(events, self_times(spans)):
            scope = scope_of.get(label.split(" = ", 1)[0].lstrip("%"), UNSCOPED)
            total[scope] = total.get(scope, 0.0) + own
    n = len(trace["devices"]) * steps
    return {k: v / 1e6 / n for k, v in sorted(total.items(), key=lambda kv: -kv[1])}


def under(scopes: dict | None, name: str) -> float | None:
    """ms per step of every scope ``name`` wherever it is nested, with what
    lies under it: the paths one of whose components is ``name`` or one of
    its own sub-scopes (``st.mla.attn`` is ``st.mla``'s)."""
    return total_of(scopes, lambda parts: any(
        p == name or p.startswith(name + ".") for p in parts))


def total_of(scopes: dict | None, pick) -> float | None:
    """ms per step of the scope paths whose components (``path.split("/")``)
    ``pick`` accepts; None when there are no scopes to read or none match."""
    if not scopes:
        return None
    found = [ms for path, ms in scopes.items() if pick(path.split("/"))]
    return sum(found) if found else None
