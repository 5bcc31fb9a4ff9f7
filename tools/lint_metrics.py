#!/usr/bin/env python
"""Metric-name lint: every emitted st_* name is documented; legacy alias
keys stay dead.

Three contracts, all red gates:

1. (the r09 schema-lint, promoted from test-only to a suite gate) every
   ``st_*`` string literal in the Python package AND the native sources
   must be a documented obs/schema.py SCHEMA name — a new metric cannot
   ship undocumented.
2. (r13) the r08 legacy nested ``peer.metrics()`` alias surface was
   removed after overstaying its "one release" by three; this lint
   forbids the alias machinery (``DEPRECATED_ALIASES``/``canonicalize``)
   and the legacy metric keys from reappearing as dict keys in the
   delivery-metrics modules. Resurrecting a parallel non-schema namespace
   should fail CI by name, not slip in as "compat".
3. (r15) DYNAMICALLY-BUILT ``st_*`` names — f-strings with a placeholder
   inside the name, ``%``/``.format`` on an st_ literal, or string
   concatenation extending an st_ prefix — evade contract 1's literal
   grep entirely: the emitted name never appears in any source line, so
   an undocumented metric ships invisibly. Base names must be complete
   literals (labels are appended via schema.link_key, which this lint
   does not flag — the base literal is intact); any construction site
   that builds the NAME itself is a finding unless allowlisted with a
   reason.
"""

from __future__ import annotations

import pathlib
import re

if __package__ in (None, ""):
    import _lintlib as L
else:
    from . import _lintlib as L

#: Non-metric st_* literals, each with a reason. Kept honest by the
#: staleness check below: every entry must still occur in the scan.
ALLOWED_NON_METRICS: dict[str, str] = {
    "st_trace": "Chrome trace_event category tag (trace_export.py)",
    "st_quantize_rows": "Pallas kernel name (ops/codec_pallas.py), shown in device traces",
    "st_apply_rows_batch": "Pallas kernel name (ops/codec_pallas.py), shown in device traces",
    "st_attn_fwd": "Pallas kernel name (ops/attention_pallas.py), shown in device traces",
    "st_attn_bwd": "Pallas kernel name (ops/attention_pallas.py), shown in device traces",
    "st_moe_combine": "Pallas kernel name (ops/moe_pallas.py), shown in device traces",
}

#: Dynamic-construction sites that are NOT metric names, keyed by the
#: st_ prefix of the literal involved, each with a reason. Kept honest
#: the same way: a stale entry fails the lint.
ALLOWED_DYNAMIC: dict[str, str] = {
    "st_postmortem_": "postmortem FILENAME prefix (obs/recorder.py), "
                      "not a metric name",
}

#: Construction patterns that build an st_* NAME at runtime — each
#: evades the literal grep above (the f-string/format/concat result
#: never appears verbatim in source). The captured group is the st_
#: prefix used for the allowlist lookup.
DYNAMIC_PATTERNS = (
    # f"st_foo_{x}" / f'st_foo_{x}...' — placeholder inside the name
    (re.compile(r'''[fF]["'](st_[a-zA-Z0-9_]*)\{'''),
     "f-string with a placeholder inside the st_ name"),
    # "st_foo_%s" % ... / "st_foo_{}".format(...)
    (re.compile(r'''["'](st_[a-zA-Z0-9_]*)%[sd]'''),
     "%-formatting inside the st_ name"),
    (re.compile(r'''["'](st_[a-zA-Z0-9_]*)\{?\}?["']\s*\.\s*format\('''),
     ".format() on an st_ literal"),
    # "st_foo_" + x — an st_ literal extended on its right (the
    # x + "st_foo" direction produces a name whose st_ part IS the
    # literal, which the schema scan above already sees whole)
    (re.compile(r'''["'](st_[a-zA-Z0-9_]*)["']\s*\+'''),
     "concatenation extending an st_ literal"),
)

#: The removed r08 legacy alias keys (and the machinery that served
#: them). Any of these reappearing as a metrics dict key in the modules
#: below is a finding.
BANNED_TOKENS = ("DEPRECATED_ALIASES", "canonicalize")
BANNED_LEGACY_KEYS = (
    "frames_out", "frames_in", "updates", "msgs_out", "msgs_in",
    "inflight_msgs", "wire_msgs_out", "wire_msgs_in", "residual_rms",
    "delivery",
)
#: Modules whose dict-literal keys are metric names (the old nested shape
#: lived here). Other modules use these words freely as attributes.
LEGACY_KEY_SCOPE = ("shared_tensor_tpu/comm/peer.py",)


def run(repo: pathlib.Path) -> list[str]:
    findings: list[str] = []
    pat = re.compile(r'["\'](st_[a-z0-9_]+)["\']')
    sources = sorted((repo / "shared_tensor_tpu").rglob("*.py")) + [
        p
        for ext in ("*.c", "*.cpp", "*.h")
        for p in sorted((repo / "native").glob(ext))
    ]
    if not sources:
        return ["scan found no sources (wrong --repo?)"]
    schema_text = L.read(repo, "shared_tensor_tpu/obs/schema.py")
    documented = set(pat.findall(schema_text))
    if len(documented) < 20:
        findings.append(
            f"parse floor: only {len(documented)} documented st_* names in "
            f"obs/schema.py (pattern rot?)"
        )
    emitted: dict[str, set[str]] = {}
    for path in sources:
        rel = str(path.relative_to(repo))
        if rel == "shared_tensor_tpu/obs/schema.py":
            continue
        for name in pat.findall(path.read_text(errors="replace")):
            emitted.setdefault(name, set()).add(rel)
    if not emitted:
        findings.append("scan found no st_* literals (pattern rot?)")
    for name in sorted(emitted):
        if name in documented or name in ALLOWED_NON_METRICS:
            continue
        findings.append(
            f"undocumented metric name {name!r} emitted in "
            f"{sorted(emitted[name])} — add a SCHEMA row or an "
            f"ALLOWED_NON_METRICS entry with a reason"
        )
    for stale in sorted(set(ALLOWED_NON_METRICS) - set(emitted)):
        findings.append(f"allowlist entry {stale!r} is no longer emitted "
                        f"anywhere — remove it")

    # contract 3: dynamically-built st_* names (python sources only —
    # the native tier has no runtime string building on metric names)
    dynamic_hits: set[str] = set()
    for path in sources:
        if path.suffix != ".py":
            continue
        rel = str(path.relative_to(repo))
        text = L.strip_py_comments(path.read_text(errors="replace"))
        for pat, what in DYNAMIC_PATTERNS:
            for m in pat.finditer(text):
                prefix = m.group(1)
                dynamic_hits.add(prefix)
                if prefix in ALLOWED_DYNAMIC:
                    continue
                findings.append(
                    f"{rel}: dynamically-built metric name "
                    f"{prefix + '...'!r} ({what}) — the literal grep "
                    f"cannot see the emitted name, so it ships "
                    f"undocumented; build the full name as a literal "
                    f"(labels go through schema.link_key) or add an "
                    f"ALLOWED_DYNAMIC entry with a reason"
                )
    for stale in sorted(set(ALLOWED_DYNAMIC) - dynamic_hits):
        findings.append(
            f"ALLOWED_DYNAMIC entry {stale!r} no longer matches any "
            f"construction site — remove it"
        )

    # legacy alias surface must stay dead
    for rel in ("shared_tensor_tpu/obs/schema.py",) + LEGACY_KEY_SCOPE:
        text = L.strip_py_comments(L.read(repo, rel))
        for tok in BANNED_TOKENS:
            if re.search(r"\b%s\b" % tok, text):
                findings.append(
                    f"{rel}: legacy alias machinery {tok!r} reintroduced "
                    f"(removed r13 — the canonical schema is the only "
                    f"metrics surface)"
                )
    for rel in LEGACY_KEY_SCOPE:
        text = L.strip_py_comments(L.read(repo, rel))
        for key in BANNED_LEGACY_KEYS:
            if re.search(r'["\']%s["\']\s*:' % key, text):
                findings.append(
                    f"{rel}: legacy metrics key {key!r} used as a dict "
                    f"key again (removed r13 — use the st_* schema name)"
                )
    return findings


if __name__ == "__main__":
    L.main(run)
