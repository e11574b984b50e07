#!/usr/bin/env python3
"""Metric-name schema lint: every literal name handed to the telemetry
factories (``counter(...)``/``gauge(...)``/``histogram(...)``) must be
dotted snake_case (docs/OBSERVABILITY.md §Prometheus naming), and a name
must never be registered as two different metric types.

The registry is get-or-create by NAME with no type check across call
sites — ``counter("x")`` in one module and ``gauge("x")`` in another
would silently coexist as two metrics whose exposition families collide
— and the Prometheus mapping (telemetry/exposition.py) sanitizes
characters outside ``[a-zA-Z0-9_:]``, so a camelCase or hyphenated name
would silently diverge from the documented ``dots -> underscores``
mapping dashboards are built against. This gate keeps both invariants
static, like jaxlint keeps the tracing invariants.

Scope and mechanics:

- AST walk of ``photon_ml_tpu/`` (tests are EXEMPT: the
  exposition tests deliberately register schema-violating names to
  exercise escaping).
- A call counts as a registration when it is ``<anything>.counter(...)``
  / ``.gauge(...)`` / ``.histogram(...)`` (the ``telemetry.X`` /
  ``registry().X`` forms) or a bare name imported from
  ``photon_ml_tpu.telemetry``.
- A fully-literal first argument (string constant, or a constant-only
  concatenation) is schema-checked whole:
  ``segment(.segment)*`` with each segment ``[a-z][a-z0-9_]*``.
- A PARTIALLY literal argument (f-string or concatenation with a
  variable — the per-model ``serving.model.<label>.*`` family) has its
  literal fragments checked for illegal characters (uppercase or
  anything outside ``[a-z0-9_.]``); the dynamic parts are runtime
  values the lint cannot see.
- An EXEMPLAR-BEARING histogram (``histogram(..., exemplars=True)`` —
  its buckets carry trace_id exemplars rendered on /metrics,
  docs/OBSERVABILITY.md §Exemplars) must name a latency distribution:
  the literal name must end in ``_seconds`` (exemplars link latency
  buckets to /tracez timelines; a counter-shaped or unitless histogram
  carrying exemplars is a schema smell), and one name must not be
  declared exemplar-bearing at one site and plain at another (the
  registry is get-or-create — whichever call runs first would silently
  win).
- GAUGE-ONLY metric families (docs/OBSERVABILITY.md §Distributions &
  drift): names under ``data.dist.`` (distribution-sketch headline
  values, refreshed whole by scrape hooks) and names containing
  ``score_drift_`` (the ``serving.model.<label>.score_drift_psi``/
  ``_ks`` drift scores, COMPUTED on scrape) are instantaneous readings
  by construction — a counter or histogram under either family would
  break the ``--slo`` value-objective contract and every dashboard
  rate() built on the family. Checked on full literals AND on literal
  fragments of partially-dynamic names (the per-model f-string form).
- The ``fleet.`` prefix is RESERVED for the fleet aggregator
  (telemetry/federation.py): a peer process emitting ``fleet.*`` would
  collide with the aggregator's synthesized series on the merged
  /metrics and break per-process attribution — no file other than
  federation.py may register a name (or literal fragment) starting
  ``fleet.`` (docs/OBSERVABILITY.md §Federation).
- Every GAUGE family must carry a DECLARED merge policy in
  federation.py's ``GAUGE_MERGE_POLICIES`` (exact name, ``prefix.`` or
  ``.suffix`` entry): gauges — unlike counters and histograms — have no
  single correct cross-process merge, and the runtime default of
  ``last`` silently picks "newest snapshot wins" for an undeclared
  family. A new gauge must state whether it sums (bytes held), maxes
  (uptime, burn rates) or follows the newest writer. Full literals must
  resolve against the declared table; partially-dynamic names need at
  least one literal fragment covered by an entry. Skipped entirely
  when the tree has no ``photon_ml_tpu/telemetry/federation.py`` (TP/FP
  tmp-tree tests supply their own).

Exit 0 = clean. Run via tests.sh or directly:
    python dev_scripts/metric_names.py [--root DIR] [paths...]
"""

from __future__ import annotations

import argparse
import ast
import re
import sys
from pathlib import Path

FACTORIES = ("counter", "gauge", "histogram")
DEFAULT_PATHS = ["photon_ml_tpu"]

_NAME_RE = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z][a-z0-9_]*)*$")
_FRAGMENT_BAD_RE = re.compile(r"[^a-z0-9_.]")

#: (trigger, match) -> gauge-only family. ``prefix`` triggers on a name
#: (or fragment) starting with the string; ``contains`` anywhere in it.
_GAUGE_ONLY_FAMILIES = (
    ("prefix", "data.dist.", "the data.dist.* distribution family"),
    ("contains", "score_drift_",
     "the serving.model.<label>.score_drift_* drift family"),
)

#: prefix-anchored COUNTER families (the inverse rule): under the
#: prefix, registrations must be counters — the family counts wire
#: events (requests, bytes, typed errors) and dashboards rate() the
#: whole namespace — except gauges whose name ends with an allowlisted
#: instantaneous-reading suffix. Histograms are never allowed (a wire
#: latency distribution belongs under serving.frontend.*, where the
#: SLO thresholds point). Prefix-anchored on fragments like the
#: gauge-only prefix families (the serving.net.errors.<kind> f-string
#: form starts with the literal prefix).
_COUNTER_FAMILIES = (
    ("serving.net.", ("open_connections",),
     "the serving.net.* wire-event family"),
)


def _counter_family_violation(text: str, kind: str):
    """The counter-family rule broken by ``text`` (a full literal name
    or the leading fragment of a partially-dynamic one) under ``kind``,
    if any: returns the family label."""
    for prefix, gauge_suffixes, label in _COUNTER_FAMILIES:
        if not text.startswith(prefix):
            continue
        if kind == "counter":
            return None
        if kind == "gauge" and text.endswith(tuple(gauge_suffixes)):
            return None
        return label
    return None


def _gauge_only_family(text: str, is_fragment: bool):
    """The gauge-only family ``text`` (a full literal name, or one
    literal fragment of a partially-dynamic name) belongs to, if any.
    Prefix families stay prefix-anchored even on fragments (an
    f-string in the family starts with the literal prefix, e.g.
    f"data.dist.{col}") — a fragment merely CONTAINING the prefix
    mid-name (".metadata.dist.errors") is a different namespace."""
    for mode, needle, label in _GAUGE_ONLY_FAMILIES:
        if mode == "prefix":
            hit = text.startswith(needle)
        else:
            hit = needle in text
        if hit:
            return label
    return None


#: Path (relative parts) of the one module allowed to emit ``fleet.*``.
_FEDERATION_PARTS = ("telemetry", "federation.py")


def _is_federation_file(path: Path) -> bool:
    return tuple(path.parts[-2:]) == _FEDERATION_PARTS


def load_gauge_policies(root: Path):
    """Parse ``GAUGE_MERGE_POLICIES`` (a pure dict literal) out of the
    tree's federation module without importing it. Returns the dict, or
    None when the module (or the table) is absent — the gauge-policy
    rule is then skipped, which lets the TP/FP tmp-tree tests declare
    their own minimal table."""
    fed = root / "photon_ml_tpu" / "telemetry" / "federation.py"
    if not fed.is_file():
        return None
    try:
        tree = ast.parse(fed.read_text(encoding="utf-8"),
                         filename=str(fed))
    except SyntaxError:
        return None
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign):
            targets = node.targets
        elif isinstance(node, ast.AnnAssign):  # NAME: Dict[...] = {...}
            targets = [node.target]
        else:
            continue
        for tgt in targets:
            if (isinstance(tgt, ast.Name)
                    and tgt.id == "GAUGE_MERGE_POLICIES"
                    and isinstance(node.value, ast.Dict)):
                out = {}
                for k, v in zip(node.value.keys, node.value.values):
                    if (isinstance(k, ast.Constant)
                            and isinstance(k.value, str)
                            and isinstance(v, ast.Constant)):
                        out[k.value] = v.value
                return out
    return None


def _policy_covers_name(name: str, policies: dict) -> bool:
    """A FULL literal gauge name resolves to a declared policy entry
    (exact > ``.suffix`` endswith > ``prefix.`` startswith — the same
    precedence the runtime resolver uses)."""
    if name in policies:
        return True
    for key in policies:
        if key.startswith(".") and name.endswith(key):
            return True
        if key.endswith(".") and name.startswith(key):
            return True
    return False


def _policy_covers_fragment(frag: str, policies: dict) -> bool:
    """One literal fragment of a partially-dynamic gauge name is
    covered: it matches an exact entry, ends with a ``.suffix`` entry's
    text (dot optional — ``pre + "burn_rate"`` fragments carry no
    leading dot), or overlaps a ``prefix.`` entry in either
    direction."""
    if frag in policies:
        return True
    for key in policies:
        if key.startswith(".") and frag.endswith(key[1:]):
            return True
        if key.endswith(".") and (frag.startswith(key)
                                  or key.startswith(frag)):
            return True
    return False


def _telemetry_bare_names(tree: ast.AST) -> set:
    """Factory names imported directly from the telemetry package
    (``from photon_ml_tpu.telemetry import counter``)."""
    out = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module \
                and node.module.startswith("photon_ml_tpu.telemetry"):
            for a in node.names:
                if a.name in FACTORIES:
                    out.add(a.asname or a.name)
    return out


def _literal_parts(node):
    """(fragments, fully_literal) for a metric-name argument: the string
    fragments statically present, and whether they cover the WHOLE
    name. Handles plain constants, ``a + b`` concatenation chains, and
    f-strings; anything else contributes an opaque dynamic part."""
    if isinstance(node, ast.Constant):
        if isinstance(node.value, str):
            return [node.value], True
        return [], False
    if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Add):
        lf, lfull = _literal_parts(node.left)
        rf, rfull = _literal_parts(node.right)
        return lf + rf, lfull and rfull
    if isinstance(node, ast.JoinedStr):
        frags = []
        for v in node.values:
            if isinstance(v, ast.Constant) and isinstance(v.value, str):
                frags.append(v.value)
        return frags, False
    return [], False


def _exemplars_kwarg(node: ast.Call):
    """True/False when the call passes a literal ``exemplars=`` keyword,
    None when absent or non-literal."""
    for kw in node.keywords:
        if kw.arg == "exemplars" and isinstance(kw.value, ast.Constant):
            return bool(kw.value.value)
    return None


def check_file(path: Path, src: str, registrations: dict,
               gauge_policies: dict = None) -> list:
    """Violations in one file; literal registrations accumulate into
    ``registrations`` (name -> {kind: first location}, with histogram
    kinds split into ``histogram``/``histogram_exemplars`` so an
    exemplar-bearing and a plain declaration of one name conflict) for
    the cross-file conflicting-type check."""
    try:
        tree = ast.parse(src, filename=str(path))
    except SyntaxError as e:
        return [(path, e.lineno or 0, "syntax",
                 f"does not parse: {e.msg}")]
    bare = _telemetry_bare_names(tree)
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) or not node.args:
            continue
        fn = node.func
        if isinstance(fn, ast.Attribute) and fn.attr in FACTORIES:
            kind = fn.attr
        elif isinstance(fn, ast.Name) and fn.id in bare:
            kind = fn.id
        else:
            continue
        exemplars = (_exemplars_kwarg(node) if kind == "histogram"
                     else None)
        frags, full = _literal_parts(node.args[0])
        if not frags:
            continue  # fully dynamic: runtime's problem
        if full:
            name = "".join(frags)
            if not _NAME_RE.match(name):
                out.append((path, node.lineno, "metric-name-schema",
                            f"{kind}({name!r}): metric names are dotted "
                            "snake_case — segment(.segment)*, each "
                            "[a-z][a-z0-9_]* (docs/OBSERVABILITY.md)"))
            else:
                if exemplars and not name.endswith("_seconds"):
                    out.append((
                        path, node.lineno, "exemplar-histogram-name",
                        f"histogram({name!r}, exemplars=True): exemplar-"
                        "bearing histograms carry trace_id latency "
                        "exemplars and must end in '_seconds' "
                        "(docs/OBSERVABILITY.md §Exemplars)"))
                family = _gauge_only_family(name, is_fragment=False)
                if family is not None and kind != "gauge":
                    out.append((
                        path, node.lineno, "gauge-only-family",
                        f"{kind}({name!r}): {family} is gauge-only — "
                        "distribution/drift values are instantaneous "
                        "readings refreshed on scrape "
                        "(docs/OBSERVABILITY.md §Distributions & "
                        "drift)"))
                cfam = _counter_family_violation(name, kind)
                if cfam is not None:
                    out.append((
                        path, node.lineno, "counter-family",
                        f"{kind}({name!r}): {cfam} is counter-only "
                        "(gauges only for allowlisted instantaneous "
                        "readings, histograms never — wire latency "
                        "belongs under serving.frontend.*) "
                        "(docs/OBSERVABILITY.md §Network front door)"))
                if (name.startswith("fleet.")
                        and not _is_federation_file(path)):
                    out.append((
                        path, node.lineno, "fleet-prefix-reserved",
                        f"{kind}({name!r}): the fleet.* prefix is "
                        "reserved for the aggregator "
                        "(telemetry/federation.py) — a peer emitting "
                        "it would collide with the merged plane "
                        "(docs/OBSERVABILITY.md §Federation)"))
                if (kind == "gauge" and gauge_policies is not None
                        and not _policy_covers_name(
                            name, gauge_policies)):
                    out.append((
                        path, node.lineno, "gauge-merge-policy",
                        f"gauge({name!r}) has no declared merge policy "
                        "in GAUGE_MERGE_POLICIES "
                        "(telemetry/federation.py) — the fleet merge "
                        "would silently default to 'last' (newest "
                        "snapshot wins); declare sum/max/last for the "
                        "family (docs/OBSERVABILITY.md §Federation)"))
                prev = registrations.setdefault(name, {})
                prev.setdefault(kind, (path, node.lineno))
                if exemplars is not None:
                    # Marker entries (filtered out of the type check):
                    # an explicit exemplars=True at one site and
                    # exemplars=False at another disagree about one
                    # get-or-create name; kwarg-less reads stay exempt.
                    prev.setdefault(f"exemplars_{exemplars}".lower(),
                                    (path, node.lineno))
        else:
            for frag in frags:
                m = _FRAGMENT_BAD_RE.search(frag)
                if m:
                    out.append((
                        path, node.lineno, "metric-name-schema",
                        f"{kind}(...{frag!r}...): literal fragment "
                        f"contains {m.group(0)!r} — metric names are "
                        "lowercase [a-z0-9_.] only"))
                    break
            for frag in frags:
                family = _gauge_only_family(frag, is_fragment=True)
                if family is not None and kind != "gauge":
                    out.append((
                        path, node.lineno, "gauge-only-family",
                        f"{kind}(...{frag!r}...): {family} is "
                        "gauge-only — distribution/drift values are "
                        "instantaneous readings refreshed on scrape "
                        "(docs/OBSERVABILITY.md §Distributions & "
                        "drift)"))
                    break
            for frag in frags:
                cfam = _counter_family_violation(frag, kind)
                if cfam is not None:
                    out.append((
                        path, node.lineno, "counter-family",
                        f"{kind}(...{frag!r}...): {cfam} is "
                        "counter-only (gauges only for allowlisted "
                        "instantaneous readings, histograms never) "
                        "(docs/OBSERVABILITY.md §Network front door)"))
                    break
            for frag in frags:
                if (frag.startswith("fleet.")
                        and not _is_federation_file(path)):
                    out.append((
                        path, node.lineno, "fleet-prefix-reserved",
                        f"{kind}(...{frag!r}...): the fleet.* prefix "
                        "is reserved for the aggregator "
                        "(telemetry/federation.py) "
                        "(docs/OBSERVABILITY.md §Federation)"))
                    break
            if (kind == "gauge" and gauge_policies is not None
                    and not any(_policy_covers_fragment(
                        f, gauge_policies) for f in frags)):
                out.append((
                    path, node.lineno, "gauge-merge-policy",
                    f"gauge(...{frags[0]!r}...) has no literal "
                    "fragment covered by GAUGE_MERGE_POLICIES "
                    "(telemetry/federation.py) — the fleet merge "
                    "would silently default to 'last'; declare "
                    "sum/max/last for the family "
                    "(docs/OBSERVABILITY.md §Federation)"))
    return out


_MARKER_KINDS = ("exemplars_true", "exemplars_false")


def conflicting_types(registrations: dict) -> list:
    out = []
    for name, kinds in sorted(registrations.items()):
        real = {k: v for k, v in kinds.items()
                if k not in _MARKER_KINDS}
        if len(real) > 1:
            where = ", ".join(
                f"{kind} at {p}:{ln}"
                for kind, (p, ln) in sorted(real.items()))
            out.append((Path("-"), 0, "metric-type-conflict",
                        f"{name!r} registered as multiple metric types: "
                        f"{where}"))
        if all(m in kinds for m in _MARKER_KINDS):
            where = ", ".join(
                f"exemplars={m.rsplit('_', 1)[1]} at {p}:{ln}"
                for m, (p, ln) in sorted(kinds.items())
                if m in _MARKER_KINDS)
            out.append((Path("-"), 0, "exemplar-declaration-conflict",
                        f"{name!r} declared both exemplar-bearing and "
                        f"plain ({where}) — the registry is "
                        "get-or-create, whichever runs first wins "
                        "silently"))
    return out


def iter_py_files(root: Path, paths):
    for raw in paths:
        p = root / raw
        if p.is_file():
            yield p
        else:
            yield from sorted(p.rglob("*.py"))


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("paths", nargs="*", default=None)
    ap.add_argument("--root", default=".",
                    help="tree root (for tests against tmp trees)")
    args = ap.parse_args(argv)
    root = Path(args.root)
    paths = args.paths or DEFAULT_PATHS
    registrations: dict = {}
    violations = []
    gauge_policies = load_gauge_policies(root)
    for f in iter_py_files(root, paths):
        violations.extend(
            check_file(f, f.read_text(encoding="utf-8"), registrations,
                       gauge_policies=gauge_policies))
    violations.extend(conflicting_types(registrations))
    for path, lineno, rule, msg in violations:
        print(f"{path}:{lineno}: [{rule}] {msg}")
    if violations:
        print(f"{len(violations)} metric-name violation(s)")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
