"""Observability-drift audit: code vs docs vs the metrics_dump contract.

The telemetry layer's value depends on its inventory staying true:
every metric family and span name the code can emit is documented in
docs/OBSERVABILITY.md, and everything the docs (or the
``tools/metrics_dump.py`` required-families lists) promise still exists
in code. Before ISSUE 12 that was reviewer vigilance; this pass makes
it mechanical:

  metric-undocumented : a ``monitor.counter/gauge/histogram`` family
      registered in code but missing from the OBSERVABILITY.md metric
      reference table.
  metric-doc-stale    : a reference-table row naming a family no code
      registers (the doc promises telemetry that is gone).
  span-undocumented   : a ``trace.span/start_span/emit/phase`` name
      literal missing from the span reference table (step phases are
      held to the same table as spans).
  span-doc-stale      : a span-table row with no emitting call site
      (dynamically-named families like ``collective/<op>`` are declared
      in :data:`DYNAMIC_SPANS` and accepted).
  required-family-gone: a family in metrics_dump's ``_REQUIRED`` /
      ``_REQUIRED_SERIES`` lists that no code registers — the CI smoke
      target would fail forever.

The docs side is parsed from the two audited tables in
docs/OBSERVABILITY.md (headings :data:`METRIC_TABLE_HEADING` and
:data:`SPAN_TABLE_HEADING`): first column, backticked name. Adding a
metric family = register it in code AND add its row; the contract gate
fails on either half alone.
"""
import ast
import os
import re

from .allowlist import allowed
from .registry import Finding

__all__ = ["RULES", "DYNAMIC_SPANS", "METRIC_TABLE_HEADING",
           "SPAN_TABLE_HEADING", "code_metric_families",
           "code_span_names", "doc_reference", "required_families",
           "audit_inventory", "audit_package"]

RULES = {
    "metric-undocumented": "error",
    "metric-doc-stale": "error",
    "span-undocumented": "error",
    "span-doc-stale": "error",
    "required-family-gone": "error",
}

METRIC_TABLE_HEADING = "## Metric family reference"
SPAN_TABLE_HEADING = "## Span name reference"

#: span families whose names are built at runtime (f-strings /
#: concatenation) — documented under a placeholder row the code harvest
#: cannot see. Keys are the exact doc-table spellings accepted.
DYNAMIC_SPANS = ("collective/<op>",)

#: modules whose counter/gauge/histogram *definitions* are the registry
#: machinery itself, not instrumentation call sites
_METRIC_DEF_EXEMPT = ("monitor/registry.py", "monitor/exporters.py")
#: the tracer's own module (docstring examples, the span constructors)
_SPAN_DEF_EXEMPT = ("trace/__init__.py",)

_METRIC_METHODS = ("counter", "gauge", "histogram")
_SPAN_METHODS = ("span", "start_span", "emit", "phase")
#: accepted receiver spellings — `_monitor.counter(...)` registers a
#: metric, `scan.counter(...)` or a bare `emit(...)` helper does not
_METRIC_RECEIVERS = ("monitor", "_monitor")
_SPAN_RECEIVERS = ("trace", "_trace")

_NAME_RE = re.compile(r"^[A-Za-z_][A-Za-z0-9_/<>]*$")


def _receiver_last(node):
    """Last segment of an attribute call's receiver ('' for bare
    names): `_monitor.counter(..)` -> '_monitor',
    `paddle.trace.span(..)` -> 'trace'."""
    if not isinstance(node.func, ast.Attribute):
        return ""
    recv = node.func.value
    if isinstance(recv, ast.Attribute):
        return recv.attr
    return recv.id if isinstance(recv, ast.Name) else ""


def _bare_telemetry_names(tree, methods, pkg_markers):
    """Method names the module imported FROM a telemetry module
    (`from ..monitor import counter`) — bare calls of those names are
    registrations too."""
    out = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module \
                and node.module.split(".")[-1] in pkg_markers:
            out |= {a.asname or a.name for a in node.names
                    if a.name in methods}
    return out


def _harvest(sources, methods, receivers, exempt):
    """{name: [(rel, lineno)]} of literal first-arg call sites whose
    receiver is a telemetry module alias (`_monitor.counter(...)`), or
    a bare name imported from one (`from ..monitor import counter`);
    the monitor package's own front-end calls its helpers bare."""
    out = {}
    for rel, src in sources.items():
        norm = rel.replace(os.sep, "/")
        if any(norm.endswith(e) for e in exempt):
            continue
        try:
            tree = ast.parse(src)
        except SyntaxError:
            continue
        bare_ok = _bare_telemetry_names(tree, methods, receivers)
        if norm.endswith("monitor/__init__.py"):
            bare_ok |= set(methods)   # the registry front-end itself
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            if isinstance(node.func, ast.Attribute):
                if node.func.attr not in methods \
                        or _receiver_last(node) not in receivers:
                    continue
            elif not (isinstance(node.func, ast.Name)
                      and node.func.id in bare_ok):
                continue
            first = node.args[0]
            if isinstance(first, ast.Constant) \
                    and isinstance(first.value, str) \
                    and _NAME_RE.match(first.value):
                out.setdefault(first.value, []).append((rel, node.lineno))
    return out


def code_metric_families(sources):
    return _harvest(sources, _METRIC_METHODS, _METRIC_RECEIVERS,
                    _METRIC_DEF_EXEMPT)


def code_span_names(sources):
    return _harvest(sources, _SPAN_METHODS, _SPAN_RECEIVERS,
                    _SPAN_DEF_EXEMPT)


_ROW_CELL_RE = re.compile(r"^\s*\|\s*`([^`]+)`")


def _table_rows(text, heading):
    """Backticked first-column names of the markdown table under
    `heading` (up to the next heading)."""
    rows = []
    in_section = False
    for line in text.splitlines():
        if line.startswith("#"):
            in_section = line.strip() == heading
            continue
        if not in_section:
            continue
        m = _ROW_CELL_RE.match(line)
        if m:
            name = m.group(1).split("{")[0].strip()
            if name and not name.startswith("-"):
                rows.append(name)
    return rows


def doc_reference(text):
    """(documented metric families, documented span names)."""
    return (_table_rows(text, METRIC_TABLE_HEADING),
            _table_rows(text, SPAN_TABLE_HEADING))


def required_families(dump_source):
    """Family names promised by metrics_dump's _REQUIRED /
    _REQUIRED_SERIES tables; {family: lineno}."""
    out = {}
    try:
        tree = ast.parse(dump_source)
    except SyntaxError:
        return out
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign):
            continue
        names = {t.id for t in node.targets if isinstance(t, ast.Name)}
        if not names & {"_REQUIRED", "_REQUIRED_SERIES"}:
            continue
        if not isinstance(node.value, ast.Dict):
            continue
        for val in node.value.values:
            if not isinstance(val, (ast.Tuple, ast.List)):
                continue
            for el in val.elts:
                if isinstance(el, ast.Constant) and isinstance(el.value,
                                                               str):
                    out.setdefault(el.value, node.lineno)
                elif isinstance(el, (ast.Tuple, ast.List)) and el.elts:
                    fam = el.elts[0]
                    if isinstance(fam, ast.Constant) \
                            and isinstance(fam.value, str):
                        out.setdefault(fam.value, node.lineno)
    return out


def audit_inventory(sources, doc_text, dump_source="", doc_where=None,
                    dynamic_spans=DYNAMIC_SPANS):
    """Run the drift rules over harvested code + docs; [Finding]."""
    doc_where = doc_where or "docs/OBSERVABILITY.md"
    findings = []
    metrics = code_metric_families(sources)
    spans = code_span_names(sources)
    doc_metrics, doc_spans = doc_reference(doc_text)
    lines_by_rel = {rel: src.splitlines() for rel, src in sources.items()}

    def emit_code(rule, name, sites, msg):
        rel, lineno = sites[0]
        if not allowed(lines_by_rel.get(rel, ()), lineno, rule):
            findings.append(Finding(rule, RULES[rule], msg,
                                    where=f"{rel}:{lineno}"))

    for name, sites in sorted(metrics.items()):
        if name not in doc_metrics:
            emit_code("metric-undocumented", name, sites,
                      f"metric family {name!r} is registered in code but "
                      f"has no row in the {doc_where} metric reference "
                      f"table ({METRIC_TABLE_HEADING!r}) — document it "
                      "or mark a deliberately-private family with "
                      "`# lint: allow(undocumented-metric)`")
    for name in doc_metrics:
        if name not in metrics:
            findings.append(Finding(
                "metric-doc-stale", RULES["metric-doc-stale"],
                f"{doc_where} documents metric family {name!r} but no "
                "code registers it — the telemetry it promises is gone; "
                "drop the row (or restore the family)",
                where=f"{doc_where}:{name}"))
    for name, sites in sorted(spans.items()):
        if name not in doc_spans:
            emit_code("span-undocumented", name, sites,
                      f"span {name!r} is emitted in code but has no row "
                      f"in the {doc_where} span reference table "
                      f"({SPAN_TABLE_HEADING!r}) — document it or mark "
                      "it `# lint: allow(undocumented-span)`")
    for name in doc_spans:
        if name not in spans and name not in dynamic_spans:
            findings.append(Finding(
                "span-doc-stale", RULES["span-doc-stale"],
                f"{doc_where} documents span {name!r} but no call site "
                "emits it (dynamic families belong in "
                "analysis/obs_audit.py DYNAMIC_SPANS)",
                where=f"{doc_where}:{name}"))
    for name, lineno in sorted(required_families(dump_source).items()):
        if name not in metrics:
            findings.append(Finding(
                "required-family-gone", RULES["required-family-gone"],
                f"tools/metrics_dump.py requires family {name!r} but no "
                "code registers it — the smoke target can never pass",
                where=f"tools/metrics_dump.py:{lineno}"))
    findings.sort(key=lambda f: f.where)
    return findings


def audit_package(root=None):
    """The repo audit: paddle_tpu/ call sites vs docs/OBSERVABILITY.md
    vs tools/metrics_dump.py."""
    from .flag_audit import package_sources

    if root is None:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    repo = os.path.dirname(root)
    sources = package_sources(root, include_tools=False)
    doc_path = os.path.join(repo, "docs", "OBSERVABILITY.md")
    with open(doc_path, encoding="utf-8") as f:
        doc_text = f.read()
    dump_path = os.path.join(repo, "tools", "metrics_dump.py")
    dump_source = ""
    if os.path.exists(dump_path):
        with open(dump_path, encoding="utf-8") as f:
            dump_source = f.read()
    return audit_inventory(sources, doc_text, dump_source)
