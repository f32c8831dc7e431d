"""Pass registry + report types for jaxpr analysis.

The shape mirrors the reference's REGISTER_PASS(name, pass) macro
(paddle/fluid/framework/ir/pass.h): passes register under a unique name
with a default severity; `run_passes` traces (or accepts) a jaxpr, runs
every registered pass over one shared AnalysisContext, and assembles an
AnalysisReport whose findings carry pass name / severity / eqn provenance.
"""

# severity ordering is part of the public contract (report sorting and the
# tier-1 gate's "zero errors" criterion both key off it)
SEVERITIES = ("error", "warning", "info")
_SEV_RANK = {s: i for i, s in enumerate(SEVERITIES)}


class Finding:
    """One analysis result: what pass fired, how bad, and where.

    `where` is provenance — an eqn path like ``eqns[12]/pjit:_bernoulli``
    for jaxpr passes, or ``file.py:123`` for source-lint rules.
    """

    __slots__ = ("pass_name", "severity", "message", "where")

    def __init__(self, pass_name, severity, message, where=""):
        if severity not in _SEV_RANK:
            raise ValueError(
                f"severity must be one of {SEVERITIES}, got {severity!r}")
        self.pass_name = pass_name
        self.severity = severity
        self.message = message
        self.where = where

    def to_dict(self):
        return {"pass": self.pass_name, "severity": self.severity,
                "message": self.message, "where": self.where}

    def __repr__(self):
        loc = f" @ {self.where}" if self.where else ""
        return f"[{self.severity}] {self.pass_name}: {self.message}{loc}"


class AnalysisReport:
    """Findings for one analyzed target, ordered most-severe first.

    Ordering is STABLE: severity rank, then pass registration order, then
    discovery order — so reports diff cleanly across runs (the baseline
    fixture in tests/lint_baseline.json relies on this).
    """

    def __init__(self, name="", findings=None):
        self.name = name
        self.findings = list(findings or [])

    def add(self, finding):
        self.findings.append(finding)

    def extend(self, findings):
        self.findings.extend(findings)

    def sort(self):
        order = {n: i for i, n in enumerate(registered_passes())}
        self.findings.sort(key=lambda f: (
            _SEV_RANK.get(f.severity, len(SEVERITIES)),
            order.get(f.pass_name, len(order)), f.where))
        return self

    def by_severity(self, severity):
        return [f for f in self.findings if f.severity == severity]

    @property
    def errors(self):
        return self.by_severity("error")

    @property
    def warnings(self):
        return self.by_severity("warning")

    def counts(self):
        out = {s: 0 for s in SEVERITIES}
        for f in self.findings:
            out[f.severity] = out.get(f.severity, 0) + 1
        return out

    def to_dict(self):
        return {"name": self.name, "counts": self.counts(),
                "findings": [f.to_dict() for f in self.sort().findings]}

    def summary(self):
        c = self.counts()
        head = (f"{self.name or 'report'}: {c['error']} error(s), "
                f"{c['warning']} warning(s), {c['info']} info")
        lines = [head] + [f"  {f!r}" for f in self.sort().findings]
        return "\n".join(lines)


class AnalysisContext:
    """Everything a pass may inspect. Passes must treat it as read-only.

    closed_jaxpr : jax ClosedJaxpr of the analyzed function
    name         : label for the report
    mesh         : optional jax Mesh the function is meant to run under
                   (enables the sharding-flow passes)
    donated      : optional frozenset of invar indices already donated
                   (None = donation intent unknown; the donation pass
                   reports at info severity then)
    hlo_text     : optional compiled HLO text (enables the exact-count
                   collective audit on top of the jaxpr-level counts)
    large_threshold : element count above which a tensor is "large"
    in_specs     : optional per-invar shardings (NamedSharding /
                   PartitionSpec / None), seeding the sharding-flow
                   propagation when the trace itself carries none
    """

    def __init__(self, closed_jaxpr, name="", mesh=None, donated=None,
                 hlo_text=None, large_threshold=1 << 20, in_specs=None):
        self.closed_jaxpr = closed_jaxpr
        self.jaxpr = closed_jaxpr.jaxpr
        self.consts = list(closed_jaxpr.consts)
        self.name = name
        self.mesh = mesh
        self.donated = donated if donated is None else frozenset(donated)
        self.hlo_text = hlo_text
        self.large_threshold = int(large_threshold)
        self.in_specs = None if in_specs is None else tuple(in_specs)


_PASSES = {}        # name -> (fn, default_severity)
_PASS_ORDER = []    # registration order (stable report ordering)


def register_pass(name, severity="warning"):
    """Decorator: register fn(ctx) -> iterable[Finding] under `name`.

    Duplicate names are rejected (same contract as the reference's
    PassRegistry::Insert CHECK). `severity` is the pass's default for
    findings built via the injected `finding(...)` convenience attribute.
    """
    if severity not in _SEV_RANK:
        raise ValueError(
            f"severity must be one of {SEVERITIES}, got {severity!r}")

    def deco(fn):
        if name in _PASSES:
            raise ValueError(f"analysis pass {name!r} already registered")
        def finding(message, where="", severity=severity):
            return Finding(name, severity, message, where)
        fn.finding = finding
        fn.pass_name = name
        fn.default_severity = severity
        _PASSES[name] = (fn, severity)
        _PASS_ORDER.append(name)
        return fn

    return deco


def registered_passes():
    """Pass names in registration order."""
    return list(_PASS_ORDER)


def _as_closed_jaxpr(fn_or_jaxpr, args, kwargs):
    import jax
    from jax.extend import core as jex_core

    if isinstance(fn_or_jaxpr, jex_core.ClosedJaxpr):
        return fn_or_jaxpr
    if isinstance(fn_or_jaxpr, jex_core.Jaxpr):
        return jex_core.ClosedJaxpr(fn_or_jaxpr, ())
    if callable(fn_or_jaxpr):
        return jax.make_jaxpr(fn_or_jaxpr)(*args, **kwargs)
    raise TypeError(
        "run_passes expects a ClosedJaxpr, a Jaxpr, or a traceable "
        f"callable; got {type(fn_or_jaxpr).__name__} (for a static "
        "Program use Program.analysis_jaxpr(feed), for a Predictor use "
        "Predictor.analysis_jaxpr())")


def run_passes(fn_or_jaxpr, *args, passes=None, name=None, mesh=None,
               donated=None, hlo_text=None, large_threshold=1 << 20,
               in_specs=None, **kwargs):
    """Run (a subset of) the registered passes; returns an AnalysisReport.

    fn_or_jaxpr: a jax ClosedJaxpr/Jaxpr, or a callable traced with *args
    via jax.make_jaxpr (tracing only — nothing is compiled or executed).
    passes: optional iterable of pass names to run (default: all).
    """
    closed = _as_closed_jaxpr(fn_or_jaxpr, args, kwargs)
    label = name or getattr(fn_or_jaxpr, "__name__", "") or "jaxpr"
    ctx = AnalysisContext(closed, name=label, mesh=mesh, donated=donated,
                          hlo_text=hlo_text, large_threshold=large_threshold,
                          in_specs=in_specs)
    selected = list(_PASS_ORDER) if passes is None else list(passes)
    unknown = [p for p in selected if p not in _PASSES]
    if unknown:
        raise ValueError(f"unknown analysis pass(es) {unknown}; "
                         f"registered: {registered_passes()}")
    report = AnalysisReport(name=label)
    for pname in selected:
        fn, _ = _PASSES[pname]
        report.extend(fn(ctx) or ())
    return report.sort()
