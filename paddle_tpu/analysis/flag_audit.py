"""Flag-contract audit: static verification of the FLAGS_* discipline.

Every feature in this framework hides behind a construction-time flag
(docs/OBSERVABILITY.md, docs/PERF.md) — the discipline the ten
``test_*_gate.py`` files each re-prove by hand for one flag. This pass
audits EVERY ``define_flag``/``get_flag`` site in the package at once:

  orphan-flag-unread      : a flag defined but read nowhere (package,
      tools/, bench.py) — dead configuration surface. A deliberate
      reference-parity stub carries ``# lint: allow(orphan-flag)``.
  orphan-flag-undefined   : a literal ``get_flag("x")`` of a name no
      module defines — the read silently returns its local default and
      drifts from whatever the definer later picks.
  flag-missing-help       : ``define_flag`` without a non-empty help
      string — ``paddle.get_flags`` and the docs tables both surface it.
  flag-default-conflict   : two modules define the same flag with
      DIFFERENT literal defaults (the runtime registry also raises on
      this since ISSUE 12 — the static form names both sites).
  structural-flag-key-miss: a STRUCTURAL flag (one that changes the
      compiled program or the state layout) whose consumption never
      reaches an ``_exec_key`` expression — toggling it would silently
      reuse a stale executable.
  hot-path-flag-read      : a structural flag re-read inside a per-step
      hot-path function (source_lint.HOT_PATHS) outside the sanctioned
      ``*_active`` cached-one-boolean checkers — construction-consumed
      flags must be compared against the cached value, not re-derived
      per step.
  flag-default-drift      : ``get_flag("x", local_default)`` whose local
      default differs from the defining site's — the two sites disagree
      about what "unset" means (warning).
  lazy-flag-eager-read    : a flag defined ONLY inside a manifest-lazy
      module (import_graph.LAZY_MODULES) but read from outside it — the
      read can run before the definition exists (warning; the fix is
      the flags.py pattern FLAGS_numerics uses).

Structural flags are DECLARED in :data:`STRUCTURAL_FLAGS` — adding a
flag that changes the traced program means adding it here AND routing it
into an exec-key expression (docs/ANALYSIS.md "Contract auditor" shows
the recipe).
"""
import ast
import os

from .allowlist import allowed
from .registry import Finding

__all__ = ["RULES", "STRUCTURAL_FLAGS", "KEY_FUNCS", "collect",
           "audit_inventory", "audit_package", "package_sources"]

RULES = {
    "orphan-flag-unread": "error",
    "orphan-flag-undefined": "error",
    "flag-missing-help": "error",
    "flag-default-conflict": "error",
    "structural-flag-key-miss": "error",
    "hot-path-flag-read": "error",
    "flag-default-drift": "warning",
    "lazy-flag-eager-read": "warning",
}

#: flags whose value changes the compiled program's identity or the
#: trainer's state layout: each MUST reach an _exec_key expression (the
#: key of an in-memory executable store) so a toggle recompiles instead
#: of reusing a stale executable. Declare new structural flags here (the
#: contract gate fails until the flag actually joins a key expression).
#: Not here, because no store of executables outlives their toggle:
#: paged_kv (each engine builds and holds its own programs, and
#: _paged_active raises on a post-construction disarm) and
#: flash_attention_block (a static argument of the kernel's own jit).
STRUCTURAL_FLAGS = (
    "check_nan_inf",
    "numerics",
    "quantized_allreduce",
    "quantized_allreduce_bits",
    "quantized_allreduce_min_size",
    "shard_weight_update",
    "overlap_grad_comm",
    "mpmd",
    "elastic",
)

#: function names whose bodies ARE executable-identity expressions —
#: anything referenced inside them counts as reaching the key
KEY_FUNCS = ("_exec_key", "_exec_key_and_example")

_MISSING = object()


def _dotted(node):
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return ".".join(reversed(parts))


def _literal(node, default=_MISSING):
    try:
        return ast.literal_eval(node)
    except (ValueError, TypeError, SyntaxError):
        return default


def _target_idents(targets):
    """Identifier names assigned by an assignment statement: plain names
    and attribute leaf names (``self._qar_bits`` -> ``_qar_bits``)."""
    out = set()
    for t in targets:
        for el in ast.walk(t):
            if isinstance(el, ast.Name):
                out.add(el.id)
            elif isinstance(el, ast.Attribute):
                out.add(el.attr)
    return out


def _refs(node):
    """Every identifier / attribute / string constant under `node`."""
    out = set()
    for el in ast.walk(node):
        if isinstance(el, ast.Name):
            out.add(el.id)
        elif isinstance(el, ast.Attribute):
            out.add(el.attr)
        elif isinstance(el, ast.Constant) and isinstance(el.value, str):
            out.add(el.value)
    return out


class _Scan(ast.NodeVisitor):
    """One module's flag inventory (defines / reads / key references)."""

    def __init__(self, rel, lines):
        self.rel = rel
        self.lines = lines
        self.defines = []      # (name, lineno, default_literal, help_ok)
        self.reads = []        # (name, lineno, func, in_key, default_lit)
        self.key_refs = set()  # identifiers/strings inside key contexts
        self.carrier_map = {}  # func name -> idents assigned from its call
        self._funcs = []
        self._key_depth = 0
        self._assign_targets = []

    # -- scoping ------------------------------------------------------------
    def _visit_func(self, node):
        keyed = node.name in KEY_FUNCS
        if keyed:
            self._key_depth += 1
            self.key_refs |= _refs(node)
        self._funcs.append(node.name)
        self.generic_visit(node)
        self._funcs.pop()
        if keyed:
            self._key_depth -= 1

    visit_FunctionDef = _visit_func
    visit_AsyncFunctionDef = _visit_func

    def _visit_assign(self, node, targets, value):
        if value is not None:
            # carrier hop: x, self._y = self._resolve_compress()  — the
            # call's enclosing function already carries the flag; its
            # assignment targets carry it one hop further
            for el in ast.walk(value):
                if isinstance(el, ast.Call):
                    fn = _dotted(el.func).split(".")[-1]
                    if fn:
                        self.carrier_map.setdefault(fn, set()).update(
                            _target_idents(targets))
        self._assign_targets.append(targets)
        self.generic_visit(node)
        self._assign_targets.pop()

    def visit_Assign(self, node):
        self._visit_assign(node, node.targets, node.value)

    def visit_AnnAssign(self, node):
        self._visit_assign(node, [node.target], node.value)

    def visit_AugAssign(self, node):
        self._visit_assign(node, [node.target], node.value)

    # -- call sites ----------------------------------------------------------
    def visit_Call(self, node):
        last = _dotted(node.func).split(".")[-1]
        if last == "define_flag" and node.args:
            name = _literal(node.args[0])
            if isinstance(name, str):
                default = _literal(node.args[1]) if len(node.args) > 1 \
                    else _MISSING
                help_node = node.args[2] if len(node.args) > 2 else None
                for kw in node.keywords:
                    if kw.arg == "help_str":
                        help_node = kw.value
                help_lit = None if help_node is None \
                    else _literal(help_node, default=None)
                # a non-literal help expression counts as present
                help_ok = help_node is not None and (
                    help_lit is None and not isinstance(help_node,
                                                        ast.Constant)
                    or bool(help_lit))
                self.defines.append(
                    (name, node.lineno, default, help_ok))
        elif last == "get_flag" and node.args:
            name = _literal(node.args[0])
            if isinstance(name, str):
                default = _literal(node.args[1]) if len(node.args) > 1 \
                    else _MISSING
                func = self._funcs[-1] if self._funcs else None
                targets = set()
                for ts in self._assign_targets:
                    targets |= _target_idents(ts)
                self.reads.append({
                    "name": name, "lineno": node.lineno, "func": func,
                    "in_key": self._key_depth > 0, "default": default,
                    "targets": targets})
        elif last == "get_flags" and node.args:
            names = _literal(node.args[0])
            if isinstance(names, str):
                names = [names]
            if isinstance(names, (list, tuple)):
                for n in names:
                    if isinstance(n, str):
                        self.reads.append({
                            "name": n, "lineno": node.lineno,
                            "func": self._funcs[-1] if self._funcs
                            else None, "in_key": self._key_depth > 0,
                            "default": _MISSING, "targets": set()})
        self.generic_visit(node)


def package_sources(root=None, include_tools=True):
    """{repo-relative path: source} for paddle_tpu/ (defines + reads)
    plus tools/ and bench.py (reads only live there too)."""
    if root is None:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    repo = os.path.dirname(root)
    out = {}
    scan_dirs = [root]
    if include_tools:
        tools = os.path.join(repo, "tools")
        if os.path.isdir(tools):
            scan_dirs.append(tools)
    for d in scan_dirs:
        for dirpath, dirnames, files in os.walk(d):
            dirnames[:] = [x for x in dirnames if x != "__pycache__"]
            for fn in sorted(files):
                if fn.endswith(".py"):
                    path = os.path.join(dirpath, fn)
                    with open(path, encoding="utf-8") as f:
                        out[os.path.relpath(path, repo)] = f.read()
    if include_tools:
        bench = os.path.join(repo, "bench.py")
        if os.path.exists(bench):
            with open(bench, encoding="utf-8") as f:
                out["bench.py"] = f.read()
    return out


def collect(sources):
    """Parse every module; returns {rel: _Scan} (unparseable skipped —
    the source linter owns syntax errors)."""
    scans = {}
    for rel, src in sources.items():
        try:
            tree = ast.parse(src)
        except SyntaxError:
            continue
        s = _Scan(rel, src.splitlines())
        s.visit(tree)
        scans[rel] = s
    return scans


def _module_name(rel):
    """'paddle_tpu/distributed/spmd.py' -> 'paddle_tpu.distributed.spmd'"""
    rel = rel[:-3] if rel.endswith(".py") else rel
    parts = rel.replace(os.sep, "/").split("/")
    if parts and parts[-1] == "__init__":
        parts = parts[:-1]
    return ".".join(parts)


def audit_inventory(scans, structural=STRUCTURAL_FLAGS, hot_paths=None,
                    lazy_modules=None):
    """Run every flag rule over collected scans; returns [Finding].

    hot_paths: {rel-to-package path: {func names}} (default:
    source_lint.HOT_PATHS); lazy_modules: manifest of lazily-imported
    module names (default: import_graph.LAZY_MODULES).
    """
    if hot_paths is None:
        from .source_lint import HOT_PATHS as hot_paths
    if lazy_modules is None:
        from .import_graph import LAZY_MODULES as lazy_modules
    findings = []

    def emit(rule, scan, lineno, msg):
        if not allowed(scan.lines, lineno, rule):
            findings.append(Finding(rule, RULES[rule], msg,
                                    where=f"{scan.rel}:{lineno}"))

    defines = {}   # name -> [(scan, lineno, default, help_ok)]
    reads = {}     # name -> [(scan, read-dict)]
    key_refs = set()
    carrier_map = {}
    for scan in scans.values():
        key_refs |= scan.key_refs
        for name, lineno, default, help_ok in scan.defines:
            defines.setdefault(name, []).append(
                (scan, lineno, default, help_ok))
        for r in scan.reads:
            reads.setdefault(r["name"], []).append((scan, r))
        for fn, targets in scan.carrier_map.items():
            carrier_map.setdefault(fn, set()).update(targets)

    # hot-path membership is PER FILE: HOT_PATHS keys are paths relative
    # to the paddle_tpu package root while scans carry repo-relative
    # paths — match on the suffix so a tools/ script defining its own
    # `step()` never collides with the trainer's
    _hot_norm = {k.replace(os.sep, "/"): frozenset(v)
                 for k, v in (hot_paths or {}).items()}

    def hot_funcs_for(rel):
        norm = rel.replace(os.sep, "/")
        for key, funcs in _hot_norm.items():
            if norm == key or norm.endswith("/" + key):
                return funcs
        return frozenset()

    lazy_modules = tuple(lazy_modules or ())

    # -- per-define rules ----------------------------------------------------
    for name, sites in sorted(defines.items()):
        for scan, lineno, default, help_ok in sites:
            if not help_ok:
                emit("flag-missing-help", scan, lineno,
                     f"FLAGS_{name} is defined without a help string — "
                     "paddle.get_flags and the docs flag tables surface "
                     "it; say what the flag does")
        if name not in reads:
            scan, lineno, _, _ = sites[0]
            emit("orphan-flag-unread", scan, lineno,
                 f"FLAGS_{name} is defined but never read (package, "
                 "tools/, bench.py) — dead configuration surface; wire "
                 "it or delete it (a deliberate reference-parity stub "
                 "carries `# lint: allow(orphan-flag)` with a comment)")
        lits = [(s, ln, d) for s, ln, d, _ in sites if d is not _MISSING]
        if lits:
            s0, ln0, d0 = lits[0]
            for s, ln, d in lits[1:]:
                # repr-distinct: False/0/0.0 are three different
                # contracts (define_flag's env parsing keys off type)
                if repr(d) != repr(d0):
                    emit("flag-default-conflict", s, ln,
                         f"FLAGS_{name} re-defined with default {d!r} "
                         f"but {s0.rel}:{ln0} says {d0!r} — whichever "
                         "module imports first silently wins; one "
                         "definition must own the default")

    # -- per-read rules ------------------------------------------------------
    for name, sites in sorted(reads.items()):
        if name not in defines:
            scan, r = sites[0]
            if r["lineno"]:
                emit("orphan-flag-undefined", scan, r["lineno"],
                     f"get_flag({name!r}) but no module defines "
                     f"FLAGS_{name} — the read silently returns its "
                     "local default; define_flag it where it is owned")
            continue
        def_default = next((d for _, _, d, _ in defines[name]
                            if d is not _MISSING), _MISSING)
        def_modules = {_module_name(s.rel) for s, _, _, _ in defines[name]}
        lazy_defs = def_modules and all(
            any(m == lm or m.startswith(lm + ".") for lm in lazy_modules)
            for m in def_modules)
        for scan, r in sites:
            if not r["lineno"]:
                continue
            # repr-distinct like flag-default-conflict and the runtime
            # define_flag check: False/0/0.0 are three different
            # contracts (env parsing keys off the default's type)
            if def_default is not _MISSING and r["default"] is not _MISSING \
                    and repr(r["default"]) != repr(def_default):
                emit("flag-default-drift", scan, r["lineno"],
                     f"get_flag({name!r}, {r['default']!r}) disagrees "
                     f"with the defining default {def_default!r} — the "
                     "two sites see different values while the flag is "
                     "unset")
            # tools/ and bench.py are entrypoints that import their lazy
            # subsystem explicitly before touching its flags — the
            # ordering hazard is package-internal
            if lazy_defs and scan.rel.split(os.sep)[0].split("/")[0] \
                    == "paddle_tpu" \
                    and _module_name(scan.rel) not in def_modules:
                emit("lazy-flag-eager-read", scan, r["lineno"],
                     f"FLAGS_{name} is defined only inside lazy module"
                     f"(s) {sorted(def_modules)} but read from "
                     f"{scan.rel} — the read can run before the "
                     "definition exists; define the flag in flags.py "
                     "(the FLAGS_numerics pattern)")
            if name in structural and r["func"] in hot_funcs_for(scan.rel) \
                    and not (r["func"] or "").endswith("_active"):
                emit("hot-path-flag-read", scan, r["lineno"],
                     f"structural FLAGS_{name} re-read inside per-step "
                     f"hot path {r['func']}: construction-consumed "
                     "flags are compared against the cached boolean in "
                     "a *_active checker, never re-derived per step")

    # -- structural reach ----------------------------------------------------
    for name in structural:
        if name not in defines:
            continue   # orphan rules already cover it
        sites = reads.get(name, ())
        reached = False
        carriers = set()
        for scan, r in sites:
            if r["in_key"]:
                reached = True
                break
            if r["func"]:
                carriers.add(r["func"])
            carriers |= r["targets"]
        if not reached:
            hop = set(carriers)
            for fn in list(carriers):
                hop |= carrier_map.get(fn, set())
            reached = bool(hop & key_refs) or name in key_refs
        if not reached:
            scan, lineno, _, _ = defines[name][0]
            emit("structural-flag-key-miss", scan, lineno,
                 f"structural FLAGS_{name} never reaches an _exec_key "
                 "expression: toggling it would reuse a stale executable "
                 "— join it to the key (docs/ANALYSIS.md "
                 "\"Contract auditor\") or remove it from "
                 "STRUCTURAL_FLAGS if it truly cannot change the "
                 "compiled program")
    findings.sort(key=lambda f: f.where)
    return findings


def audit_package(root=None):
    """The repo audit: scan paddle_tpu/ (+tools/, bench.py) and run every
    rule. Returns [Finding]."""
    return audit_inventory(collect(package_sources(root)))
