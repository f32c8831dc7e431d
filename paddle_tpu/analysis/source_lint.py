"""AST-based source linter with framework-specific rules, run over
paddle_tpu/ itself (tools/graph_lint.py --all and the tier-1 gate).

Rules target the hazards the jaxpr passes cannot see because they happen
BEFORE tracing:

  np-random-in-traced-code : np.random.* inside a function of the
      trace-reachable core (nn/, models/, ops/, tensor/, core/, amp/).
      Under jit the draw happens once at trace time and the sample is
      BAKED into the compiled program — every step replays it. Layer
      __init__ / parameter-init code is exempt (runs eagerly, once).
  time-in-traced-code : time.time()/perf_counter() in the same scope —
      a trace-time constant masquerading as a clock.
  mutable-default-arg : list/dict/set literal defaults on methods of
      nn.Layer subclasses — shared across every instance of the layer
      (the classic aliasing bug, promoted to error because layers are
      long-lived and cloned).
  private-model-import-in-serving : a module under inference/ or
      serving/ importing a module-PRIVATE name (``_foo``) from
      ``models.*``. The serving tier is model-agnostic by contract
      (docs/SERVING.md): models plug in through the DecodeModel registry
      (serving/decode_model.py), never by reaching into a model module's
      privates — that coupling is exactly what ISSUE 6 removed.
  step-loop-host-sync : a per-step host pull (np.asarray /
      jax.device_get / .item() / .block_until_ready()) inside the
      trainer/serving HOT-PATH functions (SpmdTrainer.train_step's
      implementation chain, ServingEngine.step's) — each one serializes
      the dispatch pipeline once per step. The deliberate syncs (the
      benchmark sync, the decode token fetch, the windowed deferred
      guard drain, host-side batch ingest) carry
      ``# lint: allow(step-loop-host-sync)``; anything new is an error
      (the ISSUE 11 satellite: hot paths stay clean).
  nonreduced-client-output : a function in federated/ returns a
      ``client_map`` result that never passed through a ``federated_*``
      reduce (or ``collective.client_reduce``). Client-placed values
      escaping a federated API leak per-client data to the server
      unaggregated AND skip the metered collective chokepoint — the
      MapReduce contract (docs/FEDERATED.md) is map THEN reduce. A
      deliberate client-placed return (e.g. ``client_map`` itself)
      carries ``# lint: allow(client_output)``.
  unlocked-thread-shared-write : in a module that spawns daemon threads
      (THREAD_SHARED_MODULES: the blackbox sentinel, the monitor
      registry, the profiler), a write to module-global shared state
      reachable from a thread body that is not under the module's
      designated lock. The GIL makes ``x += 1`` interleavable, not
      atomic — cross-thread mutations take the lock or carry
      ``# lint: allow(thread-shared-write)`` with a reason (e.g. a
      single-slot boolean latch).

Suppression: a trailing ``# lint: allow(<rule>)`` comment on the
offending line acknowledges a documented, deliberate exception (e.g. an
eager host op that already warns under tracing). The marker grammar and
alias table are shared with the contract-auditor passes
(analysis/allowlist.py).
"""
import ast
import os

from .allowlist import RULE_ALIASES as _RULE_ALIASES  # noqa: F401 (compat)
from .allowlist import allowed as _shared_allowed
from .registry import Finding

# packages whose function bodies are reachable from a jit trace
_TRACED_PKGS = ("nn", "models", "ops", "tensor", "core", "amp")
# packages forming the serving tier: model access ONLY via the DecodeModel
# registry, never a model module's privates
_SERVING_PKGS = ("inference", "serving")
# methods that run eagerly at construction time, never inside a trace
_INIT_METHODS = {"__init__", "__init_subclass__", "reset_parameters",
                 "_init_weights", "extra_repr", "__repr__"}

RULES = {
    "np-random-in-traced-code": "error",
    "time-in-traced-code": "warning",
    "mutable-default-arg": "error",
    "private-model-import-in-serving": "error",
    "nonreduced-client-output": "error",
    "step-loop-host-sync": "error",
    "unlocked-thread-shared-write": "error",
    "syntax-error": "error",
}

#: per-step hot-path functions policed by step-loop-host-sync: the
#: train-step and serving-step implementation chains. Keyed by the
#: module's path relative to the paddle_tpu package root.
HOT_PATHS = {
    os.path.join("distributed", "spmd.py"): {
        "train_step", "_train_step_impl", "_run_step", "_unpack_step",
        "_finish_step", "_drain_verdicts"},
    os.path.join("inference", "serving.py"): {
        "step", "_step_inner", "_step_inner_sync", "_step_inner_lookahead",
        "_dispatch_ahead", "_emit_round", "_emit_token", "_admit_phase",
        "_step_speculative", "_advance_prefill", "_activate",
        "_admit_one_inner", "_advance_and_admit", "_dispatch_decode",
        "_apply_decode"},
}

#: dotted call names that pull device values to the host
_SYNC_CALLS = {"np.asarray", "numpy.asarray", "jax.device_get"}
#: method names that pull device values to the host when called
_SYNC_METHODS = {"item", "block_until_ready"}

#: modules that spawn daemon threads (or are mutated cross-thread) and
#: their designated lock name — the unlocked-thread-shared-write rule
#: polices writes to module-global state reachable from thread bodies.
#: Keyed by path relative to the paddle_tpu package root.
THREAD_SHARED_MODULES = {
    os.path.join("monitor", "blackbox.py"): "_LOCK",
    os.path.join("monitor", "registry.py"): "_lock",
    os.path.join("profiler", "__init__.py"): "_LOCK",
}

# the shared marker grammar lives in analysis/allowlist.py
_allowed = _shared_allowed


def _dotted(node):
    """'np.random.uniform' for an Attribute/Call chain, '' otherwise."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return ".".join(reversed(parts))


def _is_layer_class(cls):
    for b in cls.bases:
        name = _dotted(b) or (b.id if isinstance(b, ast.Name) else "")
        if name.split(".")[-1] in ("Layer", "Module"):
            return True
    return False


class _Visitor(ast.NodeVisitor):
    def __init__(self, rel_path, lines, traced, serving=False,
                 federated=False, hot_funcs=None):
        self.rel = rel_path
        self.lines = lines
        self.traced = traced
        self.serving = serving
        self.federated = federated
        self.hot_funcs = hot_funcs or frozenset()
        self.findings = []
        self._func_stack = []
        self._class_stack = []
        # per-function {name: lineno} of client_map results not yet passed
        # through a federated_* reduce (nonreduced-client-output)
        self._client_vals = []

    def _emit(self, rule, lineno, message):
        if _allowed(self.lines, lineno, rule):
            return
        self.findings.append(Finding(
            rule, RULES[rule], message, where=f"{self.rel}:{lineno}"))

    # -- function / class scoping ------------------------------------------
    def visit_ClassDef(self, node):
        self._class_stack.append(node)
        self.generic_visit(node)
        self._class_stack.pop()

    def _visit_func(self, node):
        if (self._class_stack and _is_layer_class(self._class_stack[-1])
                and self._func_stack == []):
            defaults = list(node.args.defaults) + [
                d for d in node.args.kw_defaults if d is not None]
            for d in defaults:
                if isinstance(d, (ast.List, ast.Dict, ast.Set)):
                    self._emit(
                        "mutable-default-arg", d.lineno,
                        f"mutable default argument on "
                        f"{self._class_stack[-1].name}.{node.name} — "
                        "shared across every call and instance; default "
                        "to None and build inside the body")
        self._func_stack.append(node)
        self._client_vals.append({})
        self.generic_visit(node)
        self._client_vals.pop()
        self._func_stack.pop()

    visit_FunctionDef = _visit_func
    visit_AsyncFunctionDef = _visit_func

    # -- nonreduced-client-output bookkeeping (federated/ modules) ----------
    @staticmethod
    def _is_client_map_call(node):
        return (isinstance(node, ast.Call)
                and _dotted(node.func).split(".")[-1] == "client_map")

    @staticmethod
    def _is_reduce_call(node):
        last = _dotted(node.func).split(".")[-1]
        return last.startswith("federated_") or last == "client_reduce"

    def visit_Assign(self, node):
        if self.federated and self._client_vals:
            scope = self._client_vals[-1]
            names = [t.id for t in node.targets if isinstance(t, ast.Name)]
            if self._is_client_map_call(node.value):
                for n in names:
                    scope[n] = node.lineno
            else:
                for n in names:
                    scope.pop(n, None)   # rebound to something else
        self.generic_visit(node)

    def _mark_reduced(self, node):
        """A federated_* reduce consumed these args: clear every Name
        reachable inside them (generous by design — a lint heuristic)."""
        scope = self._client_vals[-1]
        for arg in list(node.args) + [kw.value for kw in node.keywords]:
            for sub in ast.walk(arg):
                if isinstance(sub, ast.Name):
                    scope.pop(sub.id, None)

    def visit_Return(self, node):
        if self.federated and self._client_vals and node.value is not None:
            scope = self._client_vals[-1]
            parts = (node.value.elts
                     if isinstance(node.value, (ast.Tuple, ast.List))
                     else [node.value])
            fname = self._func_stack[-1].name if self._func_stack else "?"
            for part in parts:
                escaped = (isinstance(part, ast.Name) and part.id in scope) \
                    or self._is_client_map_call(part)
                if escaped:
                    self._emit(
                        "nonreduced-client-output", node.lineno,
                        f"{fname} returns a client_map result that never "
                        "passed through a federated_* reduce: client-"
                        "placed values must aggregate via federated_sum/"
                        "mean/weighted_mean (the metered collective "
                        "chokepoint) before escaping a federated API, or "
                        "carry `# lint: allow(client_output)` when client "
                        "placement is the contract")
        self.generic_visit(node)

    def _in_traced_scope(self):
        if not self.traced or not self._func_stack:
            return False
        return self._func_stack[0].name not in _INIT_METHODS

    def _in_hot_scope(self):
        """Inside a policed per-step hot-path function (closures nested
        in one count — they run per step too)."""
        return any(f.name in self.hot_funcs for f in self._func_stack)

    # -- import rules -------------------------------------------------------
    def visit_ImportFrom(self, node):
        # serving tier: `from ..models.X import _private` (any nesting,
        # module- or function-level) couples the engine to one model's
        # internals — the DecodeModel registry is the doorway
        mod = node.module or ""
        if self.serving and (mod == "models" or mod.startswith("models.")
                             or ".models." in mod
                             or mod.endswith(".models")):
            private = sorted(a.name for a in node.names
                             if a.name.startswith("_"))
            if private:
                self._emit(
                    "private-model-import-in-serving", node.lineno,
                    f"serving code imports module-private "
                    f"{', '.join(private)} from {mod!r}: the serving "
                    "tier is model-agnostic — go through the DecodeModel "
                    "registry (paddle_tpu/serving/decode_model.py) or "
                    "register an adapter on the model module")
        self.generic_visit(node)

    # -- call-site rules ----------------------------------------------------
    def visit_Call(self, node):
        name = _dotted(node.func)
        if self.federated and self._client_vals \
                and self._is_reduce_call(node):
            self._mark_reduced(node)
        if self.hot_funcs and self._in_hot_scope():
            last = name.split(".")[-1]
            if name in _SYNC_CALLS or (last in _SYNC_METHODS
                                       and "." in name):
                self._emit(
                    "step-loop-host-sync", node.lineno,
                    f"{name}(...) inside per-step hot path "
                    f"{self._func_stack[-1].name}: a host pull here "
                    "serializes the dispatch pipeline EVERY step — "
                    "defer/batch the fetch (docs/PERF.md), or mark a "
                    "deliberate sync with "
                    "`# lint: allow(step-loop-host-sync)`")
        if self._in_traced_scope():
            if name.startswith(("np.random.", "numpy.random.")) or \
                    name in ("np.random", "numpy.random"):
                self._emit(
                    "np-random-in-traced-code", node.lineno,
                    f"{name}(...) in jit-reachable code: under a trace "
                    "the draw happens once and the sample is baked into "
                    "the compiled program — use jax.random with a "
                    "threaded key (or mark a documented eager host op "
                    "with `# lint: allow(np-random-in-traced-code)`)")
            elif name in ("time.time", "time.perf_counter",
                          "time.monotonic"):
                self._emit(
                    "time-in-traced-code", node.lineno,
                    f"{name}() in jit-reachable code: a trace-time "
                    "constant, frozen into the compiled program")
        self.generic_visit(node)


def _dotted_last(node):
    d = _dotted(node)
    return d.split(".")[-1] if d else ""


class _ThreadScan(ast.NodeVisitor):
    """Phase 1 of the thread-discipline lint: module globals, function
    defs (by simple name), intra-module call edges, thread-body roots."""

    def __init__(self):
        self.module_globals = set()
        self.funcs = {}          # name -> [FunctionDef]
        self.calls = {}          # func name -> {called simple names}
        self.thread_roots = set()
        self.lock_seen = False
        self._stack = []
        self._class_bases = []

    def set_lock(self, lock_name):
        self._lock_name = lock_name

    def visit_ClassDef(self, node):
        bases = [_dotted_last(b) if not isinstance(b, ast.Name) else b.id
                 for b in node.bases]
        self._class_bases.append(bases)
        self.generic_visit(node)
        self._class_bases.pop()

    def _visit_func(self, node):
        self.funcs.setdefault(node.name, []).append(node)
        # a Thread subclass's run() IS a thread body
        if node.name == "run" and self._class_bases \
                and any(b.endswith("Thread") for b in self._class_bases[-1]):
            self.thread_roots.add("run")
        self._stack.append(node.name)
        self.generic_visit(node)
        self._stack.pop()

    visit_FunctionDef = _visit_func
    visit_AsyncFunctionDef = _visit_func

    def _visit_assign_targets(self, targets):
        if self._stack:
            return
        for t in targets:
            if isinstance(t, ast.Name):
                self.module_globals.add(t.id)
            elif isinstance(t, (ast.Tuple, ast.List)):
                self._visit_assign_targets(list(t.elts))

    def visit_Assign(self, node):
        self._visit_assign_targets(node.targets)
        self.generic_visit(node)

    def visit_AnnAssign(self, node):
        self._visit_assign_targets([node.target])
        self.generic_visit(node)

    def visit_Call(self, node):
        name = _dotted(node.func)
        if self._stack:
            self.calls.setdefault(self._stack[-1], set()).add(
                name.split(".")[-1])
        if name.split(".")[-1] == "Thread":
            for kw in node.keywords:
                if kw.arg == "target":
                    tgt = _dotted_last(kw.value) if not isinstance(
                        kw.value, ast.Name) else kw.value.id
                    if tgt:
                        self.thread_roots.add(tgt)
        self.generic_visit(node)

    def visit_Attribute(self, node):
        if node.attr == getattr(self, "_lock_name", None):
            self.lock_seen = True
        self.generic_visit(node)

    def visit_Name(self, node):
        if node.id == getattr(self, "_lock_name", None):
            self.lock_seen = True
        self.generic_visit(node)


class _WriteScan(ast.NodeVisitor):
    """Phase 2: inside one (thread-reachable) function, flag writes to
    module-global-rooted state outside `with <lock>:` blocks."""

    def __init__(self, module_globals, lock_name, rel, lines, emit):
        self.module_globals = module_globals
        self.lock_name = lock_name
        self.rel = rel
        self.lines = lines
        self.emit = emit
        self._lock_depth = 0
        self._locals = set()
        self._globals_decl = set()

    def prime(self, func):
        args = func.args
        for a in (list(args.posonlyargs) + list(args.args)
                  + list(args.kwonlyargs)
                  + ([args.vararg] if args.vararg else [])
                  + ([args.kwarg] if args.kwarg else [])):
            self._locals.add(a.arg)
        def bound_names(t, out):
            # only PLAIN name bindings shadow: `x = ...`, `x, y = ...`.
            # A Subscript/Attribute target (`_STATE["k"] = v`) mutates
            # the module object — its root must NOT count as local
            if isinstance(t, ast.Name):
                out.add(t.id)
            elif isinstance(t, (ast.Tuple, ast.List, ast.Starred)):
                for el in getattr(t, "elts", [t.value] if isinstance(
                        t, ast.Starred) else []):
                    bound_names(el, out)

        for node in ast.walk(func):
            if isinstance(node, ast.Global):
                self._globals_decl.update(node.names)
            elif isinstance(node, ast.arg):
                # nested-def / lambda parameters shadow too
                self._locals.add(node.arg)
            elif isinstance(node, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
                targets = node.targets if isinstance(node, ast.Assign) \
                    else [node.target]
                for t in targets:
                    bound_names(t, self._locals)
            elif isinstance(node, (ast.For, ast.AsyncFor)):
                bound_names(node.target, self._locals)
            elif isinstance(node, ast.withitem) and node.optional_vars:
                bound_names(node.optional_vars, self._locals)
        self._locals -= self._globals_decl
        return self

    # nested defs are visited for writes too (they run on the thread),
    # but their params/locals shadow — good enough for a lint heuristic

    def _is_locked_with(self, node):
        for item in node.items:
            if _dotted_last(item.context_expr) == self.lock_name:
                return True
        return False

    def visit_With(self, node):
        locked = self._is_locked_with(node)
        if locked:
            self._lock_depth += 1
        self.generic_visit(node)
        if locked:
            self._lock_depth -= 1

    visit_AsyncWith = visit_With

    def _root_name(self, t):
        while isinstance(t, (ast.Attribute, ast.Subscript)):
            t = t.value
        return t.id if isinstance(t, ast.Name) else None

    def _check_target(self, t, lineno):
        if isinstance(t, (ast.Tuple, ast.List)):
            for el in t.elts:
                self._check_target(el, lineno)
            return
        shared = False
        if isinstance(t, ast.Name):
            shared = t.id in self._globals_decl \
                or (t.id in self.module_globals
                    and t.id not in self._locals)
        else:
            root = self._root_name(t)
            shared = root is not None and root in self.module_globals \
                and root not in self._locals
        if shared and self._lock_depth == 0:
            name = self._root_name(t) if not isinstance(t, ast.Name) \
                else t.id
            self.emit(
                "unlocked-thread-shared-write", lineno,
                f"write to module-shared {name!r} reachable from a "
                f"daemon-thread body without holding {self.lock_name} — "
                "the GIL interleaves, it does not serialize; take the "
                "lock or mark a deliberate single-slot latch with "
                "`# lint: allow(thread-shared-write)`")

    def visit_Assign(self, node):
        for t in node.targets:
            self._check_target(t, node.lineno)
        self.generic_visit(node)

    def visit_AnnAssign(self, node):
        self._check_target(node.target, node.lineno)
        self.generic_visit(node)

    def visit_AugAssign(self, node):
        self._check_target(node.target, node.lineno)
        self.generic_visit(node)


def lint_thread_discipline(source, rel_path="<string>", lock_name="_LOCK"):
    """The unlocked-thread-shared-write rule over one module: find
    thread bodies (``threading.Thread(target=...)`` targets and
    ``Thread``-subclass ``run`` methods), walk the same-module call
    graph they can reach, and flag writes to module-global-rooted state
    outside ``with <lock_name>:``. Returns [Finding]."""
    findings = []
    lines = source.splitlines()

    def emit(rule, lineno, message):
        if not _allowed(lines, lineno, rule):
            findings.append(Finding(rule, RULES[rule], message,
                                    where=f"{rel_path}:{lineno}"))

    try:
        tree = ast.parse(source)
    except SyntaxError as e:
        return [Finding("syntax-error", "error",
                        f"unparseable source: {e}", where=rel_path)]
    scan = _ThreadScan()
    scan.set_lock(lock_name)
    scan.visit(tree)
    if not scan.lock_seen:
        findings.append(Finding(
            "unlocked-thread-shared-write",
            RULES["unlocked-thread-shared-write"],
            f"{rel_path} is declared thread-shared "
            f"(THREAD_SHARED_MODULES) but its designated lock "
            f"{lock_name!r} appears nowhere in the module",
            where=rel_path))
    if not scan.thread_roots:
        return findings
    # names reachable from the thread bodies over same-module calls
    reach, frontier = set(scan.thread_roots), list(scan.thread_roots)
    while frontier:
        fn = frontier.pop()
        for callee in scan.calls.get(fn, ()):
            if callee in scan.funcs and callee not in reach:
                reach.add(callee)
                frontier.append(callee)
    for fname in sorted(reach):
        for func in scan.funcs.get(fname, ()):
            _WriteScan(scan.module_globals, lock_name, rel_path, lines,
                       emit).prime(func).visit(func)
    findings.sort(key=lambda f: f.where)
    return findings


def lint_source(source, rel_path="<string>", traced=True, serving=None,
                federated=None, hot_funcs=None, thread_lock=None):
    """Lint one python source string; returns a list of Finding.
    serving=None / federated=None derive the tier flags from rel_path
    (modules under inference|serving/ resp. federated/); hot_funcs=None
    derives the step-loop-host-sync function set from HOT_PATHS;
    thread_lock=None derives the thread-discipline lock from
    THREAD_SHARED_MODULES."""
    if serving is None:
        serving = _is_serving_module(rel_path)
    if federated is None:
        federated = _is_federated_module(rel_path)
    if hot_funcs is None:
        hot_funcs = HOT_PATHS.get(rel_path, frozenset())
    if thread_lock is None:
        thread_lock = THREAD_SHARED_MODULES.get(rel_path)
    tree = ast.parse(source)
    v = _Visitor(rel_path, source.splitlines(), traced, serving=serving,
                 federated=federated, hot_funcs=hot_funcs)
    v.visit(tree)
    if thread_lock:
        v.findings.extend(lint_thread_discipline(source, rel_path,
                                                 thread_lock))
    v.findings.sort(key=lambda f: f.where)
    return v.findings


def _is_traced_module(rel_path):
    top = rel_path.split(os.sep)[0]
    if top not in _TRACED_PKGS:
        return False
    # vision/io/text/datasets are host-side by design; nn/, models/ etc.
    # are fully trace-reachable
    return True


def _is_serving_module(rel_path):
    return rel_path.split(os.sep)[0] in _SERVING_PKGS


def _is_federated_module(rel_path):
    return rel_path.split(os.sep)[0] == "federated"


def lint_path(root=None):
    """Lint the paddle_tpu package tree; returns a list of Finding."""
    if root is None:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    findings = []
    for dirpath, dirnames, files in os.walk(root):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in sorted(files):
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            rel = os.path.relpath(path, root)
            with open(path, encoding="utf-8") as f:
                src = f.read()
            try:
                findings.extend(
                    lint_source(src, rel, traced=_is_traced_module(rel)))
            except SyntaxError as e:   # pragma: no cover — repo is valid
                findings.append(Finding(
                    "syntax-error", "error",
                    f"unparseable source: {e}", where=rel))
    return findings
