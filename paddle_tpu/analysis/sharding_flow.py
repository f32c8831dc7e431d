"""Sharding-flow analysis: partition specs propagated through jaxprs.

The distributed layer's invariants lived in reviewer heads until ISSUE 13:
a missing sharding constraint replicates a tensor on every device, a typo'd
collective axis deadlocks (or worse, silently runs on the wrong group), a
non-bijective ppermute drops a rank's activation on the floor, and a
collective inside one cond arm but not the other is a rank-divergence
deadlock that shows up only as a run that never ends. All of it is visible
statically — this module propagates NamedSharding/PartitionSpec facts
through a traced program's jaxpr under the mesh it is meant to run on and
turns each hazard into a Finding with the offending provenance chain.

Passes (registered in the ordinary pass registry, so they ride every
``run_passes`` call; all are inert without the relevant structure):

- ``implicit-replication`` (warning): a large intermediate whose value is
  MATERIALIZED replicated inside the graph — built from iota/broadcast/
  trace constants that no declared sharding covers — under a multi-device
  mesh. Declared-replicated *inputs* (dp params, optimizer moments) are
  intentional and everything derived from them inherits that intent; what
  this pass hunts is replication nobody declared. Upgrades PR 1's
  size-threshold-only ``unsharded-large-tensor`` pass: findings carry the
  provenance chain from the offending value back to its origin.
- ``resharding-churn`` (warning): a value constrained to spec S1 is
  re-constrained to a different S2 (same shape) — the partitioner lowers
  that as all-gather + re-slice every step.
- ``collective-axis-mismatch`` (error): a psum/ppermute/all_to_all/
  all_gather/axis_index names an axis no enclosing shard_map binds, or an
  axis absent from (or sized differently than) the deployment mesh.
- ``ppermute-malformed`` (error): a ppermute whose permutation is not a
  bijection, contains self-referential (i, i) pairs, or indexes outside
  the axis size.
- ``branch-collective-mismatch`` (error): cond branch arms with different
  collective sequences — ranks disagreeing on the predicate deadlock in
  the arm's collective (while-loop *predicates* containing collectives
  warn under the same pass).

Targets: ``sharding_reports()`` traces the bundled distributed programs
under their real meshes — gpt/bert/ernie SpmdTrainer steps (dp), the dp8
quantized-allreduce step (shard_map + int8 exchange), the pipeline
trainer (pp, ppermute ring), the serving decode step, and the
disaggregated prefill program — and runs the full battery over each.
CLI: ``python tools/graph_lint.py --sharding`` (folded into ``--all``);
tier-1: tests/test_sharding_gate.py. See docs/ANALYSIS.md.
"""
import numpy as np

from .jaxpr_utils import fmt_aval, iter_eqns, sub_jaxprs
from .registry import register_pass

#: rule -> severity, merged into the --list-rules vocabulary on both CLIs
RULES = {
    "implicit-replication": "warning",
    "resharding-churn": "warning",
    "collective-axis-mismatch": "error",
    "ppermute-malformed": "error",
    "branch-collective-mismatch": "error",
}

# jaxpr spellings of the named-axis collectives (psum traces as psum2 on
# current jax; reduce_scatter is psum_scatter's primitive name)
REDUCE_PRIMS = {"psum", "psum2", "pmin", "pmax", "pmin2", "pmax2"}
EXCHANGE_PRIMS = {"all_gather", "all_to_all", "psum_scatter",
                  "reduce_scatter", "pgather"}
PERMUTE_PRIMS = {"ppermute", "pshuffle"}
COLLECTIVE_PRIMS = REDUCE_PRIMS | EXCHANGE_PRIMS | PERMUTE_PRIMS
#: axis-consuming but not collective-sequenced (no wire traffic to match)
AXIS_ONLY_PRIMS = {"axis_index", "pvary", "pbroadcast", "pcast"}


def _axes_of(eqn):
    """Named axes an eqn consumes, normalized to a tuple of strings
    (positional/vmap integer axes are not deployment-mesh axes)."""
    raw = eqn.params.get("axes", eqn.params.get("axis_name", ()))
    if raw is None:
        return ()
    if not isinstance(raw, (tuple, list, frozenset, set)):
        raw = (raw,)
    return tuple(a for a in raw if isinstance(a, str))


def check_permutation(perm, axis_size=None):
    """Problems with a ppermute permutation: returns a list of strings
    (empty = proven bijective, non-self-referential, in range). A
    size-1 axis is exempt: its only possible permutation is the
    identity no-op a degenerate (single-device) mesh legitimately
    traces."""
    if axis_size == 1:
        return [f"rank(s) {sorted({r for p in perm for r in p if r})} "
                "outside the axis size 1"] if any(
                    r for p in perm for r in p) else []
    problems = []
    srcs = [s for s, _ in perm]
    dsts = [d for _, d in perm]
    dup_src = sorted({s for s in srcs if srcs.count(s) > 1})
    dup_dst = sorted({d for d in dsts if dsts.count(d) > 1})
    if dup_src:
        problems.append(f"duplicate source rank(s) {dup_src} — one rank "
                        "sends twice, not a permutation")
    if dup_dst:
        problems.append(f"duplicate destination rank(s) {dup_dst} — two "
                        "ranks send to one, not a bijection")
    selfs = sorted({s for s, d in perm if s == d})
    if selfs:
        problems.append(f"self-referential pair(s) {[(s, s) for s in selfs]}"
                        " — a rank permuting to itself is a wire no-op that"
                        " still pays the collective")
    if axis_size is not None:
        oob = sorted({r for p in perm for r in p
                      if not 0 <= r < axis_size})
        if oob:
            problems.append(f"rank(s) {oob} outside the axis size "
                            f"{axis_size}")
    return problems


# ---------------------------------------------------------------------------
# axis-environment walk: every eqn with the manual axes bound around it
# ---------------------------------------------------------------------------


def _shard_map_axes(eqn):
    """(manual axis names, mesh) bound by a shard_map eqn."""
    mesh = eqn.params.get("mesh")
    names = tuple(getattr(mesh, "axis_names", ()) or ())
    auto = eqn.params.get("auto") or ()
    return tuple(n for n in names if n not in auto), mesh


def _iter_with_axes(jaxpr, path="", axes_env=(), sm_mesh=None, depth=32):
    """Depth-first (eqn, path, axes_env, sm_mesh): like iter_eqns but
    threading the enclosing shard_map's manual axis names and mesh."""
    if depth < 0:
        return
    for i, eqn in enumerate(jaxpr.eqns):
        here = f"{path}eqns[{i}]"
        yield eqn, here, axes_env, sm_mesh
        tag = eqn.params.get("name", "")
        label = f"{eqn.primitive.name}:{tag}" if tag else eqn.primitive.name
        env, mesh = axes_env, sm_mesh
        if eqn.primitive.name == "shard_map":
            bound, m = _shard_map_axes(eqn)
            env = tuple(dict.fromkeys(axes_env + bound))
            mesh = m or sm_mesh
        for _, sub in sub_jaxprs(eqn):
            inner = sub.jaxpr if hasattr(sub, "jaxpr") else sub
            yield from _iter_with_axes(inner, f"{here}/{label}/", env,
                                       mesh, depth - 1)


def _axis_size(axis, sm_mesh, ctx_mesh):
    for mesh in (sm_mesh, ctx_mesh):
        shape = getattr(mesh, "shape", None)
        if shape and axis in shape:
            return shape[axis]
    return None


# ---------------------------------------------------------------------------
# collective soundness
# ---------------------------------------------------------------------------


@register_pass("collective-axis-mismatch", severity="error")
def collective_axis_mismatch(ctx):
    """Every collective's axis names must be bound by an enclosing
    shard_map AND exist (same size) on the deployment mesh."""
    out = []
    mesh_axes = tuple(getattr(ctx.mesh, "axis_names", ()) or ()) \
        if ctx.mesh is not None else None
    for eqn, path, env, sm_mesh in _iter_with_axes(ctx.jaxpr):
        p = eqn.primitive.name
        if p == "shard_map" and ctx.mesh is not None:
            for a in _shard_map_axes(eqn)[0]:
                if a not in mesh_axes:
                    out.append(collective_axis_mismatch.finding(
                        f"shard_map binds axis '{a}' that the deployment "
                        f"mesh {dict(ctx.mesh.shape)} does not have",
                        where=path))
                elif _axis_size(a, eqn.params.get("mesh"), None) not in (
                        None, ctx.mesh.shape[a]):
                    out.append(collective_axis_mismatch.finding(
                        f"shard_map axis '{a}' has size "
                        f"{eqn.params['mesh'].shape[a]} but the deployment "
                        f"mesh gives it {ctx.mesh.shape[a]}", where=path))
            continue
        if p not in COLLECTIVE_PRIMS and p not in AXIS_ONLY_PRIMS:
            continue
        for a in _axes_of(eqn):
            if a not in env:
                out.append(collective_axis_mismatch.finding(
                    f"'{p}' over axis '{a}' with no enclosing shard_map "
                    f"binding it (bound here: {sorted(env) or 'none'})",
                    where=path))
            elif mesh_axes is not None and a not in mesh_axes:
                out.append(collective_axis_mismatch.finding(
                    f"'{p}' over axis '{a}' absent from the deployment "
                    f"mesh {dict(ctx.mesh.shape)} — the program cannot "
                    "run on the mesh it is analyzed for", where=path))
    return out


@register_pass("ppermute-malformed", severity="error")
def ppermute_malformed(ctx):
    """ppermute permutations proven bijective, non-self-referential, and
    in-range for the axis size."""
    out = []
    for eqn, path, env, sm_mesh in _iter_with_axes(ctx.jaxpr):
        if eqn.primitive.name not in PERMUTE_PRIMS:
            continue
        perm = tuple(tuple(p) for p in eqn.params.get("perm", ()))
        axes = _axes_of(eqn)
        size = _axis_size(axes[0], sm_mesh, ctx.mesh) if axes else None
        for problem in check_permutation(perm, axis_size=size):
            out.append(ppermute_malformed.finding(
                f"ppermute over {axes or '?'} perm={list(perm)}: "
                f"{problem}", where=path))
    return out


def _collective_sequence(jaxpr, depth=32):
    """Ordered (primitive-family, axes) sequence of every collective at
    every nesting depth — the wire program two branch arms must agree on."""
    seq = []
    for eqn, _ in iter_eqns(jaxpr, max_depth=depth):
        p = eqn.primitive.name
        if p in COLLECTIVE_PRIMS:
            fam = ("reduce" if p in REDUCE_PRIMS
                   else "permute" if p in PERMUTE_PRIMS else p)
            seq.append((fam, _axes_of(eqn)))
    return tuple(seq)


@register_pass("branch-collective-mismatch", severity="error")
def branch_collective_mismatch(ctx):
    """cond arms must issue identical collective sequences (all ranks take
    the arm their own predicate picks — divergent predicates leave some
    ranks waiting in a collective the others never enter). while-loop
    PREDICATES containing collectives warn: a rank-varying trip count is
    the same deadlock one level up."""
    out = []
    for eqn, path, env, _ in _iter_with_axes(ctx.jaxpr):
        p = eqn.primitive.name
        if p == "cond":
            branches = eqn.params.get("branches", ())
            seqs = []
            for b in branches:
                inner = b.jaxpr if hasattr(b, "jaxpr") else b
                seqs.append(_collective_sequence(inner))
            if len(set(seqs)) > 1:
                desc = "; ".join(
                    f"arm[{i}]: {[f'{f}{list(a)}' for f, a in s] or 'none'}"
                    for i, s in enumerate(seqs))
                out.append(branch_collective_mismatch.finding(
                    "cond arms issue different collective sequences — a "
                    "rank-divergent predicate deadlocks the arm with the "
                    f"extra collective ({desc})", where=path))
        elif p == "while":
            cond_j = eqn.params.get("cond_jaxpr")
            if cond_j is not None:
                inner = cond_j.jaxpr if hasattr(cond_j, "jaxpr") else cond_j
                seq = _collective_sequence(inner)
                if seq:
                    out.append(branch_collective_mismatch.finding(
                        f"while-loop predicate contains collectives "
                        f"({[f'{f}{list(a)}' for f, a in seq]}) — a rank-"
                        "varying trip count hangs the slower ranks",
                        where=path, severity="warning"))
    return out


# ---------------------------------------------------------------------------
# partition-spec propagation (implicit replication + resharding churn)
# ---------------------------------------------------------------------------

#: what a traced jax.jit call is named in a jaxpr: "pjit" in older jax,
#: "jit" in the installed 0.9.0
_JIT_PRIMITIVES = ("pjit", "jit")

_UNKNOWN = "unknown"     # no sharding information
_SHARDED = "sharded"     # derived from sharded data, exact spec unknown


class _Spec:
    """A known placement: a PartitionSpec-like tuple plus where it came
    from ('declared' input/constraint vs 'derived' propagation)."""

    __slots__ = ("dims", "declared")

    def __init__(self, dims, declared=False):
        self.dims = tuple(dims)
        self.declared = declared

    @property
    def replicated(self):
        return all(d is None for d in self.dims)

    def __repr__(self):
        inner = ", ".join("None" if d is None else repr(d)
                          for d in self.dims)
        return f"P({inner})"


def _norm_spec(spec_like, rank, declared=False):
    """NamedSharding / PartitionSpec / dim-dict -> _Spec of `rank`."""
    spec = getattr(spec_like, "spec", spec_like)
    if isinstance(spec_like, dict):   # shard_map in_names/out_names form
        dims = [None] * rank
        for d, names in spec_like.items():
            if int(d) < rank:
                dims[int(d)] = tuple(names) if names else None
        return _Spec(dims, declared)
    try:
        entries = tuple(spec)
    except TypeError:
        return None
    dims = []
    for e in entries[:rank]:
        if e is None:
            dims.append(None)
        elif isinstance(e, (tuple, list)):
            dims.append(tuple(e))
        else:
            dims.append((str(e),))
    dims += [None] * (rank - len(dims))
    return _Spec(dims, declared)


def _is_named_sharding(obj):
    return hasattr(obj, "spec") and hasattr(obj, "mesh")


def _rank(var):
    shape = getattr(getattr(var, "aval", None), "shape", None)
    return None if shape is None else len(shape)


def _size(var):
    shape = getattr(getattr(var, "aval", None), "shape", None)
    if not shape:
        return 0
    try:
        return int(np.prod(shape))
    except Exception:
        return 0


#: primitives that taint instead of propagate (output layout is not the
#: input layout) — anything not listed and not shape-preserving also taints
_REDUCE_SHAPED = {"reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
                  "reduce_and", "reduce_or", "argmax", "argmin"}
_MATERIALIZERS = {"iota", "broadcast_in_dim"}


class _SpecFlow:
    """One propagation over a (possibly pjit-nested) jaxpr.

    env maps id(var) -> _Spec | 'sharded' | 'unknown'. origin maps
    id(var) -> (label, path, parent_id|None) so replication findings can
    print the chain from the offender back to the value that introduced
    the replication.
    """

    def __init__(self, large_threshold):
        self.large_threshold = large_threshold
        self.env = {}
        self.origin = {}
        self.constrained = set()          # ids consumed by a constraint
        self.replicated_offenders = []    # (path, var, root_kind)
        self.churn = []                   # (path, old_spec, new_spec, var)

    # -- provenance ---------------------------------------------------------
    def _note(self, var, label, path, parent=None):
        vid = id(var)
        if vid not in self.origin:
            self.origin[vid] = (label, path,
                                None if parent is None else id(parent))

    def chain(self, var, max_hops=8):
        """Human-readable provenance chain for a var."""
        parts = []
        vid = id(var)
        for _ in range(max_hops):
            entry = self.origin.get(vid)
            if entry is None:
                break
            label, path, parent = entry
            parts.append(f"{label}" + (f" @ {path}" if path else ""))
            if parent is None:
                break
            vid = parent
        return " <- ".join(parts) if parts else "(origin unknown)"

    # -- env helpers --------------------------------------------------------
    def get(self, var):
        from .jaxpr_utils import is_literal

        if is_literal(var):
            return _Spec((), declared=False)   # scalars: neutral
        return self.env.get(id(var), _UNKNOWN)

    def set(self, var, state):
        self.env[id(var)] = state

    # -- propagation --------------------------------------------------------
    def run(self, jaxpr, in_states=None, path=""):
        """Propagate through `jaxpr`; in_states aligns with jaxpr.invars
        (missing entries default to unknown). Returns outvar states."""
        from .jaxpr_utils import is_literal

        if in_states:
            for var, st in zip(jaxpr.invars, in_states):
                if st is not None:
                    self.set(var, st)
        for i, var in enumerate(jaxpr.invars):
            self._note(var, self._invar_label(var, i), path)
        for i, var in enumerate(jaxpr.constvars):
            self.set(var, _Spec((None,) * (_rank(var) or 0)))
            self._note(var, f"constvar[{i}] {fmt_aval(var.aval)} (baked "
                            "trace constant, replicated)", path)

        for i, eqn in enumerate(jaxpr.eqns):
            here = f"{path}eqns[{i}]"
            self._eqn(eqn, here)
        return [self.get(v) if not is_literal(v) else _Spec(())
                for v in jaxpr.outvars]

    def _invar_label(self, var, i):
        st = self.env.get(id(var))
        if isinstance(st, _Spec) and st.declared:
            return f"invar[{i}] {fmt_aval(var.aval)} declared {st!r}"
        return f"invar[{i}] {fmt_aval(var.aval)}"

    def _join(self, states):
        """Combine same-shape operand states: any sharded wins, agreeing
        specs pass through, disagreement degrades to sharded-unknown."""
        specs = [s for s in states if isinstance(s, _Spec)]
        if any(s is _SHARDED for s in states):
            return _SHARDED
        non_repl = [s for s in specs if not s.replicated]
        if non_repl:
            dims = non_repl[0].dims
            return (non_repl[0] if all(s.dims == dims for s in non_repl)
                    else _SHARDED)
        if specs and len(specs) == len(states):
            return _Spec(specs[0].dims)
        return _UNKNOWN

    def _eqn(self, eqn, here):
        p = eqn.primitive.name
        invars = [v for v in eqn.invars]
        in_states = [self.get(v) for v in invars]

        if p == "sharding_constraint" or p == "with_sharding_constraint":
            new = eqn.params.get("sharding")
            rank = _rank(eqn.outvars[0]) or 0
            spec = (_norm_spec(new, rank, declared=True)
                    if new is not None else None)
            old = in_states[0] if in_states else _UNKNOWN
            if (spec is not None and isinstance(old, _Spec)
                    and not old.replicated and old.dims != spec.dims
                    and _size(eqn.outvars[0]) >= self.large_threshold):
                self.churn.append((here, old, spec, eqn.outvars[0]))
            for v in invars:
                self.constrained.add(id(v))
            for ov in eqn.outvars:
                self.constrained.add(id(ov))
                self.set(ov, spec if spec is not None else old)
                self._note(ov, f"sharding_constraint {spec!r}", here,
                           invars[0] if invars else None)
            return

        if p in _JIT_PRIMITIVES:
            self._pjit(eqn, here, in_states)
            return

        if p == "shard_map":
            # the body is manual — per-shard shapes, explicit collectives;
            # replication analysis restarts at the outputs via out_names
            out_names = eqn.params.get("out_names", ())
            for ov, names in zip(eqn.outvars, out_names):
                rank = _rank(ov) or 0
                self.set(ov, _norm_spec(dict(names), rank, declared=True))
                self._note(ov, f"shard_map out {dict(names)}", here)
            return

        subs = [s for _, s in sub_jaxprs(eqn)]
        if subs:
            # scan/while/cond/custom-vjp bodies: taint rule only
            st = self._join(in_states) if in_states else _UNKNOWN
            for ov in eqn.outvars:
                rank = _rank(ov)
                if isinstance(st, _Spec) and st.replicated \
                        and rank is not None:
                    self.set(ov, _Spec((None,) * rank))
                else:
                    self.set(ov, st if st is _SHARDED else _UNKNOWN)
                self._note(ov, f"{p}", here, invars[0] if invars else None)
                self._maybe_flag(ov, here)
            return

        for ov in eqn.outvars:
            rank = _rank(ov)
            if rank is None:
                continue
            st = self._propagate(p, eqn, invars, in_states, ov)
            self.set(ov, st)
            parent = invars[0] if invars else None
            if p in _MATERIALIZERS and all(
                    not isinstance(s, _Spec) or s.replicated or
                    _size(v) == 0
                    for s, v in zip(in_states, invars)):
                self._note(ov, f"{p} {fmt_aval(ov.aval)} (materialized "
                                "replicated in-graph)", here, None)
            else:
                self._note(ov, p, here, parent)
            self._maybe_flag(ov, here)

    def _propagate(self, p, eqn, invars, in_states, ov):
        rank = _rank(ov)
        out_shape = tuple(ov.aval.shape)
        if p in _MATERIALIZERS:
            if p == "broadcast_in_dim" and invars:
                src = in_states[0]
                if src is _SHARDED:
                    return _SHARDED
                if isinstance(src, _Spec):
                    dims = [None] * rank
                    bdims = eqn.params.get("broadcast_dimensions", ())
                    for sdim, odim in enumerate(bdims):
                        if sdim < len(src.dims):
                            dims[odim] = src.dims[sdim]
                    return _Spec(dims)
                return _UNKNOWN
            return _Spec((None,) * rank)   # iota: replicated by birth
        if p == "transpose":
            src = in_states[0]
            if isinstance(src, _Spec):
                perm = eqn.params.get("permutation", ())
                return _Spec(tuple(src.dims[d] if d < len(src.dims)
                                   else None for d in perm))
            return src
        if p in _REDUCE_SHAPED:
            src = in_states[0]
            if isinstance(src, _Spec):
                axes = set(eqn.params.get("axes", ()))
                return _Spec(tuple(d for i, d in enumerate(src.dims)
                                   if i not in axes))
            return src
        # shape-preserving ops (elementwise, converts, select, ...): join
        same = [s for s, v in zip(in_states, invars)
                if getattr(getattr(v, "aval", None), "shape", None)
                == out_shape]
        if same:
            return self._join(same + [
                s for s, v in zip(in_states, invars)
                if _size(v) <= 1])
        # layout-changing op (dot_general, reshape, gather, concat, ...):
        # replicated-only inputs stay replicated, sharded inputs taint
        if in_states and all(
                isinstance(s, _Spec) and s.replicated for s in in_states):
            return _Spec((None,) * rank)
        if any(s is _SHARDED or (isinstance(s, _Spec) and not s.replicated)
               for s in in_states):
            return _SHARDED
        return _UNKNOWN

    def _pjit(self, eqn, here, in_states):
        inner = eqn.params["jaxpr"]
        inner_jaxpr = inner.jaxpr if hasattr(inner, "jaxpr") else inner
        declared = eqn.params.get("in_shardings", ())
        seeds = []
        for k, var in enumerate(inner_jaxpr.invars):
            st = in_states[k] if k < len(in_states) else None
            sh = declared[k] if k < len(declared) else None
            if _is_named_sharding(sh):
                st = _norm_spec(sh, _rank(var) or 0, declared=True)
            seeds.append(st if st not in (_UNKNOWN,) else None)
        tag = eqn.params.get("name", "")
        label = f"pjit:{tag}" if tag else "pjit"
        out_states = self.run(inner_jaxpr, seeds, f"{here}/{label}/")
        out_decl = eqn.params.get("out_shardings", ())
        for k, ov in enumerate(eqn.outvars):
            st = out_states[k] if k < len(out_states) else _UNKNOWN
            sh = out_decl[k] if k < len(out_decl) else None
            if _is_named_sharding(sh):
                st = _norm_spec(sh, _rank(ov) or 0, declared=True)
            self.set(ov, st)
            self._note(ov, label, here,
                       inner_jaxpr.outvars[k]
                       if k < len(inner_jaxpr.outvars) and
                       hasattr(inner_jaxpr.outvars[k], "aval") else None)

    def _maybe_flag(self, ov, here):
        """Record a replication offender: large, provably replicated, and
        rooted at an in-graph materializer/constant (not a declared
        input — dp-replicated params are intentional by declaration)."""
        st = self.env.get(id(ov))
        if not isinstance(st, _Spec) or not st.replicated:
            return
        if _size(ov) < self.large_threshold:
            return
        root = self._root_kind(ov)
        if root is not None:
            self.replicated_offenders.append((here, ov, root))

    def _root_kind(self, var, max_hops=16):
        """'materialized'/'const' when the provenance root is an in-graph
        materializer or baked constant; None when it reaches a declared
        input (intentional replication)."""
        vid = id(var)
        for _ in range(max_hops):
            entry = self.origin.get(vid)
            if entry is None:
                return None
            label, _, parent = entry
            if parent is None:
                if label.startswith("invar["):
                    return None
                if "constvar" in label:
                    return "const"
                if "materialized" in label:
                    return "materialized"
                return None
            vid = parent
        return None


def _mesh_size(mesh):
    try:
        return int(np.prod(list(mesh.shape.values())))
    except Exception:
        return 1


def _flow_for(ctx):
    """One propagation per AnalysisContext, memoized on the ctx object
    (two passes share it)."""
    flow = getattr(ctx, "_sharding_flow", None)
    if flow is None:
        flow = _SpecFlow(ctx.large_threshold)
        seeds = None
        in_specs = getattr(ctx, "in_specs", None)
        if in_specs is not None:
            seeds = [None if s is None else
                     _norm_spec(s, _rank(v) or 0, declared=True)
                     for s, v in zip(in_specs, ctx.jaxpr.invars)]
        flow.run(ctx.jaxpr, seeds)
        ctx._sharding_flow = flow
    return flow


@register_pass("implicit-replication", severity="warning")
def implicit_replication(ctx):
    """Large tensors MATERIALIZED replicated in-graph under a multi-device
    mesh, with the provenance chain to the value that introduced the
    replication. Upgrades the size-threshold-only unsharded-large-tensor
    pass: declared-replicated inputs (and everything derived from sharded
    data) never false-positive."""
    if ctx.mesh is None or _mesh_size(ctx.mesh) <= 1:
        return []
    flow = _flow_for(ctx)
    out = []
    # a later sharding_constraint covers an earlier producer: filter at
    # report time, after the whole walk populated `constrained`
    offenders = [(p, v, r) for p, v, r in flow.replicated_offenders
                 if id(v) not in flow.constrained]
    for path, var, root in offenders[:8]:
        out.append(implicit_replication.finding(
            f"{fmt_aval(var.aval)} ({_size(var)} elems) is materialized "
            f"replicated on every device of the {dict(ctx.mesh.shape)} "
            f"mesh ({'baked trace constant' if root == 'const' else 'built in-graph from iota/broadcast'}, "
            "no declared sharding covers it) — provenance: "
            f"{flow.chain(var)}", where=path))
    extra = len(offenders) - 8
    if extra > 0:
        out.append(implicit_replication.finding(
            f"... and {extra} more implicitly-replicated large "
            "intermediate(s)", where="(summary)"))
    return out


@register_pass("resharding-churn", severity="warning")
def resharding_churn(ctx):
    """A value constrained to one spec then re-constrained to another:
    the partitioner lowers the transition as all-gather + re-slice on
    what is, in every analyzed program, the train/decode hot path."""
    if ctx.mesh is None:
        return []
    flow = _flow_for(ctx)
    out = []
    for path, old, new, var in flow.churn[:8]:
        out.append(resharding_churn.finding(
            f"{fmt_aval(var.aval)} re-constrained {old!r} -> {new!r}: "
            "the spec change implies an all-gather + re-slice every "
            f"step — provenance: {flow.chain(var)}", where=path))
    return out


# ---------------------------------------------------------------------------
# bundled-program targets (tools/graph_lint.py --sharding)
# ---------------------------------------------------------------------------

SHARDING_TARGETS = ("gpt_train", "bert_train", "ernie_train", "serving",
                    "dp8_quantized", "pipeline", "disagg", "mpmd_train")

#: analysis threshold for the bundled CPU-shrunk programs. 1<<17 keeps
#: the CI-size traces quiet (a [16, 4, 16, 16] attention mask is 16k
#: elements — replicated, true, and fused away by XLA at this size)
#: while the same pass at production shapes flags the [b, h, s, s] mask
#: class flash attention exists to avoid. Planted unit tests exercise
#: the machinery with explicit low thresholds.
TARGET_THRESHOLD = 1 << 17


def _tiny_train_setup(model_name, dp):
    import jax

    import paddle_tpu as paddle
    from ..distributed.mesh import build_mesh
    from ..distributed.spmd import SpmdTrainer
    from ..models import (BertConfig, BertForPretraining, BertPretrainLoss,
                          ErnieConfig, ErnieModel, ErniePretrainLoss,
                          GPTConfig, GPTForCausalLM, GPTPretrainLoss)

    dims = dict(vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
                dropout=0.0)
    paddle.seed(0)
    rng = np.random.RandomState(0)
    b, s = 2 * dp, 16
    if model_name == "gpt":
        model = GPTForCausalLM(GPTConfig(max_seq_len=64, **dims))
        loss = GPTPretrainLoss()
        batch = (rng.randint(0, 256, (b, s)).astype(np.int32),
                 rng.randint(0, 256, (b, s)).astype(np.int32))
    elif model_name == "bert":
        model = BertForPretraining(BertConfig(max_position=64,
                                              intermediate_size=256,
                                              **dims))
        loss = BertPretrainLoss()
        batch = (rng.randint(0, 256, (b, s)).astype(np.int32),
                 np.zeros((b, s), np.int32),
                 rng.randint(0, 256, (b, s)).astype(np.int32))
    elif model_name == "ernie":
        class _ErnieWithHead(paddle.nn.Layer):
            """seq output -> MLM logits + pooled NSP head (the pretrain
            program shape; MLM-only labels through the flat batch)."""

            def __init__(self, cfg):
                super().__init__()
                self.ernie = ErnieModel(cfg)
                self.mlm = paddle.nn.Linear(cfg.hidden_size,
                                            cfg.vocab_size)
                self.nsp = paddle.nn.Linear(cfg.hidden_size, 2)

            def forward(self, ids):
                seq, pooled = self.ernie(ids)
                return self.mlm(seq), self.nsp(pooled)

        model = _ErnieWithHead(ErnieConfig(max_position=64,
                                           intermediate_size=256, **dims))
        loss = ErniePretrainLoss()
        batch = (rng.randint(0, 256, (b, s)).astype(np.int32),
                 rng.randint(0, 256, (b, s)).astype(np.int32))
    else:
        raise ValueError(model_name)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    mesh = build_mesh((dp,), ("dp",), devices=jax.devices()[:dp])
    trainer = SpmdTrainer(model, opt, loss_fn=loss, mesh=mesh)
    return trainer, tuple(batch), mesh


def _donated_of(closed):
    """The pjit-declared donation set of a traced jitted program — the
    donation-miss pass's ground truth."""
    donated = set()
    for eqn in closed.jaxpr.eqns:
        if eqn.primitive.name in _JIT_PRIMITIVES:
            for i, d in enumerate(eqn.params.get("donated_invars", ())):
                if d:
                    donated.add(i)
    return donated


def _trace_trainer_step(trainer, batch_arrays):
    """ClosedJaxpr of the trainer's jitted step (trace only, no compile),
    plus the pjit-declared donation set for the donation-miss pass."""
    import jax
    import jax.numpy as jnp

    from ..core.generator import default_generator

    step = trainer._build(list(batch_arrays))
    lr = jnp.asarray(trainer.optimizer.get_lr(), dtype=jnp.float32)
    key = default_generator().fold_in(0)
    closed = jax.make_jaxpr(step)(trainer.params, trainer.opt_state,
                                  trainer.buffers, lr, key, *batch_arrays)
    return closed, _donated_of(closed)


def _dp(n_want):
    import jax

    return max(1, min(n_want, len(jax.devices())))


def _target_train(model_name):
    trainer, batch, mesh = _tiny_train_setup(model_name, _dp(8))
    closed, donated = _trace_trainer_step(trainer, batch)
    return closed, dict(mesh=mesh, donated=donated)


def _target_dp8_quantized():
    from .. import flags as _flags

    old = {"quantized_allreduce": _flags.get_flag("quantized_allreduce",
                                                  False)}
    _flags.set_flags({"quantized_allreduce": True})
    try:
        trainer, batch, mesh = _tiny_train_setup("gpt", _dp(8))
        closed, donated = _trace_trainer_step(trainer, batch)
    finally:
        _flags.set_flags(old)
    return closed, dict(mesh=mesh, donated=donated)


def _target_pipeline():
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from ..distributed.mesh import build_mesh
    from ..distributed.pipeline import PipelineTrainer
    from ..models import GPTConfig, GPTForCausalLM

    n_pp = _dp(4)
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=n_pp,
                    num_heads=4, max_seq_len=64, dropout=0.0)
    model = GPTForCausalLM(cfg)
    pre, stages, post = model.pipeline_split(n_pp)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    mesh = build_mesh((n_pp,), ("pp",), devices=jax.devices()[:n_pp])
    tr = PipelineTrainer(pre, stages, post, opt, mesh=mesh,
                         n_micro=n_pp, schedule_mode="F-then-B")
    rng = np.random.RandomState(0)
    b, s = n_pp * 2, 16
    x = rng.randint(0, 256, (b, s)).astype(np.int32)
    y = rng.randint(0, 256, (b, s)).astype(np.int32)
    mb = b // tr.n_micro
    x_micro = jnp.asarray(x).reshape((tr.n_micro, mb, s))
    y_micro = jnp.asarray(y).reshape((tr.n_micro, mb, s))
    step = tr._build()
    lr = jnp.asarray(tr.optimizer.get_lr(), dtype=jnp.float32)
    closed = jax.make_jaxpr(step)(tr.params, tr.opt_state, tr.frozen, lr,
                                  x_micro, y_micro)
    return closed, dict(mesh=mesh, donated=_donated_of(closed))


def _target_mpmd():
    """The FLAGS_mpmd armed pipeline (distributed/stage.py): per-stage
    programs on their own mesh slices. The traced program is the fused
    last stage (loss + grads — the densest of the per-stage programs);
    its mesh is that stage's OWN mesh, not the trainer's."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from .. import flags as _flags
    from ..distributed.mesh import build_mesh
    from ..distributed.pipeline import PipelineTrainer
    from ..models import GPTConfig, GPTForCausalLM

    n_pp = max(2, _dp(2))
    old = {"mpmd": _flags.get_flag("mpmd", False)}
    _flags.set_flags({"mpmd": True})
    try:
        paddle.seed(0)
        cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=n_pp,
                        num_heads=4, max_seq_len=64, dropout=0.0)
        model = GPTForCausalLM(cfg)
        pre, stages, post = model.pipeline_split(n_pp)
        opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=model.parameters())
        mesh = build_mesh((n_pp,), ("pp",), devices=jax.devices()[:n_pp])
        tr = PipelineTrainer(pre, stages, post, opt, mesh=mesh,
                             n_micro=n_pp, schedule_mode="F-then-B")
        rng = np.random.RandomState(0)
        b, s = n_pp * 2, 16
        mb = b // tr.n_micro
        x_micro = jnp.asarray(
            rng.randint(0, 256, (b, s)).astype(np.int32)).reshape(
                (tr.n_micro, mb, s))
        y_micro = jnp.asarray(
            rng.randint(0, 256, (b, s)).astype(np.int32)).reshape(
                (tr.n_micro, mb, s))
        runner = tr._mpmd_runner
        closed = runner.lint_jaxpr(x_micro, y_micro)
    finally:
        _flags.set_flags(old)
    return closed, dict(mesh=runner.stage_meshes[-1], donated=set())


def _target_serving(large_threshold=TARGET_THRESHOLD):
    from .targets import analyze_serving_decode

    return analyze_serving_decode(large_threshold=large_threshold)


def _target_disagg():
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from ..models import GPTConfig, GPTForCausalLM
    from ..serving.disagg import PrefillWorker

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                    num_heads=4, max_seq_len=64, dropout=0.0)
    m = GPTForCausalLM(cfg)
    m.eval()
    worker = PrefillWorker(m, prompt_buckets=(32,))
    padded = jnp.zeros((1, 32), jnp.int32)
    closed = jax.make_jaxpr(worker._prefill._jit)(
        worker._params, padded, np.int32(7))
    return closed, dict(mesh=None)


def flow_summary(closed, mesh=None, large_threshold=TARGET_THRESHOLD):
    """Machine-readable communication summary of one traced program —
    the dict counterpart of the finding-producing passes, consumed by
    the plan-search cost model (analysis/cost_model.py).

    Collective payload bytes are summed per family with the per-device
    ring wire factor applied — ``2 (n-1)/n`` for reduce (psum and kin),
    ``(n-1)/n`` for exchange (all_gather/all_to_all/scatter), ``1`` for
    permute — where ``n`` is the product of the collective's axis sizes
    resolved against the enclosing shard_map's mesh (falling back to
    `mesh`); unresolvable axes get factor 1. Resharding-churn bytes sum
    the payloads of every :class:`_SpecFlow` churn event (a layout
    change re-materializes the value once on the wire). Trace-only,
    like everything else here."""
    jaxpr = closed.jaxpr if hasattr(closed, "jaxpr") else closed
    from .jaxpr_utils import is_literal

    fam_bytes = {"reduce": 0.0, "exchange": 0.0, "permute": 0.0}
    fam_counts = {"reduce": 0, "exchange": 0, "permute": 0}
    for eqn, path, env, sm_mesh in _iter_with_axes(jaxpr):
        p = eqn.primitive.name
        if p not in COLLECTIVE_PRIMS:
            continue
        fam = ("reduce" if p in REDUCE_PRIMS
               else "exchange" if p in EXCHANGE_PRIMS else "permute")
        payload = sum(
            _size(v) * getattr(getattr(v.aval, "dtype", None),
                               "itemsize", 4)
            for v in eqn.invars if not is_literal(v))
        n = 1
        for a in _axes_of(eqn):
            sz = _axis_size(a, sm_mesh, mesh)
            if sz:
                n *= int(sz)
        if fam == "reduce":
            factor = 2.0 * (n - 1) / n if n > 1 else 0.0
        elif fam == "exchange":
            factor = (n - 1) / n if n > 1 else 0.0
        else:
            factor = 1.0
        fam_bytes[fam] += payload * factor
        fam_counts[fam] += 1
    flow = _SpecFlow(large_threshold)
    flow.run(jaxpr)
    churn_bytes = sum(
        _size(var) * getattr(getattr(var.aval, "dtype", None),
                             "itemsize", 4)
        for _, _, _, var in flow.churn)
    return {
        "collective_bytes": fam_bytes,
        "collective_counts": fam_counts,
        "collective_bytes_total": sum(fam_bytes.values()),
        "resharding_churn_bytes": churn_bytes,
        "resharding_events": len(flow.churn),
    }


def _target_builders():
    """target name -> () -> (ClosedJaxpr, run_passes kwargs), for every
    jaxpr-producing sharding target (serving builds its own report)."""
    return {
        "gpt_train": lambda: _target_train("gpt"),
        "bert_train": lambda: _target_train("bert"),
        "ernie_train": lambda: _target_train("ernie"),
        "dp8_quantized": _target_dp8_quantized,
        "pipeline": _target_pipeline,
        "disagg": _target_disagg,
        "mpmd_train": _target_mpmd,
    }


def sharding_summaries(targets=None, large_threshold=TARGET_THRESHOLD):
    """{target: flow_summary dict} over the bundled distributed
    programs — per-program resharding-churn bytes and collective byte
    totals as plain data (the findings stay with sharding_reports).
    `targets` picks a subset; ``serving`` has no single jaxpr and is
    excluded from the default set."""
    builders = _target_builders()
    picked = tuple(targets) if targets is not None \
        else tuple(builders)
    unknown = [t for t in picked if t not in builders]
    if unknown:
        raise ValueError(f"unknown sharding summary target(s) {unknown}; "
                         f"choose from {sorted(builders)}")
    out = {}
    for name in picked:
        closed, kw = builders[name]()
        out[name] = flow_summary(closed, mesh=kw.get("mesh"),
                                 large_threshold=large_threshold)
    return out


def sharding_reports(targets=None, large_threshold=TARGET_THRESHOLD):
    """{target: AnalysisReport} for the bundled distributed programs,
    traced under their real meshes and run through the full pass battery
    (trace only — nothing compiles or executes)."""
    from .registry import run_passes
    from .targets import _trace_with_warnings

    picked = tuple(targets) if targets is not None else SHARDING_TARGETS
    unknown = [t for t in picked if t not in SHARDING_TARGETS]
    if unknown:
        raise ValueError(f"unknown sharding target(s) {unknown}; "
                         f"choose from {SHARDING_TARGETS}")
    builders = _target_builders()
    reports = {}
    for name in picked:
        if name == "serving":
            reports[name] = _target_serving(large_threshold)
            continue
        (closed, kw), warn_findings = _trace_with_warnings(builders[name])
        rep = run_passes(closed, name=name,
                         large_threshold=large_threshold, **kw)
        rep.extend(warn_findings)
        reports[name] = rep.sort()
    return reports
