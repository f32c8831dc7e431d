"""Auto-parallel plan search: enumerate, verify, score, emit.

ROADMAP item 4, the Alpa/MPMD line (arXiv:2412.14374) redone as *search
over verified static analyses* instead of live trial runs. For one
bundled tiny model the enumerator walks the dp/mp/pp/n_micro/compress
space, every candidate is **verified by the existing analyzers** —
rejection always names the failing pass, never crashes — survivors are
scored by :class:`cost_model.CostModel`, and the winner is emitted as a
ready-to-run ``SpmdTrainer`` / stage-graph config
(:func:`spmd.spmd_trainer_from_plan` /
:func:`stage.pipeline_trainer_from_plan` realize it).

The verification battery, per candidate:

1. **sharding-flow** — the plan's axis program (a shard_map psum over
   every plan axis, traced on an ``AbstractMesh`` with the PLAN's axis
   sizes; nothing allocates devices) runs through the full registered
   pass battery with the *deployment* mesh the host can actually build.
   A plan asking for more devices than exist is rejected by the real
   ``collective-axis-mismatch`` pass — same finding text a hand-built
   bad mesh gets. Valid plans additionally get their trainer-step
   *program class* traced (memoized per (model, dp, quantized) /
   (model, pp)) and the battery run on the real jaxpr; its
   :func:`sharding_flow.flow_summary` supplies measured collective
   bytes to the cost model.
2. **pallas VMEM** — the per-stage boundary-activation working set goes
   through :func:`pallas_audit.audit_tile` (the registered kernels'
   16 MiB double-buffered accounting); over-budget stages are rejected
   by ``kernel-vmem-over-budget``.
3. **handoff schema** — the stage-edge payload the plan would put on
   the wire is checked against the AST-extracted ``HANDOFF_SCHEMA`` /
   ``HANDOFF_SCHEMA_GRAD`` declarations via
   :func:`handoff_schema.validate`; a mismatch (e.g. asking to quantize
   the always-dense grad edge) is rejected as ``plan-handoff-mismatch``
   carrying the validator's edge/leaf/field message.
4. **HBM** — the cost model's per-device memory term against the
   budget (``plan-hbm-over-budget``).

CLI: ``python tools/plan_search.py --model gpt --top 5 --explain``;
``tools/graph_lint.py --plan`` folds the same reports into ``--all``.
Manifest-lazy like cost_model — a plain trainer never imports this.
"""
import numpy as np

from .registry import AnalysisReport, Finding, run_passes
from . import cost_model as _cm

__all__ = ["RULES", "SearchResult", "PLAN_MODELS", "enumerate_plans",
           "verify_plan", "search", "emit", "default_plan",
           "realize_trainer", "clear_cache"]

RULES = {
    "plan-space-empty": "error",
    "plan-handoff-mismatch": "error",
    "plan-ranked": "info",
    "plan-rejected": "info",
}

#: models the planner knows how to profile (the sharding targets' tiny
#: builders); pipeline plans additionally need model.pipeline_split
PLAN_MODELS = ("gpt", "bert", "ernie")

#: memoized trainer-step traces: key -> (AnalysisReport, flow_summary)
_TRACE_CACHE = {}
_PROFILE_CACHE = {}


def clear_cache():
    _TRACE_CACHE.clear()
    _PROFILE_CACHE.clear()


def _profile(model):
    if model not in _PROFILE_CACHE:
        if model not in PLAN_MODELS:
            raise ValueError(f"unknown model {model!r}; "
                             f"choose from {PLAN_MODELS}")
        _PROFILE_CACHE[model] = _cm.ModelProfile.trace(model)
    return _PROFILE_CACHE[model]


# ---------------------------------------------------------------------------
# enumeration
# ---------------------------------------------------------------------------


def enumerate_plans(profile, devices):
    """Every candidate the verifier will judge. Deliberately generous —
    infeasible combinations (mp without split layers, pp beyond the
    layer count, axes beyond the device pool) are enumerated anyway so
    their rejection is an ANALYZER finding, not a silent gap."""
    plans = []
    g = _cm.GLOBAL_BATCH
    dps = [d for d in (1, 2, 4, 8, 16) if d <= devices and g % d == 0]
    for dp in dps:
        plans.append(_cm.Plan(dp=dp))
        if dp > 1:
            plans.append(_cm.Plan(dp=dp, quantized_allreduce=True))
    for dp, mp in ((1, 2), (2, 2), (4, 2), (1, 4)):
        if dp * mp <= max(devices, 2) and g % dp == 0:
            plans.append(_cm.Plan(dp=dp, mp=mp))
    for pp in (2, 4):
        cuts = _equal_cuts(profile.n_layers, pp)
        for n_micro in (pp, 2 * pp, 4 * pp):
            if g % n_micro:
                continue
            for comp in (None, 8):
                plans.append(_cm.Plan(pp=pp, n_micro=n_micro,
                                      edge_compress=comp,
                                      stage_layers=cuts))
    return plans


def _equal_cuts(n_layers, pp):
    if pp <= 0 or n_layers % pp:
        return None
    per = n_layers // pp
    return [list(range(i * per, (i + 1) * per)) for i in range(pp)]


def default_plan(profile, devices):
    """The hand-written default every bundled test/doc uses: plain data
    parallel over the whole device pool, no compression."""
    g = _cm.GLOBAL_BATCH
    dp = max(d for d in (1, 2, 4, 8, 16)
             if d <= devices and g % d == 0)
    return _cm.Plan(dp=dp)


# ---------------------------------------------------------------------------
# verification (every rejection names the analyzer pass that fired)
# ---------------------------------------------------------------------------


class _DeployMesh:
    """Duck-typed deployment mesh (axis_names + shape dict is all the
    sharding-flow passes read): the best factorization the host's
    device pool can offer for the plan's axes — an axis the pool cannot
    fill gets what is left, and the collective pass reports the
    mismatch against the plan's traced sizes."""

    def __init__(self, names, wanted, devices):
        self.axis_names = tuple(names)
        shape = {}
        remaining = max(1, int(devices))
        for n, want in zip(names, wanted):
            got = want if want <= remaining else max(1, remaining)
            while remaining % got:
                got -= 1
            shape[n] = got
            remaining //= got
        self.shape = shape

    def __repr__(self):
        return f"_DeployMesh({self.shape})"


def _axis_program_report(plan, devices):
    """Trace the plan's axis program on an AbstractMesh with the PLAN's
    sizes and run the full pass battery against the deployment mesh."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import AbstractMesh, PartitionSpec as P

    names, sizes = plan.mesh_axes
    amesh = AbstractMesh(tuple(sizes), tuple(names))

    def axis_prog(x):
        for a in names:
            x = jax.lax.psum(x, a)
        return x

    f = jax.shard_map(axis_prog, mesh=amesh, in_specs=P(), out_specs=P())
    closed = jax.make_jaxpr(f)(jnp.zeros((8, 8), jnp.float32))
    deploy = _DeployMesh(names, sizes, devices)
    return run_passes(closed, name=f"plan:{plan.describe()}",
                      mesh=deploy,
                      large_threshold=_sf().TARGET_THRESHOLD)


def _sf():
    from . import sharding_flow

    return sharding_flow


def _class_key(plan, model):
    if plan.pp > 1:
        return (model, "pp", plan.pp, plan.n_micro)
    if plan.quantized_allreduce:
        return (model, "dp_q", plan.dp)
    return (model, "dp_dense")


def _trace_class(plan, model, devices):
    """(AnalysisReport, flow_summary) of the plan's trainer-step
    program class, traced on the real (virtual-CPU) device pool and
    memoized. Dense-dp plans share one trace at max dp: the program is
    identical modulo batch, and its jaxpr carries no explicit
    collectives to measure anyway."""
    key = _class_key(plan, model)
    if key in _TRACE_CACHE:
        return _TRACE_CACHE[key]
    import jax

    sf = _sf()
    ndev = min(devices, len(jax.devices()))
    if plan.pp > 1:
        closed, kw = _trace_pipeline_class(model, plan, ndev)
    else:
        dp = plan.dp if plan.quantized_allreduce else \
            max(d for d in (1, 2, 4, 8) if d <= ndev)
        dp = min(dp, ndev)
        if plan.quantized_allreduce:
            from .. import flags as _flags

            old = {"quantized_allreduce":
                   _flags.get_flag("quantized_allreduce", False)}
            _flags.set_flags({"quantized_allreduce": True})
            try:
                trainer, batch, mesh = sf._tiny_train_setup(model, dp)
                closed, donated = sf._trace_trainer_step(trainer, batch)
            finally:
                _flags.set_flags(old)
        else:
            trainer, batch, mesh = sf._tiny_train_setup(model, dp)
            closed, donated = sf._trace_trainer_step(trainer, batch)
        kw = dict(mesh=mesh, donated=donated)
    rep = run_passes(closed, name=f"plan_class:{'/'.join(map(str, key))}",
                     large_threshold=sf.TARGET_THRESHOLD, **kw)
    flow = sf.flow_summary(closed, mesh=kw.get("mesh"),
                           large_threshold=sf.TARGET_THRESHOLD)
    _TRACE_CACHE[key] = (rep, flow)
    return _TRACE_CACHE[key]


def _trace_pipeline_class(model, plan, ndev):
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from ..distributed.mesh import build_mesh
    from ..distributed.pipeline import PipelineTrainer
    from ..models import GPTConfig, GPTForCausalLM

    sf = _sf()
    if model != "gpt":
        raise ValueError(f"{model} has no pipeline_split")
    n_pp = min(plan.pp, ndev)
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=256, hidden_size=64,
                    num_layers=max(n_pp, 2), num_heads=4,
                    max_seq_len=64, dropout=0.0)
    m = GPTForCausalLM(cfg)
    pre, stages, post = m.pipeline_split(n_pp)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=m.parameters())
    mesh = build_mesh((n_pp,), ("pp",), devices=jax.devices()[:n_pp])
    tr = PipelineTrainer(pre, stages, post, opt, mesh=mesh,
                         n_micro=plan.n_micro, schedule_mode="F-then-B")
    rng = np.random.RandomState(0)
    b, s = _cm.GLOBAL_BATCH, _cm.SEQ_LEN
    mb = b // tr.n_micro
    x = jnp.asarray(rng.randint(0, 256, (b, s)).astype(np.int32))
    y = jnp.asarray(rng.randint(0, 256, (b, s)).astype(np.int32))
    x_micro = x.reshape((tr.n_micro, mb, s))
    y_micro = y.reshape((tr.n_micro, mb, s))
    step = tr._build()
    lr = jnp.asarray(tr.optimizer.get_lr(), dtype=jnp.float32)
    closed = jax.make_jaxpr(step)(tr.params, tr.opt_state, tr.frozen,
                                  lr, x_micro, y_micro)
    return closed, dict(mesh=mesh, donated=sf._donated_of(closed))


def _edge_schema_findings(plan, profile):
    """Check the stage-edge payload this plan puts on the wire against
    the declared (AST-extracted) schemas — the real validator, wrapped
    so a mismatch is a named finding, not a crash."""
    if plan.pp <= 1:
        return []
    import jax

    from . import handoff_schema as hs

    mb = _cm.GLOBAL_BATCH // plan.n_micro
    dims = {"mb": mb, "t": profile.seq, "d": profile.hidden}
    out = []
    for edge, compress in (("mpmd_activation", plan.edge_compress),
                           ("mpmd_grad",
                            8 if plan.compress_grad_edge else None)):
        relpath, attr = hs.EDGES[edge]
        schema = hs.extract_declaration(relpath, attr)
        leaf = next(iter(schema["payload"]))
        shape = (mb, profile.seq, profile.hidden)
        if compress:
            payload = {leaf: (
                jax.ShapeDtypeStruct(shape, np.int8),
                jax.ShapeDtypeStruct(shape[:-1] + (1,), np.float32))}
            dt = None     # int8 wire values: skip the $act binding
        else:
            payload = {leaf: jax.ShapeDtypeStruct(shape, np.float32)}
            dt = {"act": "float32"}
        try:
            hs.validate(schema, payload, dims=dict(dims), dtypes=dt)
        except hs.HandoffMismatch as e:
            out.append(Finding(
                "plan-handoff-mismatch", "error",
                f"stage-edge payload rejected by the handoff-schema "
                f"validator: {e}", where=plan.describe()))
    return out


def _vmem_findings(plan, profile):
    """The per-stage boundary-activation working set through the Pallas
    VMEM accounting (pp plans; dp plans stream no stage tiles)."""
    if plan.pp <= 1:
        return []
    from . import pallas_audit

    mb = _cm.GLOBAL_BATCH // plan.n_micro
    block = (mb * profile.seq, profile.hidden)
    return [f for f in pallas_audit.audit_tile(
        f"plan.stage_act[{plan.describe()}]", block)
        if f.severity == "error"]


def verify_plan(plan, profile, devices=8, model=None, cm=None,
                trace_classes=True):
    """(error_findings, flow_summary|None) — empty findings = valid.

    Composes the existing analyzers; every rejection is a Finding whose
    ``pass_name`` names the analyzer that fired. Never raises on a bad
    plan."""
    cm = cm or _cm.CostModel()
    model = model or profile.name
    errs = list(cm.check_config(plan, profile, devices))
    if errs:
        return errs, None
    rep = _axis_program_report(plan, devices)
    errs.extend(rep.errors)
    errs.extend(_vmem_findings(plan, profile))
    errs.extend(_edge_schema_findings(plan, profile))
    errs.extend(cm.check_memory(plan, profile))
    if errs:
        return errs, None
    flow = None
    if trace_classes:
        class_rep, flow = _trace_class(plan, model, devices)
        errs.extend(class_rep.errors)
    return errs, flow


# ---------------------------------------------------------------------------
# search + report
# ---------------------------------------------------------------------------


class SearchResult:
    """ranked: [(Plan, score dict)] best-first; rejected:
    [(Plan, [Finding])]; profile: the traced ModelProfile."""

    def __init__(self, model, profile, ranked, rejected):
        self.model = model
        self.profile = profile
        self.ranked = ranked
        self.rejected = rejected

    @property
    def best(self):
        return self.ranked[0] if self.ranked else None

    def to_report(self, top=None):
        rep = AnalysisReport(name=f"plan_{self.model}")
        if not self.ranked:
            rep.add(Finding(
                "plan-space-empty", "error",
                f"{self.model}: every one of "
                f"{len(self.rejected)} candidate plan(s) was rejected "
                "— no valid partitioning under the given budgets",
                where=self.model))
        for i, (plan, score) in enumerate(
                self.ranked[:top] if top else self.ranked):
            rep.add(Finding(
                "plan-ranked", "info",
                f"#{i + 1} {plan.describe()}: total "
                f"{score['total_s'] * 1e6:.1f}us (compute "
                f"{score['compute_s'] * 1e6:.1f}us, comm "
                f"{score['comm_s'] * 1e6:.1f}us, "
                f"{score['mem_bytes_per_device'] / (1 << 20):.2f} "
                "MiB/device)", where=plan.describe()))
        for plan, errs in self.rejected:
            first = errs[0]
            rep.add(Finding(
                "plan-rejected", "info",
                f"{plan.describe()}: rejected by "
                f"{sorted({e.pass_name for e in errs})} — "
                f"{first.message}", where=plan.describe()))
        return rep.sort()

    def to_dict(self, top=None):
        return {
            "model": self.model,
            "profile": self.profile.to_dict(),
            "ranked": [dict(score, describe=plan.describe())
                       for plan, score in
                       (self.ranked[:top] if top else self.ranked)],
            "rejected": [{"plan": plan.to_dict(),
                          "passes": sorted({e.pass_name for e in errs}),
                          "messages": [e.message for e in errs]}
                         for plan, errs in self.rejected],
        }


def search(model, devices=None, hbm_bytes=None, cm=None):
    """Enumerate, verify, score and rank plans for one bundled model."""
    import jax

    ndev = devices or len(jax.devices())
    profile = _profile(model)
    cm = cm or _cm.CostModel(
        hbm_bytes=hbm_bytes or _cm.DEFAULT_HBM_BYTES)
    ranked, rejected = [], []
    for plan in enumerate_plans(profile, ndev):
        errs, flow = verify_plan(plan, profile, devices=ndev,
                                 model=model, cm=cm)
        if errs:
            rejected.append((plan, errs))
            continue
        ranked.append((plan, cm.score(plan, profile, flow=flow)))
    ranked.sort(key=lambda ps: ps[1]["total_s"])
    return SearchResult(model, profile, ranked, rejected)


# ---------------------------------------------------------------------------
# emission: plan -> ready-to-run config
# ---------------------------------------------------------------------------


def emit(plan, profile):
    """The winning plan as a ready-to-run, JSON-able trainer config.

    ``kind="spmd"`` realizes as a :class:`SpmdTrainer`
    (distributed/spmd.py ``spmd_trainer_from_plan``); ``kind="stage_graph"``
    as a FLAGS_mpmd :class:`PipelineTrainer` whose runner builds the
    typed-edge StageGraph (distributed/stage.py
    ``pipeline_trainer_from_plan``). ``flags`` must be set BEFORE
    construction — both builders check (construction consumes flags)."""
    names, sizes = plan.mesh_axes
    cfg = {
        "model": profile.name,
        "mesh": {"shape": list(sizes), "axes": list(names)},
        "global_batch": _cm.GLOBAL_BATCH,
        "seq_len": profile.seq,
        "plan": plan.to_dict(),
    }
    if plan.pp > 1:
        cfg["kind"] = "stage_graph"
        cfg["flags"] = {"mpmd": True}
        cfg["pipeline"] = {
            "n_micro": plan.n_micro,
            "schedule": "1F1B",
            "stage_layers": plan.stage_layers
            or _equal_cuts(profile.n_layers, plan.pp),
            "compress": plan.edge_compress,
        }
    else:
        cfg["kind"] = "spmd"
        cfg["flags"] = {
            "quantized_allreduce": plan.quantized_allreduce}
        cfg["spmd"] = {"dp_axis": "dp",
                       "tensor_parallel": plan.mp > 1}
    return cfg


def realize_trainer(config):
    """Build the bundled tiny model + optimizer the config's profile
    describes and hand them to the distributed-layer builders. SETS
    ``config["flags"]`` process-wide first (trainer construction
    consumes flags); restore via ``paddle_tpu.flags.set_flags`` when
    done. Returns ``(trainer, batch arrays)`` — the batch is the
    model's pretrain tuple at the plan's global batch size."""
    import paddle_tpu as paddle
    from .. import flags as _flags

    _flags.set_flags(dict(config.get("flags") or {}))
    model_name = config["model"]
    g, s = int(config["global_batch"]), int(config["seq_len"])
    rng = np.random.RandomState(0)
    paddle.seed(0)
    if config["kind"] == "stage_graph":
        from ..distributed.stage import pipeline_trainer_from_plan
        from ..models import GPTConfig, GPTForCausalLM

        cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                        num_heads=4, max_seq_len=64, dropout=0.0)
        m = GPTForCausalLM(cfg)
        opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                     parameters=m.parameters())
        trainer = pipeline_trainer_from_plan(config, m, opt)
    else:
        from ..distributed.spmd import spmd_trainer_from_plan
        from .sharding_flow import _tiny_train_setup

        base, _, _ = _tiny_train_setup(model_name, dp=1)
        trainer = spmd_trainer_from_plan(
            config, base.layer, base.optimizer, loss_fn=base.loss_fn)
    ids = rng.randint(0, 256, (g, s)).astype(np.int32)
    labels = rng.randint(0, 256, (g, s)).astype(np.int32)
    batch = (ids, np.zeros((g, s), np.int32), labels) \
        if model_name == "bert" else (ids, labels)
    return trainer, batch
