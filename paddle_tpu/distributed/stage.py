"""MPMD stage programs: per-stage compiled programs on their own mesh
slices, connected by typed, validated, backpressured transfer edges.

The repo ran two parallel per-stage hand-off systems — the pipeline
trainer's single-program ppermute schedule (distributed/pipeline.py) and
the serving pool's prefill→decode hand-off (serving/disagg.py). This
module is the unification ROADMAP item 3 named, the MPMD
pipeline-parallelism design of arXiv:2412.14374 (PAPERS.md): each stage
is its OWN compiled program on its OWN mesh (unequal per-stage device
counts allowed), and what moves between stages is a typed payload on a
:class:`StageEdge` — declared as a ``HANDOFF_SCHEMA`` literal
(analysis/handoff_schema.py), validated on every ``put``, bounded
(``EdgeFullError`` is the backpressure signal, never silent loss), and
metered at the existing ``kv_handoff_bytes_total`` chokepoint.

Three pieces:

- :class:`StageEdge` — a capacity-bounded FIFO whose payloads are
  validated against a declared schema. ``compress=8`` encodes every
  ``quantizable`` leaf through the EQuARX-style int8 row codec
  (distributed/compress.py, arXiv:2506.17615): wire bytes land in
  ``kv_handoff_bytes_total``, the displaced fp32 bytes in
  ``collective_bytes_saved_total{op="stage_edge"}`` — wire-vs-logical
  accounting identical to the quantized all-reduce's.
- :class:`StageProgram` — one pure function + its mesh, compiled through
  its own ``CachedJit`` (framework/aot.py), labelled with its name.
- :class:`StageGraph` — the MPMD runner: executes a schedule of
  (stage, thunk) ticks, each under a ``stage_step`` span sharing ONE
  trace_id (a ``stage_graph`` root) and a blackbox progress window, so a
  stalled stage is named by the stall sentinel.

:class:`MpmdPipelineRunner` re-bases ``PipelineTrainer`` on the graph
(armed by ``FLAGS_mpmd`` at trainer construction): the 1F1B /
F-then-B / interleaved schedules become tick orderings over per-stage
forward/backward programs whose activations and grads ride typed edges —
no hand-rolled ppermute bookkeeping. ``DisaggregatedPool`` routes its
prefill→decode hand-off over a :class:`StageEdge` validating the SAME
``disagg_kv`` declaration ``ServingEngine.admit_prefilled`` enforces.

This module is manifest-lazy (analysis/import_graph.py): with
``FLAGS_mpmd`` unset nothing imports it and the plain trainer/engine are
byte-identical to the pre-PR build (tests/test_stage_gate.py).
"""
import collections
import contextlib
import time

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import flags as _flags
from .. import monitor as _monitor
from ..monitor import blackbox_lazy as _blackbox  # import-free recorder facade
from .. import trace as _trace
from ..framework import aot as _aot
from ..testing import failpoints as _fp

__all__ = ["StageEdge", "StageProgram", "StageGraph", "EdgeFullError",
           "EdgeEmptyError", "MpmdPipelineRunner", "HANDOFF_SCHEMA",
           "HANDOFF_SCHEMA_GRAD"]

#: The MPMD stage-boundary activation edge (docs/ANALYSIS.md "Declaring a
#: transfer edge"): one micro-batch of transformer-stage activations,
#: carried stage->stage by a typed edge instead of the ppermute ring.
#: ``mb`` binds to the micro-batch rows, ``t``/``d`` to the stage's
#: sequence/feature dims, ``$act`` to the stages' compute dtype. The leaf
#: is ``quantizable``: a ``compress=8`` edge moves the int8
#: (values, scales) pair — per-last-axis-row symmetric, deterministic
#: rounding (compress.quantize_rows) — and the consumer decodes against
#: this same declaration.
HANDOFF_SCHEMA = {
    "edge": "mpmd_activation",
    "producer": "paddle_tpu/distributed/stage.py::StageEdge.put",
    "consumer": "paddle_tpu/distributed/stage.py::StageEdge.get",
    "runtime_checked": True,
    "doc": "one micro-batch of stage activations moving over a typed "
           "MPMD stage edge (forward direction)",
    "payload": {
        "activation": {"shape": ("mb", "t", "d"), "dtype": "$act",
                       "layout": "[micro_batch, seq, features]",
                       "quantizable": True},
    },
}

#: The backward twin: the loss gradient w.r.t. a stage boundary
#: activation. Grad edges stay DENSE even under ``compress=8`` —
#: quantizing the backward signal compounds the forward quantization
#: error, so only the forward direction trades bits for bandwidth.
HANDOFF_SCHEMA_GRAD = {
    "edge": "mpmd_grad",
    "producer": "paddle_tpu/distributed/stage.py::StageEdge.put",
    "consumer": "paddle_tpu/distributed/stage.py::StageEdge.get",
    "runtime_checked": True,
    "doc": "the loss gradient w.r.t. one micro-batch of stage-boundary "
           "activations (backward direction; never quantized)",
    "payload": {
        "grad": {"shape": ("mb", "t", "d"), "dtype": "$act",
                 "layout": "[micro_batch, seq, features]"},
    },
}

#: Same chokepoint counter serving/disagg.py meters (the registry is
#: get-or-create by name, so whichever module loads first owns the help
#: text and both increment ONE family): every edge transfer's WIRE bytes.
_EDGE_BYTES = _monitor.counter(
    "kv_handoff_bytes_total",
    "bytes handed between stage programs (KV rows, activations, grads) "
    "— wire bytes: a compress=8 edge counts the int8+scales payload")

_ELASTIC_RESUME = None  # lazy elastic_resume_total — same family the
#                         ElasticSupervisor (distributed/elastic.py)
#                         counts under; get-or-create by name, so both
#                         call sites increment ONE family


def _note_elastic_resume(reason):
    global _ELASTIC_RESUME
    if not _monitor.is_enabled():
        return
    if _ELASTIC_RESUME is None:
        _ELASTIC_RESUME = _monitor.counter(
            "elastic_resume_total",
            "elastic recoveries by reason (failpoint | nonfinite | crash "
            "from the supervisor's resume path, stage_replace from MPMD "
            "stage rebinding); zero unless FLAGS_elastic machinery "
            "actually recovered something",
            labelnames=("reason",))
    _ELASTIC_RESUME.labels(reason=reason).inc()


def _goodput_bucket(name):
    """Goodput wall-time attribution for edge transfers (FLAGS_goodput,
    ISSUE 20): null context unless the accountant is armed — one flag
    read per put, and the disarmed path never imports monitor/goodput.py
    (manifest-lazy). Edge validate/quantize/enqueue time books as
    ``edge_wait``, pausing the enclosing tick's ``step`` bucket."""
    if not _flags.get_flag("goodput", False):
        return contextlib.nullcontext()
    from ..monitor import goodput as _goodput

    return _goodput.bucket(name)


class EdgeFullError(RuntimeError):
    """A producer ran ahead of its consumer past the edge's capacity —
    the backpressure signal. The payload was NOT enqueued (and not
    dropped elsewhere): the producer must drain the consumer and retry,
    exactly like serving's QueueFullError."""


class EdgeEmptyError(RuntimeError):
    """get() on an edge with nothing in flight."""


def _nbytes(a):
    return int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize if a.shape \
        else np.dtype(a.dtype).itemsize


def _iter_leaves(payload_spec, prefix=""):
    """(dotted-path, leaf-spec) pairs, sorted — mirrors the walk
    analysis/handoff_schema.validate performs."""
    for k in sorted(payload_spec):
        v = payload_spec[k]
        path = f"{prefix}{k}"
        if isinstance(v, dict) and ("shape" in v or "dtype" in v
                                    or "kind" in v):
            yield path, v
        elif isinstance(v, dict):
            yield from _iter_leaves(v, f"{path}.")


def _get_path(tree, path):
    node = tree
    for part in path.split("."):
        node = node[part]
    return node


def _set_path(tree, path, value):
    parts = path.split(".")
    node = tree
    for part in parts[:-1]:
        node = node.setdefault(part, {})
    node[parts[-1]] = value


class StageEdge:
    """A typed, validated, backpressured transfer edge between stage
    programs.

    ``put(payload)`` validates the payload against the declared
    ``schema`` (raising ``HandoffMismatch`` naming the leaf), meters its
    wire bytes into ``kv_handoff_bytes_total``, and enqueues; a full
    edge raises :class:`EdgeFullError` BEFORE any work (backpressure,
    never loss). ``get()`` dequeues in FIFO order, decoding quantized
    leaves back to their original dtype.

    ``compress=8`` (only value; EQuARX int8, arXiv:2506.17615) encodes
    every leaf the schema marks ``quantizable`` through
    ``compress.quantize_rows`` — deterministic per-row symmetric int8 —
    and re-validates the encoded (values, scales) pairs against the SAME
    declaration with the dtype symbol bound to int8. Non-quantizable
    leaves (logits, grads) always move dense. Per payload the compressed
    transfer also lands in ``collective_bytes_total{op="stage_edge"}`` /
    ``collective_bytes_saved_total{op="stage_edge"}`` — the wire-vs-
    logical split the quantized all-reduce established. Byte math for a
    leaf with last dim D: wire/logical = (1 + 4/D)/4, i.e. ~3.94x saved
    at D=256, 3.76x at D=64, 3.2x at the disagg KV row's hd=16.

    Every ``put`` runs under a ``stage/edge`` blackbox progress window
    and fires the registered ``stage/edge`` failpoint first — a chaos
    delay injected there reads as a stalled stage to the stall sentinel.
    """

    def __init__(self, name, schema, capacity=2, compress=None,
                 dims=None, dtypes=None):
        if compress not in (None, 8):
            raise ValueError(f"edge {name!r}: compress={compress!r} "
                             "unsupported (None or 8)")
        self.name = name
        self.schema = schema
        self.capacity = int(capacity)
        if self.capacity < 1:
            raise ValueError(f"edge {name!r}: capacity must be >= 1")
        self.compress = compress
        self._dims = dict(dims or {})
        self._dtypes = dict(dtypes or {})
        self._q = collections.deque()
        self.stats = {"puts": 0, "gets": 0, "backpressured": 0,
                      "wire_bytes": 0, "logical_bytes": 0}

    def __len__(self):
        return len(self._q)

    def full(self):
        return len(self._q) >= self.capacity

    def put(self, payload, dims=None, dtypes=None):
        """Validate + enqueue one payload; returns its wire bytes."""
        from ..analysis import handoff_schema as _hs

        if len(self._q) >= self.capacity:
            self.stats["backpressured"] += 1
            raise EdgeFullError(
                f"stage edge {self.name!r} is full ({self.capacity} "
                "payload(s) in flight) — backpressure: drain the "
                "consumer before producing more")
        with _goodput_bucket("edge_wait"), \
                _blackbox.progress("stage/edge"):
            _fp.failpoint("stage/edge")
            bind_dims = dict(self._dims, **(dims or {}))
            bind_dtypes = dict(self._dtypes, **(dtypes or {}))
            _hs.validate(self.schema, payload, dims=bind_dims,
                         dtypes=bind_dtypes)
            logical = wire = 0
            stored = {}
            enc_dtypes = {}
            for leaf, spec in _iter_leaves(self.schema["payload"]):
                node = _get_path(payload, leaf)
                nb = _nbytes(node)
                logical += nb
                if (self.compress and spec.get("quantizable")
                        and jnp.issubdtype(node.dtype, jnp.floating)):
                    from . import compress as _compress

                    q, scales = _compress.quantize_rows(node)
                    stored[leaf] = ("q", q, scales, str(node.dtype))
                    wire += _nbytes(q) + _nbytes(scales)
                    dt = spec.get("dtype")
                    if isinstance(dt, str) and dt.startswith("$"):
                        enc_dtypes[dt[1:]] = "int8"
                else:
                    stored[leaf] = ("dense", node)
                    wire += nb
            if self.compress:
                # the ENCODED form must satisfy the same declaration the
                # consumer decodes against: int8 values at the declared
                # shape, f32 per-row scales
                enc = {}
                for leaf, s in stored.items():
                    _set_path(enc, leaf,
                              (s[1], s[2]) if s[0] == "q" else s[1])
                _hs.validate(self.schema, enc, dims=bind_dims,
                             dtypes=dict(bind_dtypes, **enc_dtypes))
                from . import collective as _coll

                _coll.record_compressed("stage_edge", logical, wire)
            _EDGE_BYTES.inc(int(wire))
            self.stats["puts"] += 1
            self.stats["wire_bytes"] += int(wire)
            self.stats["logical_bytes"] += int(logical)
            self._q.append(stored)
            return int(wire)

    def get(self):
        """Dequeue (FIFO) one payload, decoding quantized leaves back to
        their original dtype."""
        if not self._q:
            raise EdgeEmptyError(f"stage edge {self.name!r} is empty")
        stored = self._q.popleft()
        out = {}
        for leaf, s in stored.items():
            if s[0] == "q":
                from . import compress as _compress

                _set_path(out, leaf,
                          _compress.dequantize_rows(s[1], s[2],
                                                    dtype=s[3]))
            else:
                _set_path(out, leaf, s[1])
        self.stats["gets"] += 1
        return out


class StageProgram:
    """One stage of an MPMD graph: a pure function compiled for — and
    pinned to — its OWN mesh.

    Inputs are committed (replicated, ``P()``) onto the stage's mesh
    before dispatch, so the compiled program belongs to that topology;
    each stage holds its own CachedJit, labelled with its name — two
    stages never share an executable (compile_cache_total{site="stage"}).
    """

    def __init__(self, name, fn, mesh=None):
        self.name = name
        self.mesh = mesh
        self._fn = fn   # retained so rebind() can recompile elsewhere
        self._sharding = (NamedSharding(mesh, P())
                         if mesh is not None else None)
        self._jit = _aot.cached_jit(
            fn, site="stage", label=name, record_event="stage/compile")

    def _commit(self, x):
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            return jax.device_put(x, self._sharding)
        return x

    def __call__(self, *args):
        _fp.failpoint("stage/run")
        if self._sharding is not None:
            args = jax.tree_util.tree_map(self._commit, args)
        return self._jit(*args)

    def warm(self, *specs):
        return self._jit.warm(*specs)

    def rebind(self, mesh):
        """Re-pin THIS program to a replacement mesh (the PR 15
        remainder, armed by MpmdPipelineRunner.replace_stage): a fresh
        CachedJit, so the program is rebuilt for the replacement slice.
        Sibling programs are untouched (their CachedJit objects keep
        their compiled entries)."""
        self.mesh = mesh
        self._sharding = (NamedSharding(mesh, P())
                         if mesh is not None else None)
        self._jit = _aot.cached_jit(
            self._fn, site="stage", label=self.name,
            record_event="stage/compile")
        return self


class StageGraph:
    """The MPMD runner: N registered stage programs + edges, executed as
    an explicit schedule of (stage_name, thunk) ticks.

    ``run(plan)`` opens one ``stage_graph`` root span and runs each tick
    under a ``stage_step`` span carrying the stage name — every span in
    one step shares ONE trace_id — and a ``stage/<name>`` blackbox
    progress window, so the stall sentinel names the stalled stage."""

    def __init__(self, name="stage_graph"):
        self.name = name
        self.stages = {}
        self.edges = {}
        #: weight lineage the ticks execute under (framework/lineage.py,
        #: ISSUE 20): set by whoever drives the graph (MpmdPipelineRunner
        #: refreshes it from its trainer each step); surfaced on every
        #: ``stage_step`` span when set
        self.weight_version = None
        # perf ledger (FLAGS_perf_ledger, docs/OBSERVABILITY.md):
        # consumed at construction; disarmed, run() pays one `is None`
        self._perf_ledger = None
        if _flags.get_flag("perf_ledger", False):
            from ..monitor import perfledger as _perfledger

            self._perf_ledger = _perfledger.get_ledger()
        # goodput accountant (FLAGS_goodput, ISSUE 20): same
        # construction-consumed pattern — each tick books `step`, edge
        # transfers inside it nest `edge_wait`
        self._goodput = None
        if _flags.get_flag("goodput", False):
            from ..monitor import goodput as _goodput

            self._goodput = _goodput

    def add_stage(self, program):
        self.stages[program.name] = program
        return program

    def add_edge(self, edge):
        self.edges[edge.name] = edge
        return edge

    def run(self, plan, trace_id=None):
        """Execute `plan` (iterable of (stage_name, thunk)) in order;
        returns the list of thunk results."""
        traced = _trace.is_enabled()
        root = _trace.start_span("stage_graph", subsystem="stage",
                                 trace_id=trace_id, graph=self.name) \
            if traced else None
        t0 = time.perf_counter() if self._perf_ledger is not None else None
        out = []
        try:
            for sname, thunk in plan:
                attrs = {} if self.weight_version is None else \
                    {"weight_version": str(self.weight_version)}
                sp = _trace.start_span(
                    "stage_step", subsystem="stage", parent=root,
                    stage=sname, **attrs) if traced else None
                try:
                    with (self._goodput.bucket("step")
                          if self._goodput is not None
                          else contextlib.nullcontext()), \
                            _blackbox.progress(f"stage/{sname}"):
                        out.append(thunk())
                finally:
                    if sp is not None:
                        sp.end()
        finally:
            if root is not None:
                root.end(ticks=len(out))
            if t0 is not None:
                self._ledger_run((time.perf_counter() - t0) * 1e3,
                                 len(out))
        return out

    def _ledger_run(self, run_ms, ticks):
        """Armed-only (FLAGS_perf_ledger) per-run feed: run/mean-tick
        wall ms through the regression sentinel, with the edge transfer
        tallies riding the row (every FLAGS_perf_ledger_interval-th
        run)."""
        m = {"run_ms": run_ms, "ticks": ticks}
        if ticks:
            m["tick_ms"] = run_ms / ticks
        edges = {}
        for name, st in self.edge_stats().items():
            nums = {k: v for k, v in st.items()
                    if isinstance(v, (int, float))
                    and not isinstance(v, bool)}
            if nums:
                edges[name] = nums
        if edges:
            m["edges"] = edges
        self._perf_ledger.on_step("stage/" + self.name, m)

    def edge_stats(self):
        return {n: dict(e.stats) for n, e in sorted(self.edges.items())}


# ---------------------------------------------------------------------------
# PipelineTrainer re-based on the graph (the FLAGS_mpmd armed path)
# ---------------------------------------------------------------------------


class MpmdPipelineRunner:
    """Runs a ``PipelineTrainer``'s schedule as true MPMD: one compiled
    forward/backward program per stage, each on its own mesh slice,
    activations and grads moving over typed edges.

    Program split (stage template is ``stage_layers[0]`` — stages are
    structurally identical, exactly the baseline's assumption):

    - ``fwd0``: pre (embedding) folded into stage 0 — ``(pre_p, s0_p,
      x_micro) -> h``; ``bwd0`` rematerializes the forward inside a vjp
      and returns ``(g_pre, g_s0)``;
    - ``fwd<k>``/``bwd<k>`` for middle stages: ``(s_p, h) -> h'`` and the
      vjp-recompute backward ``(s_p, h, g') -> (g_s, g_h)``;
    - ``last<K-1>``: the head+loss fused with the final stage —
      ``(s_p, post_p, h, y_micro) -> (loss, g_s, g_post, g_h)`` via
      value_and_grad (1F1B's "backward follows immediately" property by
      construction).

    Schedules order the SAME ticks — per-micro grads are collected and
    summed in fixed micro order, then averaged, so all three schedules
    produce bit-identical updates:

    - ``F-then-B``: every forward tick stage-major, then every backward —
      edge depth reaches n_micro (the GPipe memory profile);
    - ``1F1B``: each micro's backward chain drains as soon as its forward
      chain completes — edge depth 1 (the 1F1B memory profile);
    - ``interleaved``: the 1F1B tick order with TWO virtual stage chunks
      per physical mesh slice (stage k placed on slice k mod K/2; K must
      be even) — the interleaved-virtual-stage placement at the same
      math.

    The optimizer update is the trainer's own ``functional_apply`` in one
    more cached program pinned to the trainer mesh, reading/writing the
    trainer's existing param/opt-state shardings — ``state_dict`` /
    ``sync_to_layer`` keep working unchanged.
    """

    SCHEDULES = ("F-then-B", "1F1B", "interleaved")

    def __init__(self, trainer, stage_meshes=None, compress=None):
        from .mesh import build_mesh
        from .pipeline import _pure_call

        self.tr = trainer
        K = trainer.n_stages
        if K < 2:
            raise ValueError("MPMD needs >= 2 stages")
        if trainer.schedule_mode not in self.SCHEDULES:
            raise ValueError(
                f"unknown schedule {trainer.schedule_mode!r}; MPMD "
                f"schedules: {self.SCHEDULES}")
        self.n_stages = K
        self.schedule_mode = trainer.schedule_mode
        self.compress = compress

        if stage_meshes is not None:
            if len(stage_meshes) != K:
                raise ValueError(f"{len(stage_meshes)} stage meshes for "
                                 f"{K} stages")
            self.stage_meshes = list(stage_meshes)
        else:
            ax_i = list(trainer.mesh.axis_names).index(trainer.pp_axis)
            devs = np.moveaxis(np.asarray(trainer.mesh.devices), ax_i,
                               0).reshape(K, -1)
            if self.schedule_mode == "interleaved":
                if K % 2:
                    raise ValueError("the interleaved schedule needs an "
                                     "even stage count (two virtual "
                                     "chunks per physical slice)")
                n_phys = K // 2
                slices = [list(devs[k % n_phys]) for k in range(K)]
            else:
                slices = [list(devs[k]) for k in range(K)]
            self.stage_meshes = [
                build_mesh((len(s),), ("stage",), devices=s)
                for s in slices]

        cap = trainer.n_micro
        self.act_edges = [
            StageEdge(f"act{k}", HANDOFF_SCHEMA, capacity=cap,
                      compress=compress) for k in range(K - 1)]
        self.grad_edges = [
            StageEdge(f"grad{k}", HANDOFF_SCHEMA_GRAD, capacity=cap)
            for k in range(K - 1)]

        pre, post = trainer.pre, trainer.post_loss
        tpl = trainer.stage_layers[0]

        def fwd_first(pre_p, s_p, x):
            return _pure_call(tpl, s_p, _pure_call(pre, pre_p, x))

        def bwd_first(pre_p, s_p, x, g):
            _, vjp = jax.vjp(
                lambda pp, sp: _pure_call(tpl, sp,
                                          _pure_call(pre, pp, x)),
                pre_p, s_p)
            return vjp(g)

        def fwd_mid(s_p, h):
            return _pure_call(tpl, s_p, h)

        def bwd_mid(s_p, h, g):
            _, vjp = jax.vjp(lambda sp, hh: _pure_call(tpl, sp, hh),
                             s_p, h)
            return vjp(g)

        def last_fused(s_p, post_p, h, y):
            def f(sp, pp, hh):
                o = _pure_call(tpl, sp, hh)
                return _pure_call(post, pp, o, y).astype(jnp.float32)

            loss, grads = jax.value_and_grad(f, argnums=(0, 1, 2))(
                s_p, post_p, h)
            return (loss,) + tuple(grads)

        self.programs = {}
        for k in range(K):
            mesh_k = self.stage_meshes[k]
            if k == 0:
                self.programs["fwd0"] = StageProgram("fwd0", fwd_first,
                                                     mesh=mesh_k)
                self.programs["bwd0"] = StageProgram("bwd0", bwd_first,
                                                     mesh=mesh_k)
            elif k == K - 1:
                self.programs[f"last{k}"] = StageProgram(
                    f"last{k}", last_fused, mesh=mesh_k)
            else:
                self.programs[f"fwd{k}"] = StageProgram(
                    f"fwd{k}", fwd_mid, mesh=mesh_k)
                self.programs[f"bwd{k}"] = StageProgram(
                    f"bwd{k}", bwd_mid, mesh=mesh_k)
        self._fwd0_fn = fwd_first
        self._last_fn = last_fused
        self.graph = StageGraph("pipeline")
        for p in self.programs.values():
            self.graph.add_stage(p)
        for e in self.act_edges + self.grad_edges:
            self.graph.add_edge(e)
        self._opt_step = None

    # -- MPMD stage elasticity (FLAGS_elastic; docs/DISTRIBUTED.md) ---------
    def replace_stage(self, k, mesh):
        """Re-bind stage ``k``'s program(s) to a replacement mesh WITHOUT
        recompiling siblings — the MPMD elasticity axis: one stage's
        slice dies, the other K-1 compiled programs survive untouched.
        Requires FLAGS_elastic (the structural elastic posture). Counted
        in elastic_resume_total{reason="stage_replace"} and noted on the
        blackbox ring so the recovery is attributable."""
        if not _flags.get_flag("elastic", False):
            raise RuntimeError(
                "MpmdPipelineRunner.replace_stage requires "
                "FLAGS_elastic=1 — stage elasticity is part of the "
                "structural elastic posture (docs/DISTRIBUTED.md)")
        K = self.n_stages
        if not 0 <= k < K:
            raise ValueError(f"stage index {k} out of range [0, {K})")
        if k == 0:
            names = ["fwd0", "bwd0"]
        elif k == K - 1:
            names = [f"last{k}"]
        else:
            names = [f"fwd{k}", f"bwd{k}"]
        for name in names:
            self.programs[name].rebind(mesh)
        self.stage_meshes[k] = mesh
        _note_elastic_resume("stage_replace")
        _blackbox.note("stage_replace", stage=k, programs=names,
                       mesh=str(_aot.mesh_fingerprint(mesh)))
        return self

    # -- per-step execution -------------------------------------------------
    def _split_groups(self):
        tr = self.tr
        groups = {"pre": {}, "stage": {}, "post": {}}
        for kname, v in {**tr.frozen, **tr.params}.items():
            grp, nm = kname.split("::", 1)
            groups[grp][nm] = v
        return groups

    def _build_opt(self):
        tr = self.tr

        def opt_fn(params, opt_state, grads, lr):
            return tr.optimizer.functional_apply(params, grads,
                                                 opt_state, lr=lr)

        repl = NamedSharding(tr.mesh, P())
        jitted = jax.jit(
            opt_fn,
            in_shardings=(tr.p_shardings, dict(tr.s_shardings),
                          tr.p_shardings, repl),
            out_shardings=(tr.p_shardings, dict(tr.s_shardings)))
        return _aot.cached_jit(
            jit=jitted, site="stage", label="optimizer",
            record_event="stage/compile")

    def train_step(self, x_micro, y_micro):
        """One MPMD train step over pre-split [n_micro, mb, ...] batches;
        returns the mean scalar loss and updates the trainer's
        params/opt_state in place (same layout as the baseline step)."""
        tr = self.tr
        K, n = self.n_stages, tr.n_micro
        groups = self._split_groups()
        pre_p, post_p = groups["pre"], groups["post"]
        stage_p = [{nm: v[k] for nm, v in groups["stage"].items()}
                   for k in range(K)]
        mb = int(x_micro.shape[1])

        h_in = [[None] * n for _ in range(K)]
        losses = [None] * n
        g_stage = [[None] * n for _ in range(K)]
        g_pre = [None] * n
        g_post = [None] * n

        def fwd_tick(k, m):
            def thunk():
                if k == 0:
                    h = self.programs["fwd0"](pre_p, stage_p[0],
                                              x_micro[m])
                    self.act_edges[0].put({"activation": h},
                                          dims={"mb": mb})
                elif k < K - 1:
                    h = self.act_edges[k - 1].get()["activation"]
                    h_in[k][m] = h
                    out = self.programs[f"fwd{k}"](stage_p[k], h)
                    self.act_edges[k].put({"activation": out},
                                          dims={"mb": mb})
                else:
                    h = self.act_edges[k - 1].get()["activation"]
                    h_in[k][m] = h
                    loss, g_s, g_po, g_h = self.programs[f"last{k}"](
                        stage_p[k], post_p, h, y_micro[m])
                    losses[m] = loss
                    g_stage[k][m] = g_s
                    g_post[m] = g_po
                    self.grad_edges[k - 1].put({"grad": g_h},
                                               dims={"mb": mb})
            return thunk

        def bwd_tick(k, m):
            def thunk():
                g = self.grad_edges[k].get()["grad"]
                if k == 0:
                    gp, gs = self.programs["bwd0"](pre_p, stage_p[0],
                                                   x_micro[m], g)
                    g_pre[m] = gp
                    g_stage[0][m] = gs
                else:
                    gs, gh = self.programs[f"bwd{k}"](stage_p[k],
                                                      h_in[k][m], g)
                    g_stage[k][m] = gs
                    self.grad_edges[k - 1].put({"grad": gh},
                                               dims={"mb": mb})
            return thunk

        def _name(k, kind):
            if k == 0:
                return "fwd0" if kind == "fwd" else "bwd0"
            if k == K - 1 and kind == "fwd":
                return f"last{k}"
            return f"{kind}{k}"

        plan = []
        if self.schedule_mode == "F-then-B":
            for k in range(K):
                for m in range(n):
                    plan.append((_name(k, "fwd"), fwd_tick(k, m)))
            for k in range(K - 2, -1, -1):
                for m in range(n):
                    plan.append((_name(k, "bwd"), bwd_tick(k, m)))
        else:   # 1F1B and interleaved: one micro's backward chain drains
                # as soon as its forward chain completes
            for m in range(n):
                for k in range(K):
                    plan.append((_name(k, "fwd"), fwd_tick(k, m)))
                for k in range(K - 2, -1, -1):
                    plan.append((_name(k, "bwd"), bwd_tick(k, m)))
        # weight lineage (ISSUE 20): the ticks about to run execute under
        # the trainer's CURRENT version — refresh per step, not at
        # construction, so post-restore/reshard bumps show on spans
        self.graph.weight_version = getattr(tr, "weight_version", None)
        self.graph.run(plan)

        def _acc(trees):
            out = trees[0]
            for t in trees[1:]:
                out = jax.tree_util.tree_map(jnp.add, out, t)
            return out

        # fixed micro-order accumulation, THEN the 1/n mean: every
        # schedule sums the same floats in the same order — schedules
        # are placement/ordering choices, not numerics choices
        gp, gpo = _acc(g_pre), _acc(g_post)
        gs = [_acc(g_stage[k]) for k in range(K)]
        # each stage's grads live on ITS mesh — re-commit onto the
        # trainer mesh (replicated) before stacking/the optimizer program
        repl_tr = NamedSharding(tr.mesh, P())
        grads = {}
        for kname in tr.params:
            grp, nm = kname.split("::", 1)
            if grp == "pre":
                g = jax.device_put(gp[nm], repl_tr)
            elif grp == "post":
                g = jax.device_put(gpo[nm], repl_tr)
            else:
                g = jnp.stack([jax.device_put(gs[k][nm], repl_tr)
                               for k in range(K)], axis=0)
            grads[kname] = jax.device_put(
                (g / n).astype(tr.params[kname].dtype),
                tr.p_shardings[kname])

        loss = jnp.mean(jnp.stack(losses))
        if self._opt_step is None:
            self._opt_step = self._build_opt()
        lr = jnp.asarray(tr.optimizer.get_lr(), dtype=jnp.float32)
        tr.params, tr.opt_state = self._opt_step(tr.params, tr.opt_state,
                                                 grads, lr)
        return loss

    # -- analysis hooks ------------------------------------------------------
    def lint_jaxpr(self, x_micro, y_micro):
        """ClosedJaxpr of the fused last-stage program (loss + grads —
        the densest stage) on one micro batch, for the sharding-flow
        lint target (analysis/sharding_flow.py "mpmd_train")."""
        K = self.n_stages
        groups = self._split_groups()
        stage0 = {nm: v[0] for nm, v in groups["stage"].items()}
        stage_last = {nm: v[K - 1] for nm, v in groups["stage"].items()}
        h = jax.eval_shape(
            self._fwd0_fn, groups["pre"], stage0,
            jax.ShapeDtypeStruct(tuple(x_micro.shape[1:]),
                                 x_micro.dtype))
        return jax.make_jaxpr(self._last_fn)(
            stage_last, groups["post"],
            jax.ShapeDtypeStruct(h.shape, h.dtype),
            jax.ShapeDtypeStruct(tuple(y_micro.shape[1:]),
                                 y_micro.dtype))

    def stats(self):
        return {"schedule": self.schedule_mode,
                "n_stages": self.n_stages,
                "compress": self.compress,
                "stage_devices": [len(m.devices.ravel())
                                  for m in self.stage_meshes],
                "edges": self.graph.edge_stats()}


def pipeline_trainer_from_plan(config, model, optimizer):
    """Realize a plan-search emission (analysis/plan_search.emit,
    ``kind="stage_graph"``) as a FLAGS_mpmd :class:`PipelineTrainer`
    whose runner builds this module's typed-edge StageGraph.

    FLAGS_mpmd must already be set (the trainer consumes it at
    construction); ``model`` must expose ``pipeline_split``. The stage
    cut is the config's per-stage layer lists — equal cuts, which is
    what ``pipeline_split(pp)`` produces; a config whose cuts disagree
    with an equal split is rejected loudly rather than silently
    re-cut."""
    import jax

    from .. import flags as _flags
    from .mesh import build_mesh
    from .pipeline import PipelineTrainer

    if config.get("kind") != "stage_graph":
        raise ValueError(
            f"config kind {config.get('kind')!r} is not 'stage_graph'")
    if not _flags.get_flag("mpmd", False):
        raise ValueError(
            "plan config arms the MPMD stage runtime — set FLAGS_mpmd "
            "before realizing (PipelineTrainer consumes it at "
            "construction)")
    if not hasattr(model, "pipeline_split"):
        raise ValueError(
            f"{type(model).__name__} has no pipeline_split(); the plan "
            "search only emits stage_graph configs for models that do")
    pipe = config["pipeline"]
    cuts = pipe.get("stage_layers") or []
    pp = len(cuts) or int(config["mesh"]["shape"][
        config["mesh"]["axes"].index("pp")])
    sizes = {len(c) for c in cuts} if cuts else set()
    if len(sizes) > 1:
        raise ValueError(
            f"unequal stage cuts {cuts}: pipeline_split(pp) produces "
            "equal stages — re-emit the plan")
    pre, stages, post = model.pipeline_split(pp)
    mesh = build_mesh((pp,), ("pp",), devices=jax.devices()[:pp])
    return PipelineTrainer(
        pre, stages, post, optimizer, mesh=mesh,
        n_micro=int(pipe["n_micro"]),
        schedule_mode=pipe.get("schedule", "1F1B"),
        compress=pipe.get("compress"))
