"""SPMD training engine — the ParallelExecutor/SSA-graph replacement.

Reference parity: this one module supersedes the reference's multi-device machinery:
ParallelExecutor + multi_devices_graph_pass (grad allreduce insertion,
framework/details/), ShardingOptimizer program surgery
(fleet/meta_optimizers/sharding_optimizer.py:161-308), and the dygraph Reducer.

TPU-native design: ONE jitted train step over a Mesh.
 - data parallel: batch sharded on 'dp'; XLA inserts the grad psum (ICI).
 - ZeRO ("sharding" stage 1/2/3): optimizer states (and for stage 3, params) get
   NamedShardings over the dp axis; XLA emits reduce_scatter/all_gather — the
   _split_program/_add_broadcast_allreduce passes become sharding annotations.
 - tensor parallel: param shardings over 'mp' provided by distributed.split layers.
 - recompute: jax.checkpoint on the forward.
 - gradient merge / accumulation: lax.scan over micro-batches.
"""
import contextlib
import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import flags as _flags
from .. import monitor as _monitor
from ..monitor import blackbox_lazy as _blackbox  # import-free recorder facade (ISSUE 12)
from ..trace import costs as _costs
from .. import trace as _trace
from ..core.tape import global_tape
from ..core.tensor import Tensor
from ..framework import aot as _aot
from ..framework import lineage as _lineage
from ..profiler import RecordEvent as _RecordEvent
from ..testing import failpoints as _failpoints
from .mesh import get_mesh, mesh_scope

#: The checkpoint transfer edge (ISSUE 13; docs/ANALYSIS.md "Declaring a
#: transfer edge"): the host-side train-state tree gather_train_state
#: writes and restore_train_state re-places onto live shardings.
#: Statically extracted and baseline-pinned by
#: analysis/handoff_schema.py — ROADMAP 5's topology-aware resharding
#: grows this edge into a logical [param, shard-spec] tree, and the
#: baseline is where that (intentional) drift gets acknowledged.
CHECKPOINT_SCHEMA = {
    "edge": "checkpoint_state",
    "producer": "paddle_tpu/distributed/spmd.py::gather_train_state",
    "consumer": "paddle_tpu/distributed/spmd.py::restore_train_state",
    "runtime_checked": False,
    "doc": "host snapshot of the sharded train state; __qar_residual__ "
           "(quantized-allreduce error feedback) and [dp, shard] "
           "optimizer moments ride opt_state; shard_specs records the "
           "logical [param, shard-spec] layout that wrote them so a "
           "restore onto a different dp/mp factorization re-lays-out "
           "(ISSUE 19 topology-aware resharding); __weight_version__ "
           "stamps the writer's weight lineage (ISSUE 20)",
    "payload": {
        "params": {"kind": "opaque",
                   "layout": "{param_name: host array}"},
        "opt_state": {"kind": "opaque",
                      "layout": "{param_name: {moment: host array}} + "
                                "__step__"},
        "optimizer_step_count": {"kind": "scalar", "dtype": "int"},
        "lr_scheduler": {"kind": "opaque",
                         "layout": "scheduler state_dict or None"},
        "shard_specs": {"kind": "opaque",
                        "layout": "writer topology metadata: {v, mode, "
                                  "ndp, dp_axis, shard_update, quantized, "
                                  "sharding_stage, params: {name: {shape, "
                                  "size}}, shard_ps, sharded_keys, "
                                  "qar_eligible} or None (pre-elastic "
                                  "checkpoint)"},
        "__weight_version__": {"kind": "opaque",
                               "layout": "{run_id, counter, origin} "
                                         "weight-version lineage stamp "
                                         "(framework/lineage.py) or "
                                         "absent — a pre-version "
                                         "checkpoint restores as "
                                         "version 0 (ISSUE 20)"},
    },
}

# compile_total/compile_cache_total are declared (and recorded) by
# framework/aot.py's record_compile — one mapping for every site; this
# module reports under site="trainer" so one snapshot schema covers both
# train paths
_COMPILE_MS = _monitor.histogram(
    "compile_ms", "wall time to obtain an executable", labelnames=("site",))
_STEP_MS = _monitor.histogram(
    "step_latency_ms",
    "Executor.run / train_step wall time (host dispatch; device-complete "
    "when FLAGS_benchmark=1 forces a sync)", labelnames=("site",))
_BENCH_SYNC = _monitor.counter(
    "benchmark_sync_total",
    "FLAGS_benchmark block_until_ready syncs on fetches",
    labelnames=("site",))
_SKIPPED = _monitor.counter(
    "train_step_skipped_total",
    "updates skipped by the FLAGS_check_nan_inf non-finite guard (params/"
    "optimizer state left bit-identical; > FLAGS_max_skip_steps "
    "consecutive skips raise)", labelnames=("reason",))

_RESHARD = None  # lazy checkpoint_reshard_total — only a cross-topology
#                  restore (FLAGS_elastic posture) ever creates the family


def _note_reshard(action, n=1):
    """Count one topology-aware restore action (lazy, the failpoints
    _note_fire pattern): moment_reshard / moment_shard / moment_unshard
    (bit-exact re-layouts of [dp, shard] moments), residual_fold /
    residual_zero / residual_drop (__qar_residual__ EF residuals re-laid
    or deterministically zeroed), step_passthrough (replicated scalars)."""
    global _RESHARD
    if not _monitor.is_enabled():
        return
    if _RESHARD is None:
        _RESHARD = _monitor.counter(
            "checkpoint_reshard_total",
            "topology-aware checkpoint restore actions by kind "
            "(docs/DISTRIBUTED.md \"Elastic training\" reshard semantics "
            "table; zero unless a checkpoint restores onto a different "
            "dp/mp factorization)",
            labelnames=("action",))
    _RESHARD.labels(action=action).inc(n)


def _batch_sig_label(batch_arrays):
    return "|".join(
        f"{a.dtype}[{','.join(str(d) for d in a.shape)}]"
        for a in batch_arrays) or "-"


def _pvary(x, ax):
    """Mark x device-varying over `ax` inside shard_map. Differentiating
    w.r.t. an UNVARYING (replicated) input auto-psums the cotangent across
    the axis — so a "local" gradient taken against replicated params comes
    back pre-summed. Casting to varying first keeps the grad rank-local."""
    return jax.lax.pcast(x, (ax,), to="varying")


def owned_device_put(v, sh):
    """device_put that never shares buffers with `v`.

    The jitted train step donates its param/state inputs; device_put to a
    replicated sharding reuses the source's buffer for the shard on its device,
    so donating the placed array would invalidate the Layer's eager tensors
    (and any other trainer placed from the same source). Copy first so the
    trainer exclusively owns every buffer it donates."""
    return jax.device_put(jnp.copy(jnp.asarray(v)), sh)


def _first_divisible_axis(shape, n):
    for i, s in enumerate(shape):
        if s % n == 0 and s >= n:
            return i
    return None


def param_shardings(params, mesh, axis_name, min_size=16384, shard_params=False):
    """ZeRO-style shardings: arrays >= min_size sharded on their first divisible dim."""
    n = mesh.shape[axis_name]
    out = {}
    for k, v in params.items():
        ax = _first_divisible_axis(v.shape, n)
        if shard_params and ax is not None and v.size >= min_size:
            spec = [None] * v.ndim
            spec[ax] = axis_name
            out[k] = NamedSharding(mesh, P(*spec))
        else:
            out[k] = NamedSharding(mesh, P())
    return out


def state_shardings(opt_state, p_shardings, mesh, axis_name, stage):
    """Shard optimizer moments like their params (stage>=2) or replicate."""
    out = {}
    n = mesh.shape[axis_name]
    for pname, st in opt_state.items():
        if pname == "__step__":
            out[pname] = NamedSharding(mesh, P())
            continue
        sub = {}
        for k, v in st.items():
            if stage >= 2 and hasattr(v, "ndim") and v.ndim > 0:
                ax = _first_divisible_axis(v.shape, n)
                if ax is not None and v.size >= 16384:
                    spec = [None] * v.ndim
                    spec[ax] = axis_name
                    sub[k] = NamedSharding(mesh, P(*spec))
                    continue
            sub[k] = NamedSharding(mesh, P())
        out[pname] = sub
    return out


def _collect_moe_aux(layer):
    """Sum MoE load-balance aux losses from the last forward (None if dense).

    Keeps the router's load-balancing gradient alive on trainer paths where the
    loss_fn only sees (outputs, labels)."""
    from ..nn.layer.moe import MoELayer

    aux = None
    for sub in layer.sublayers(include_self=True):
        if isinstance(sub, MoELayer) and sub.aux_loss is not None:
            aux = sub.aux_loss if aux is None else aux + sub.aux_loss
    return aux


#: named selective-remat policies (SpmdTrainer recompute_policy=...): the
#: TPU-native analog of the reference RecomputeConfig.checkpoints name list
_REMAT_POLICIES = {
    "dots": "dots_saveable",
    "dots_no_batch": "dots_with_no_batch_dims_saveable",
    "nothing": "nothing_saveable",
    "everything": "everything_saveable",
}


def _resolve_remat_policy(name):
    if name not in _REMAT_POLICIES:
        raise ValueError(f"recompute_policy must be one of "
                         f"{sorted(_REMAT_POLICIES)}, got {name!r}")
    return getattr(jax.checkpoint_policies, _REMAT_POLICIES[name])


class SpmdTrainer:
    """Compile a Layer + Optimizer + loss into one sharded XLA train step."""

    def __init__(self, layer, optimizer, loss_fn=None, mesh=None, dp_axis="dp",
                 sharding_stage=0, recompute=False, accumulate_steps=1,
                 extra_param_specs=None, metrics_fn=None, donate=True,
                 amp_dtype=None, return_outputs=False, **extra_kwargs):
        self.layer = layer
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self.mesh = mesh or get_mesh()
        self.dp_axis = dp_axis
        self.sharding_stage = sharding_stage
        self.recompute = recompute
        self.accumulate_steps = accumulate_steps
        self.extra_param_specs = extra_param_specs or {}
        self.amp_dtype = amp_dtype
        # hapi metric path: the jitted step also returns the network outputs
        # (no second eager forward per batch); see JitGraphAdapter
        self.return_outputs = return_outputs
        self.last_outputs = None
        self.extra_kwargs = extra_kwargs
        # consumed meta-optimizer knobs (VERDICT r1 #2: every flag must change
        # the compiled program or raise)
        self.localsgd_k = extra_kwargs.get("localsgd_k")
        self.localsgd_begin = extra_kwargs.get("localsgd_begin", 1)
        self.state_offload = bool(extra_kwargs.get("state_offload"))
        if self.localsgd_k:
            if sharding_stage > 0 or accumulate_steps > 1 or extra_param_specs:
                raise ValueError(
                    "localsgd holds per-rank param replicas and cannot compose "
                    "with sharding/gradient-merge/tensor-parallel specs")
        if return_outputs and (self.localsgd_k or self._is_dgc()):
            raise ValueError(
                "return_outputs is not supported with localsgd/DGC steps "
                "(their shard_map programs do not thread outputs)")
        pol = extra_kwargs.get("recompute_policy")
        if pol is not None:
            _resolve_remat_policy(pol)  # fail fast on unknown names
            if not recompute:
                raise ValueError("recompute_policy requires recompute=True "
                                 "(the policy selects WHAT jax.checkpoint "
                                 "saves; without remat it changes nothing)")
            if extra_kwargs.get("remat_offload"):
                raise ValueError("remat_offload and recompute_policy both "
                                 "select a jax.checkpoint policy — pick one")
        self._compiled = None       # latest executable (back-compat handle)
        self._compiled_store = {}   # (batch-sig, guarded, numerics,
        #                             quantized, shard_update) ->
        #                             (executable, guarded, numerics,
        #                             qerr-leg) — these flags change the
        #                             step's output arity (finiteness
        #                             verdict / fused health-stats leg /
        #                             quantization-error scalar)
        self._nonfinite_streak = 0  # consecutive skipped steps
        self._nonfinite_total = 0   # lifetime skipped steps (stats())
        # step-time accounting for stats(): host wall time per step plus
        # the FLAGS_benchmark sync share, joined with the cost registry's
        # per-executable FLOPs into the MFU report (docs/OBSERVABILITY.md)
        self._step_count = 0
        self._step_ms_sum = 0.0
        self._sync_ms_sum = 0.0
        self._last_sig = None       # batch-sig label of the last step
        self._step_span = None      # open span of the in-flight step
        self._cost_entries = {}     # THIS trainer's sig -> cost entry: a
        #                             second trainer with the same batch
        #                             shapes must not clobber our join
        # numerics telescope (FLAGS_numerics, docs/OBSERVABILITY.md):
        # the monitor is created lazily on the first armed fetch so the
        # plain path never imports monitor/numerics.py at all
        self._numerics = None
        self._numerics_seen = 0            # armed steps so far
        self._numerics_last_device = None  # device-resident stats leg
        self._numerics_last_host = None    # cached fetch of the above
        # perf ledger (FLAGS_perf_ledger, docs/OBSERVABILITY.md):
        # consumed at construction. Deliberately NON-structural — the
        # ledger only observes host-side timings and never changes the
        # compiled program, so it joins NO executable key (armed and
        # disarmed runs share AOT entries and train byte-identically);
        # disarmed, the hook in _finish_step is one `is not None`
        self._perf_ledger = None
        self._perf_mesh_fp = None
        self._perf_cold = False   # last step resolved a compile
        if _flags.get_flag("perf_ledger", False):
            from ..monitor import perfledger as _perfledger

            self._perf_ledger = _perfledger.get_ledger()
            self._perf_mesh_fp = _aot.mesh_fingerprint(self.mesh)
        # weight-version lineage (framework/lineage.py, ISSUE 20):
        # ALWAYS-ON host metadata — every weight state this trainer
        # produces carries a monotone (run_id, counter, origin) stamp,
        # bumped per step/restore/reshard, written into checkpoints as
        # the __weight_version__ leaf and onto train_step spans. No
        # metric series, no compiled-program effect: parity is trivial.
        self.weight_version = _lineage.WeightVersion(
            _lineage.new_run_id(), 0, "init")
        # goodput accountant (FLAGS_goodput, docs/OBSERVABILITY.md):
        # consumed at construction. Deliberately NON-structural like the
        # perf ledger — wall-clock bucketing only, joins NO executable
        # key; disarmed, every hook is one `is not None`
        self._goodput = None
        if _flags.get_flag("goodput", False):
            from ..monitor import goodput as _goodput

            self._goodput = _goodput
            _goodput.ensure_run(self.weight_version.run_id)
        self.params = {n: p._data for n, p in layer.named_parameters() if getattr(p, "trainable", True)}
        self.frozen = {n: p._data for n, p in layer.named_parameters() if not getattr(p, "trainable", True)}
        self.buffers = {n: b._data for n, b in layer.named_buffers()}
        self.opt_state = optimizer.functional_init(self.params)
        # bandwidth-frugal dp (docs/DISTRIBUTED.md): both flags are
        # consumed HERE — the quantized reduce lays residual state into
        # the opt-state pytree and update sharding re-shapes the moments,
        # so a post-construction toggle raises (see _compress_active)
        # instead of silently mis-reducing
        self._quantized, self._shard_update = self._resolve_compress()
        self._qerr_device = None    # banked per-step quantization-error
        #                             norm (device-resident; fetched
        #                             lazily by quantize_error())
        # async double-buffered dispatch (docs/PERF.md): the flag and its
        # window are consumed HERE (post-hoc toggles raise via
        # _async_active); the deferred-guard ledger below exists on EVERY
        # trainer — the non-async path defers the verdict fetch by one
        # step, the armed path by up to FLAGS_async_window steps. Only
        # the armed path imports distributed/async_dispatch.py.
        self._async, self._async_window = self._resolve_async()
        self._overlap_comm = self._resolve_overlap()
        self._mpmd = self._resolve_mpmd()
        self._elastic = self._resolve_elastic()
        self._pending_verdicts = []  # [(schedule position, device bool)]
        self._guard_abort = None     # undelivered deferred FloatingPointError
        self._verdict_fetches = 0    # drains (host syncs) so far
        self._window_max_depth = 0   # deepest in-flight window seen
        self._prefetch_hits = 0      # prefetch()-staged batches consumed
        self._prefetched = None      # (ids key, device arrays) or None
        if self._async:
            from . import async_dispatch as _async_mod

            # crash/stall bundles record how deep the in-flight window
            # was (weakly held — same contract as the serving provider)
            _blackbox.register_provider("trainer_async", self,
                                        _async_mod.blackbox_table)
        self._place_state()

    # -- bandwidth-frugal dp (quantized all-reduce / update sharding) ----------
    def _resolve_compress(self):
        """Consume FLAGS_quantized_allreduce / FLAGS_shard_weight_update
        at construction. Returns (quantized, shard_update) after
        validating the config: both run the plain-dp shard_map step, so
        ZeRO stages / gradient merge / tensor-parallel specs /
        return_outputs are rejected loudly; localsgd/DGC silently ignore
        the flags (they own their reduce — the PR 4 guard's carve-out).
        Also captures bits/min-size and the eligibility set (float
        params >= FLAGS_quantized_allreduce_min_size elements)."""
        q = bool(_flags.get_flag("quantized_allreduce", False))
        s = bool(_flags.get_flag("shard_weight_update", False))
        self._qar_bits = int(_flags.get_flag("quantized_allreduce_bits", 8))
        self._qar_min_size = int(
            _flags.get_flag("quantized_allreduce_min_size", 1024))
        self._qar_eligible = frozenset()
        self._shard_state_keys = {}
        self._shard_ps = {}
        if not (q or s) or self.localsgd_k or self._is_dgc():
            return False, False
        names = ("FLAGS_quantized_allreduce" if q else "") \
            + ("+" if q and s else "") \
            + ("FLAGS_shard_weight_update" if s else "")
        if self.sharding_stage > 0:
            raise ValueError(
                f"{names} targets the plain-dp path; sharding_stage="
                f"{self.sharding_stage} already reduce-scatters through "
                "XLA's ZeRO shardings — pick one (docs/DISTRIBUTED.md "
                "composition matrix)")
        if self.accumulate_steps > 1:
            raise ValueError(
                f"{names} does not compose with gradient merge "
                "(accumulate_steps > 1) yet")
        if self.extra_param_specs:
            raise ValueError(
                f"{names} does not compose with tensor-parallel "
                "extra_param_specs (params must be replicated over dp)")
        if self.return_outputs:
            raise ValueError(
                f"{names} steps run under shard_map, which does not "
                "thread network outputs (same restriction as "
                "localsgd/DGC)")
        if q:
            from . import compress as _compress

            _compress._check_bits(self._qar_bits)
            self._qar_eligible = frozenset(
                n for n, v in self.params.items()
                if jnp.issubdtype(v.dtype, jnp.floating)
                and v.size >= self._qar_min_size)
        if s and type(self.optimizer).__name__ in ("Lamb", "Lars",
                                                   "LarsMomentum"):
            raise ValueError(
                "FLAGS_shard_weight_update needs an elementwise update "
                f"rule; {type(self.optimizer).__name__}'s trust-ratio "
                "reads whole-parameter norms, which a 1/dp shard cannot "
                "see (docs/DISTRIBUTED.md)")
        return q, s

    def _compress_active(self):
        """FLAGS_quantized_allreduce was consumed at construction (the
        error-feedback residuals ride the opt-state pytree laid out
        then); a post-construction toggle is loud instead of silently
        mis-reducing. localsgd/DGC carve-out as for the PR 4 guard —
        the disarmed check is one get_flag + compare."""
        q = bool(_flags.get_flag("quantized_allreduce", False))
        if q != self._quantized and not self.localsgd_k \
                and not self._is_dgc():
            raise RuntimeError(
                "FLAGS_quantized_allreduce changed after this trainer "
                "was constructed; the quantized reduce lays out its "
                "error-feedback residual state at __init__ — build a "
                "new SpmdTrainer under the new flag value")
        return self._quantized

    def _shard_update_active(self):
        """FLAGS_shard_weight_update, same construction-time contract
        as _compress_active (the optimizer moments are stored sharded)."""
        s = bool(_flags.get_flag("shard_weight_update", False))
        if s != self._shard_update and not self.localsgd_k \
                and not self._is_dgc():
            raise RuntimeError(
                "FLAGS_shard_weight_update changed after this trainer "
                "was constructed; update sharding re-shapes the "
                "optimizer-state pytree at __init__ — build a new "
                "SpmdTrainer under the new flag value")
        return self._shard_update

    # -- MPMD stage runtime (distributed/stage.py) -----------------------------
    def _resolve_mpmd(self):
        """Consume FLAGS_mpmd at construction. The data-parallel trainer
        has no stage split — the flag only keys the executables here
        (exec key), so an MPMD-armed trainer never aliases an executable
        with a plain one; the armed runtime itself lives on
        PipelineTrainer/DisaggregatedPool."""
        return bool(_flags.get_flag("mpmd", False))

    def _mpmd_active(self):
        """FLAGS_mpmd was consumed at construction (it is baked into
        this trainer's executable keys); a post-construction toggle is
        loud instead of silently re-keying mid-run. One get_flag +
        compare when disarmed."""
        m = bool(_flags.get_flag("mpmd", False))
        if m != self._mpmd:
            raise RuntimeError(
                "FLAGS_mpmd changed after this trainer was constructed; "
                "the flag is baked into the executable cache keys at "
                "__init__ — build a new trainer under the new flag "
                "value")
        return self._mpmd

    # -- elastic training (distributed/elastic.py) -----------------------------
    def _resolve_elastic(self):
        """Consume FLAGS_elastic at construction. Arms resize(mesh) and
        keys the executables (exec key) so an elastic world never
        aliases a plain executable; the supervisor itself
        lives in the manifest-lazy distributed/elastic.py — a plain
        trainer never imports it (tests/test_elastic_gate.py)."""
        return bool(_flags.get_flag("elastic", False))

    def _elastic_active(self):
        """FLAGS_elastic was consumed at construction (it is baked into
        this trainer's executable keys and gates resize); a
        post-construction toggle is loud instead of silently re-keying
        mid-run. One get_flag + compare when disarmed."""
        e = bool(_flags.get_flag("elastic", False))
        if e != self._elastic:
            raise RuntimeError(
                "FLAGS_elastic changed after this trainer was "
                "constructed; the flag is baked into the executable "
                "cache keys at __init__ — build a new trainer under the "
                "new flag value")
        return self._elastic

    # -- async double-buffered dispatch (docs/PERF.md) -------------------------
    def _resolve_async(self):
        """Consume FLAGS_async_dispatch / FLAGS_async_window at
        construction. Returns (armed, window); window is 1 when the flag
        is unset — the non-async deferred-by-one guard fetch."""
        a = bool(_flags.get_flag("async_dispatch", False))
        w = max(1, int(_flags.get_flag("async_window", 8))) if a else 1
        return a, w

    def _async_active(self):
        """FLAGS_async_dispatch was consumed at construction (the step
        handle/window machinery is armed then); a post-construction
        toggle is loud instead of silently changing what train_step
        returns. One get_flag + compare when disarmed."""
        a = bool(_flags.get_flag("async_dispatch", False))
        if a != self._async:
            raise RuntimeError(
                "FLAGS_async_dispatch changed after this trainer was "
                "constructed; the step-handle/deferred-verdict window is "
                "armed at __init__ — build a new SpmdTrainer under the "
                "new flag value")
        return self._async

    def _resolve_overlap(self):
        """Consume FLAGS_overlap_grad_comm at construction: per-layer
        int8 exchange legs interleavable with backward compute. Only
        meaningful on the quantized quant-only path — anything else is
        rejected loudly (shard_weight_update already exchanges per leg);
        localsgd/DGC ignore it like every compress flag."""
        o = bool(_flags.get_flag("overlap_grad_comm", False))
        if not o or self.localsgd_k or self._is_dgc():
            return False
        if not self._quantized:
            raise ValueError(
                "FLAGS_overlap_grad_comm splits the quantized gradient "
                "exchange into per-layer legs — it requires "
                "FLAGS_quantized_allreduce (docs/PERF.md overlap matrix)")
        if self._shard_update:
            raise ValueError(
                "FLAGS_overlap_grad_comm composed with "
                "FLAGS_shard_weight_update is redundant: the sharded "
                "update already exchanges one quantized leg per param")
        return True

    def _overlap_active(self):
        """Construction-time contract for FLAGS_overlap_grad_comm (the
        leg structure is part of the compiled program's identity)."""
        o = bool(_flags.get_flag("overlap_grad_comm", False))
        if o != self._overlap_comm and not self.localsgd_k \
                and not self._is_dgc():
            raise RuntimeError(
                "FLAGS_overlap_grad_comm changed after this trainer was "
                "constructed; the per-leg exchange structure is compiled "
                "in — build a new SpmdTrainer under the new flag value")
        return self._overlap_comm

    def _drain_verdicts(self, force=False, deliver=False):
        """Host-fetch pending deferred guard verdicts and replay the
        skip bookkeeping in dispatch order (docs/PERF.md "deferred
        guard"). Without `force`, drains only when the window is full —
        ONE host sync per FLAGS_async_window steps. A trailing skip
        rolls the optimizer schedule position back (the device never
        advanced __step__ for it — the retry contract holds); a streak
        beyond FLAGS_max_skip_steps raises the same FloatingPointError
        the per-step fetch used to, just up to a window later.

        The abort is STICKY until delivered through a train_step call
        (`deliver=True`): a drain triggered inside an observability
        helper (stats() under a scraper's try/except) may have its
        raise swallowed, but the run still cannot train past the limit
        — the next train_step entry re-raises it."""
        if self._guard_abort is not None:
            err = self._guard_abort
            if deliver:
                self._guard_abort = None
            raise err
        pending = self._pending_verdicts
        if not pending or (not force and len(pending) < self._async_window):
            return
        if len(pending) > self._window_max_depth:
            self._window_max_depth = len(pending)
        batch, self._pending_verdicts = pending, []
        self._verdict_fetches += 1
        if self._async and _monitor.is_enabled():
            from . import async_dispatch as _async_mod

            _async_mod.window_depth_gauge().set(len(batch))
            _async_mod.verdict_fetch_counter().inc()
        # ONE device_get for the whole window — THE deliberate host sync
        # of the guard path (everything else stays device-resident)
        vals = jax.device_get([v for _, v in batch])  # lint: allow(step-loop-host-sync)
        raise_streak = None
        for (pos, _), val in zip(batch, vals):
            if bool(val):   # device_get above already landed it on host
                self._nonfinite_streak = 0
                continue
            # the update was skipped ON DEVICE (params/state/buffers
            # where-selected pre-update, __step__ included); the host
            # learns now
            self._nonfinite_streak += 1
            self._nonfinite_total += 1
            if pos == self.optimizer._step_count - 1:
                # the skip is the NEWEST dispatch — nothing consumed
                # the next schedule position yet, so rewind and the
                # retry reuses this slot (the window-1 / sync-path
                # retry contract, exactly). A MID-window skip's
                # position is burned instead: later dispatches already
                # advanced the schedule, and rewinding would hand the
                # next dispatch an rng position an APPLIED step
                # already consumed (duplicated dropout masks).
                self.optimizer._step_count -= 1
            _SKIPPED.labels(reason="nonfinite").inc()
            if _trace.is_enabled():
                # the skipping step's own span ended long ago — the
                # trace-level skip signal lands at discovery time
                with _trace.span("guard/skip", subsystem="trainer",
                                 step=int(pos)):
                    pass
            max_skip = int(_flags.get_flag("max_skip_steps", 3))
            if self._nonfinite_streak > max_skip:
                raise_streak = self._nonfinite_streak
        if raise_streak is not None:
            max_skip = int(_flags.get_flag("max_skip_steps", 3))
            err = FloatingPointError(
                f"train_step: non-finite loss/gradients for "
                f"{raise_streak} consecutive steps "
                f"(> FLAGS_max_skip_steps={max_skip}); aborting — "
                "every skipped step left parameters untouched (the "
                "on-device where-select); finite steps dispatched LATER "
                "in this deferred window (if any) applied normally "
                "before the limit was discovered (docs/PERF.md); "
                "inspect the data pipeline / learning rate")
            if not deliver:
                self._guard_abort = err   # sticky until train_step sees it
            raise err

    def guard_sync(self):
        """Force-fetch every pending deferred guard verdict NOW: after
        this, stats()/streak counters reflect every dispatched step and
        a pending FloatingPointError surfaces here. The per-step fetch
        the pre-async trainer did, on demand."""
        self._drain_verdicts(force=True)

    def prefetch(self, *batch):
        """Stage the NEXT step's batch on device (async double-
        buffering): device_put runs asynchronously, so the transfer
        overlaps the in-flight step's compute. The next train_step call
        made with the SAME array objects consumes the staged copies
        instead of re-marshalling them. The originals are HELD here
        until consumed (identity is the match key), and a train_step
        over DIFFERENT arrays discards the staging. Standard
        double-buffer contract: do not mutate a staged array in place
        before the step that consumes it — the device copy was taken
        at prefetch() time."""
        from jax.sharding import NamedSharding as _NS

        shard = _NS(self.mesh, P(self.dp_axis))
        arrays = [jax.device_put(
            b._data if isinstance(b, Tensor) else jnp.asarray(np.asarray(b)),
            shard) for b in batch]
        self._prefetched = (batch, arrays)

    # -- sharding placement ----------------------------------------------------
    def _offload_state_shardings(self, force=False):
        """sharding_configs.offload parity: optimizer moments live in pinned
        host memory; XLA inserts the HBM<->host transfers around the update.
        TPU-only — the CPU backend cannot execute replicated pinned_host
        programs (same XLA limitation as remat_offload). `force` skips the
        CPU guard so tests can assert the produced memory kinds."""
        on_cpu = (not force and
                  np.asarray(self.mesh.devices).flat[0].platform == "cpu")
        if on_cpu:
            import warnings

            warnings.warn("state_offload ignored on the CPU backend; "
                          "optimizer state stays in device memory")
            return self.s_shardings
        out = {}
        for pname, st in self.s_shardings.items():
            if pname == "__step__":
                out[pname] = st
                continue
            out[pname] = {
                k: NamedSharding(sh.mesh, sh.spec, memory_kind="pinned_host")
                for k, sh in st.items()
            }
        return out

    def _place_state(self):
        mesh = self.mesh
        ax = self.dp_axis
        if self.localsgd_k:
            # LocalSGD: every dp rank holds its own param/moment replica
            # (leading replica dim sharded on dp); see _build_localsgd
            ndp = mesh.shape[ax]
            rep = lambda v: jnp.broadcast_to(v, (ndp,) + v.shape)
            self.params = {k: rep(v) for k, v in self.params.items()}
            self.p_shardings = {k: NamedSharding(mesh, P(ax)) for k in self.params}
            self.s_shardings, new_state = {}, {}
            for pname, st in self.opt_state.items():
                if pname == "__step__":
                    self.s_shardings[pname] = NamedSharding(mesh, P())
                    new_state[pname] = st
                    continue
                self.s_shardings[pname] = {k: NamedSharding(mesh, P(ax)) for k in st}
                new_state[pname] = {k: rep(v) for k, v in st.items()}
            self.opt_state = new_state
            self.b_shardings = {k: NamedSharding(mesh, P()) for k in self.buffers}
            self.params = {k: owned_device_put(v, self.p_shardings[k]) for k, v in self.params.items()}
            self.buffers = {k: owned_device_put(v, self.b_shardings[k]) for k, v in self.buffers.items()}
            self.opt_state = {
                pname: (owned_device_put(st, self.s_shardings[pname]) if pname == "__step__"
                        else {k: owned_device_put(v, self.s_shardings[pname][k]) for k, v in st.items()})
                for pname, st in self.opt_state.items()
            }
            return
        if self._is_dgc():
            if self.sharding_stage > 0 or self.accumulate_steps > 1:
                raise ValueError("DGC composes with plain data parallel only "
                                 "(no sharding / gradient merge)")
            ndp = mesh.shape[ax]
            # params/velocity replicated; DGC residuals u/v are PER-RANK state
            self.p_shardings = {k: NamedSharding(mesh, P()) for k in self.params}
            self.s_shardings, new_state = {}, {}
            for pname, st in self.opt_state.items():
                if pname == "__step__":
                    self.s_shardings[pname] = NamedSharding(mesh, P())
                    new_state[pname] = st
                    continue
                sub_sh, sub = {}, {}
                for k, v in st.items():
                    if k in ("dgc_u", "dgc_v"):
                        sub_sh[k] = NamedSharding(mesh, P(ax))
                        sub[k] = jnp.broadcast_to(v, (ndp,) + v.shape)
                    else:
                        sub_sh[k] = NamedSharding(mesh, P())
                        sub[k] = v
                self.s_shardings[pname] = sub_sh
                new_state[pname] = sub
            self.opt_state = new_state
            self.b_shardings = {k: NamedSharding(mesh, P()) for k in self.buffers}
            self.params = {k: owned_device_put(v, self.p_shardings[k]) for k, v in self.params.items()}
            self.buffers = {k: owned_device_put(v, self.b_shardings[k]) for k, v in self.buffers.items()}
            self.opt_state = {
                pname: (owned_device_put(st, self.s_shardings[pname]) if pname == "__step__"
                        else {k: owned_device_put(v, self.s_shardings[pname][k]) for k, v in st.items()})
                for pname, st in self.opt_state.items()
            }
            return
        if self._quantized or self._shard_update:
            # bandwidth-frugal dp layout (docs/DISTRIBUTED.md): params and
            # buffers replicated (the step all-gathers updated params
            # itself when sharding the update); with shard_weight_update
            # every param-shaped optimizer moment is flattened, padded,
            # and stored [dp, shard] over the dp axis (scalar state like
            # Adam's beta powers stays replicated — its update is
            # rank-invariant); with quantized_allreduce each eligible
            # param carries a per-rank error-feedback residual
            # [dp, *shape] under the reserved __qar_residual__ key
            ndp = mesh.shape[ax]
            block = 1
            if self._quantized:
                from . import compress as _compress

                block = _compress.DEFAULT_BLOCK
            self.p_shardings = {k: NamedSharding(mesh, P())
                                for k in self.params}
            self.b_shardings = {k: NamedSharding(mesh, P())
                                for k in self.buffers}
            if self._shard_update:
                for k, v in self.params.items():
                    if k in self._qar_eligible:
                        # the quantized exchange hands each rank whole
                        # blocks — the state shard must line up with it
                        unit = block * ndp
                        self._shard_ps[k] = (-(-int(v.size) // unit)
                                             * unit) // ndp
                    else:
                        self._shard_ps[k] = -(-int(v.size) // ndp)
            s_sh, new_state = {}, {}
            for pname, st in self.opt_state.items():
                if pname == "__step__":
                    s_sh[pname] = NamedSharding(mesh, P())
                    new_state[pname] = st
                    continue
                p = self.params[pname]
                sub_sh, sub, sharded_keys = {}, {}, set()
                for k, v in st.items():
                    if (self._shard_update
                            and getattr(v, "shape", None) == p.shape):
                        ps = self._shard_ps[pname]
                        flat = jnp.pad(jnp.ravel(v),
                                       (0, ps * ndp - int(v.size)))
                        sub[k] = flat.reshape(ndp, ps)
                        sub_sh[k] = NamedSharding(mesh, P(ax))
                        sharded_keys.add(k)
                    else:
                        sub[k] = v
                        sub_sh[k] = NamedSharding(mesh, P())
                s_sh[pname] = sub_sh
                new_state[pname] = sub
                self._shard_state_keys[pname] = sharded_keys
            if self._quantized:
                res_sh, res = {}, {}
                for name in sorted(self._qar_eligible):
                    v = self.params[name]
                    res[name] = jnp.zeros((ndp,) + tuple(v.shape),
                                          jnp.float32)
                    res_sh[name] = NamedSharding(mesh, P(ax))
                new_state["__qar_residual__"] = res
                s_sh["__qar_residual__"] = res_sh
            self.s_shardings = s_sh
            self.opt_state = new_state
            self.params = {k: owned_device_put(v, self.p_shardings[k])
                           for k, v in self.params.items()}
            self.buffers = {k: owned_device_put(v, self.b_shardings[k])
                            for k, v in self.buffers.items()}
            self.opt_state = {
                pname: (owned_device_put(st, self.s_shardings[pname])
                        if pname == "__step__"
                        else {k: owned_device_put(v,
                                                  self.s_shardings[pname][k])
                              for k, v in st.items()})
                for pname, st in self.opt_state.items()
            }
            return
        self.p_shardings = param_shardings(
            self.params, mesh, ax, shard_params=(self.sharding_stage >= 3)
        )
        for k, spec in self.extra_param_specs.items():
            if k in self.p_shardings:
                self.p_shardings[k] = NamedSharding(mesh, spec)
        self.s_shardings = state_shardings(self.opt_state, self.p_shardings, mesh, ax, self.sharding_stage)
        if self.state_offload:
            self.s_shardings = self._offload_state_shardings()
        self.b_shardings = {k: NamedSharding(mesh, P()) for k in self.buffers}
        # device_put everything per its sharding (owned copies: the step donates)
        self.params = {k: owned_device_put(v, self.p_shardings[k]) for k, v in self.params.items()}
        self.buffers = {k: owned_device_put(v, self.b_shardings[k]) for k, v in self.buffers.items()}
        new_state = {}
        for pname, st in self.opt_state.items():
            if pname == "__step__":
                new_state[pname] = owned_device_put(st, NamedSharding(self.mesh, P()))
            else:
                new_state[pname] = {k: owned_device_put(v, self.s_shardings[pname][k]) for k, v in st.items()}
        self.opt_state = new_state

    # -- pure step -------------------------------------------------------------
    def _forward_loss(self, params, buffers, batch, rng=None):
        import contextlib

        from ..core.functional import functional_state
        from ..core.generator import traced_rng

        layer = self.layer
        tape = global_tape()
        amp_ctx = contextlib.nullcontext()
        if self.amp_dtype is not None:
            from ..amp.auto_cast import auto_cast

            amp_ctx = auto_cast(True, dtype=self.amp_dtype)
        rng_ctx = traced_rng(rng) if rng is not None else contextlib.nullcontext()
        with functional_state(layer, {**params, **self.frozen},
                              buffers) as (named_p, named_b):
            with tape.pause(), amp_ctx, rng_ctx:
                inputs = [Tensor(b) for b in batch[:-1]]
                label = Tensor(batch[-1])
                out = None
                if self.loss_fn is not None:
                    out = layer(*inputs)
                    loss = self.loss_fn(out, label)
                    aux = _collect_moe_aux(layer)
                    if aux is not None:
                        w = getattr(getattr(layer, "cfg", None), "moe_aux_weight", 0.01)
                        loss = loss + w * aux
                else:
                    loss = layer(*inputs, label)
            new_buffers = {n: named_b[n]._data for n in buffers}
            out_raw = None
            if self.return_outputs and out is not None:
                out_raw = jax.tree_util.tree_map(
                    lambda t: t._data if isinstance(t, Tensor) else t, out,
                    is_leaf=lambda t: isinstance(t, Tensor))
            return (loss._data if isinstance(loss, Tensor) else loss,
                    new_buffers, out_raw)

    def _is_dgc(self):
        """DGC + dp>1: grads must be top-k compressed BEFORE the cross-rank
        reduce (the whole point of DGC) — handled by _build_dgc."""
        from .fleet.meta_optimizers.dgc_optimizer import DGCMomentumOptimizer

        return (isinstance(self.optimizer, DGCMomentumOptimizer)
                and self.dp_axis in self.mesh.axis_names
                and self.mesh.shape[self.dp_axis] > 1)

    def _wrapped_forward(self):
        fwd = self._forward_loss
        if self.recompute:
            # the offload custom call (annotate_device_placement) has no CPU
            # lowering under the sharded jit step in this jax version; guard
            # verified empirically — the policy itself works on TPU
            on_cpu = np.asarray(self.mesh.devices).flat[0].platform == "cpu"
            if self.extra_kwargs.get("remat_offload") and on_cpu:
                import warnings

                warnings.warn("remat_offload ignored on the CPU backend; "
                              "falling back to plain recompute")
            if self.extra_kwargs.get("remat_offload") and not on_cpu:
                # RecomputeConfig.enable_offload parity: matmul residuals go
                # to pinned host memory instead of being recomputed or held
                # in HBM (reference offloads checkpoints to CPU)
                policy = jax.checkpoint_policies.offload_dot_with_no_batch_dims(
                    "device", "pinned_host")
                fwd = jax.checkpoint(fwd, static_argnums=(), policy=policy)
            elif self.extra_kwargs.get("recompute_policy") is not None:
                # selective remat: trade recompute FLOPs vs HBM per policy.
                # 'dots' saves matmul outputs (recompute elementwise only) —
                # usually the sweet spot on TPU; 'nothing' recomputes
                # everything (max memory savings, max FLOPs).
                fwd = jax.checkpoint(
                    fwd, static_argnums=(),
                    policy=_resolve_remat_policy(
                        self.extra_kwargs["recompute_policy"]))
            else:
                fwd = jax.checkpoint(fwd, static_argnums=())
        return fwd

    def _build(self, batch_arrays):
        if self.localsgd_k:
            return self._build_localsgd(batch_arrays)
        if self._is_dgc():
            return self._build_dgc(batch_arrays)
        if self._compress_active() or self._shard_update_active():
            return self._build_dp_compressed(batch_arrays)
        mesh = self.mesh
        ax = self.dp_axis
        fwd = self._wrapped_forward()
        accum = self.accumulate_steps

        want_out = self.return_outputs
        guard = self._guard_active()
        narmed = self._numerics_active()
        if narmed:
            from ..monitor import numerics as _numerics

            # SORTED param order: jax returns dict pytrees key-sorted, so
            # self.params' insertion order changes after the first step —
            # sorted is the one order that matches across build/fetch
            stat_layers = sorted(self.params)

        def step(params, opt_state, buffers, lr, rng, *batch):
            def loss_fn(p, b, r):
                # this step is partitioned by XLA from its shardings; a
                # kernel XLA cannot partition (Pallas flash) reads the
                # scoped mesh and shard_maps itself over it
                with mesh_scope(mesh):
                    loss, new_buf, outs = fwd(p, buffers, b, r)
                return loss.astype(jnp.float32), (new_buf, outs)

            if accum > 1:
                # gradient merge (fleet/meta_optimizers/gradient_merge_optimizer.py):
                # micro-batch scan, grads averaged; per-micro rng via fold_in
                micro = [jnp.reshape(b, (accum, b.shape[0] // accum) + b.shape[1:]) for b in batch]

                def body(carry, xs):
                    g_acc, l_acc = carry
                    mb, idx = xs[:-1], xs[-1]
                    r = jax.random.fold_in(rng, idx)
                    (loss, aux), grads = jax.value_and_grad(
                        loss_fn, has_aux=True)(params, list(mb), r)
                    g_acc = jax.tree_util.tree_map(lambda a, g: a + g, g_acc, grads)
                    return (g_acc, l_acc + loss), aux

                g0 = jax.tree_util.tree_map(jnp.zeros_like, params)
                (grads, loss_sum), (new_buf_all, outs_all) = jax.lax.scan(
                    body, (g0, jnp.zeros((), jnp.float32)),
                    tuple(micro) + (jnp.arange(accum),))
                grads = jax.tree_util.tree_map(lambda g: g / accum, grads)
                loss = loss_sum / accum
                new_buffers = jax.tree_util.tree_map(lambda v: v[-1], new_buf_all)
                # outputs scanned [accum, mb, ...] -> full batch [accum*mb, ...]
                outputs = (jax.tree_util.tree_map(
                    lambda v: v.reshape((-1,) + v.shape[2:]), outs_all)
                    if want_out else None)
            else:
                (loss, (new_buffers, outputs)), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, batch, rng)
            # the gradients' reduction over dp is XLA's here, from the
            # shardings: it has no scope of its own (`grad_sync` names the
            # explicit collectives of the three other builders)
            with jax.named_scope("optimizer"):
                new_params, new_state = self.optimizer.functional_apply(
                    params, grads, opt_state, lr=lr)
            nstats = None
            if narmed:
                # FLAGS_numerics: the fused per-layer health aggregation
                # (monitor/numerics.py), computed on the RAW grads and
                # update BEFORE any guard select — a poisoned step must
                # still name the layer that went non-finite
                nstats = _numerics.device_stats(
                    stat_layers, loss, grads, params, new_params)
            if guard:
                # FLAGS_check_nan_inf: ONE fused on-device finiteness
                # verdict over loss + every gradient; a non-finite step
                # selects the PRE-update params/state/buffers (bit-
                # identical — __step__ included, so the LR schedule does
                # not advance either) and reports the flag to the host
                finite = jnp.isfinite(loss)
                for g in jax.tree_util.tree_leaves(grads):
                    finite = jnp.logical_and(
                        finite, jnp.all(jnp.isfinite(g)))

                def keep(new, old):
                    return jnp.where(finite, new, old)

                new_params = jax.tree_util.tree_map(keep, new_params, params)
                new_state = jax.tree_util.tree_map(keep, new_state, opt_state)
                new_buffers = jax.tree_util.tree_map(
                    keep, new_buffers, buffers)
            out = [loss, new_params, new_state, new_buffers]
            if want_out:
                out.append(outputs)
            if narmed:
                out.append(nstats)
            if guard:
                out.append(finite)
            return tuple(out)

        batch_shard = NamedSharding(mesh, P(ax))
        repl = NamedSharding(mesh, P())
        in_shardings = (
            self.p_shardings,
            dict(self.s_shardings),
            self.b_shardings,
            repl,
            repl,  # per-step rng key
        ) + tuple(batch_shard for _ in batch_arrays)
        out_shardings = (
            repl,
            self.p_shardings,
            dict(self.s_shardings),
            self.b_shardings,
        )
        if want_out:
            # outputs: per-example arrays, batch-sharded over dp (prefix spec)
            out_shardings = out_shardings + (batch_shard,)
        if narmed:
            out_shardings = out_shardings + (
                _numerics.stat_shardings(repl),)   # the stats leg
        if guard:
            out_shardings = out_shardings + (repl,)   # the finite flag
        # buffers (argnum 2) donate like params/opt_state: the trainer
        # owns them (owned_device_put) and rebinds them from the step
        # output every call — not donating doubled their HBM footprint
        # (the donation-miss finding ISSUE 13's sharding targets surfaced)
        return jax.jit(_aot.named(step, "train.step"),
                       in_shardings=in_shardings, out_shardings=out_shardings,
                       donate_argnums=(0, 1, 2))

    def _shard_map(self, f, in_specs, out_specs, check_vma=True):
        """check_vma=False is for bodies whose replicated outputs flow
        through all_gather: the values are identical on every rank by
        construction (deterministic dequantize of identical gathered
        bytes), but static rep-inference cannot prove it — the compressed
        dp step's tests assert the cross-replica equality dynamically."""
        return jax.shard_map(f, mesh=self.mesh, in_specs=in_specs,
                             out_specs=out_specs,
                             axis_names={self.dp_axis},
                             check_vma=check_vma)

    def _build_localsgd(self, batch_arrays):
        """LocalSGD (fleet/meta_optimizers/localsgd_optimizer.py parity, SPMD):
        every dp rank trains its own param replica for k steps with NO grad
        allreduce; every k-th step (>= begin_step) the replicas are pmean'd.
        The compiled program provably differs from plain DP: the per-step grad
        psum disappears and a step-gated param pmean appears."""
        mesh, ax = self.mesh, self.dp_axis
        k, begin = int(self.localsgd_k), int(self.localsgd_begin)
        fwd = self._wrapped_forward()
        opt = self.optimizer

        def step(params, opt_state, buffers, lr, rng, *batch):
            def local(params_r, state_r, buffers, lr, rng, *batch_local):
                p = {n: v[0] for n, v in params_r.items()}
                st = {n: (v if n == "__step__" else {m: a[0] for m, a in v.items()})
                      for n, v in state_r.items()}
                # per-rank dropout masks (ranks intentionally diverge)
                r = jax.random.fold_in(rng, jax.lax.axis_index(ax))

                def loss_fn(pp, b):
                    loss, nb, _ = fwd(pp, buffers, b, r)
                    return loss.astype(jnp.float32), nb

                (loss, new_buf), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(p, batch_local)
                with jax.named_scope("optimizer"):
                    new_p, new_st = opt.functional_apply(p, grads, st, lr=lr)
                # what LocalSGD reduces is the replicas, every k-th step
                with jax.named_scope("grad_sync"):
                    step_no = new_st["__step__"]
                    do_avg = jnp.logical_and(step_no >= begin, step_no % k == 0)
                    avg = {n: jax.lax.pmean(v, ax) for n, v in new_p.items()}
                    new_p = {n: jnp.where(do_avg, avg[n], new_p[n]) for n in new_p}
                    loss = jax.lax.pmean(loss, ax)
                    new_buf = {n: jax.lax.pmean(v, ax) for n, v in new_buf.items()}
                out_p = {n: v[None] for n, v in new_p.items()}
                out_st = {n: (v if n == "__step__" else {m: a[None] for m, a in v.items()})
                          for n, v in new_st.items()}
                return loss, out_p, out_st, new_buf

            in_specs = (
                {n: P(ax) for n in params},
                {n: (P() if n == "__step__" else {m: P(ax) for m in st})
                 for n, st in opt_state.items()},
                {n: P() for n in buffers},
                P(),
                P(),  # rng key (ranks fold in their axis index)
            ) + tuple(P(ax) for _ in batch)
            out_specs = (P(), {n: P(ax) for n in params},
                         {n: (P() if n == "__step__" else {m: P(ax) for m in st})
                          for n, st in opt_state.items()},
                         {n: P() for n in buffers})
            return self._shard_map(local, in_specs, out_specs)(
                params, opt_state, buffers, lr, rng, *batch)

        batch_shard = NamedSharding(mesh, P(ax))
        repl = NamedSharding(mesh, P())
        in_shardings = (self.p_shardings, dict(self.s_shardings),
                        self.b_shardings, repl, repl) + tuple(batch_shard for _ in batch_arrays)
        out_shardings = (repl, self.p_shardings, dict(self.s_shardings), self.b_shardings)
        return jax.jit(_aot.named(step, "train.step_localsgd"), in_shardings=in_shardings,
                       out_shardings=out_shardings,
                       donate_argnums=(0, 1, 2))  # buffers too (ISSUE 13)

    def _build_dgc(self, batch_arrays):
        """DGC (dgc_momentum_op.cc parity) with a REAL cross-rank sparse
        reduce: each dp rank momentum-corrects its LOCAL gradient, top-k
        sparsifies, and only the sparse tensor crosses the interconnect
        (psum); residuals u/v stay rank-local. Plain DP psums the dense grad;
        this program psums the masked one — compressing what crosses DCN."""
        mesh, ax = self.mesh, self.dp_axis
        opt = self.optimizer
        m = opt._momentum
        sparsity = opt._sparsity
        fwd = self._wrapped_forward()

        def step(params, opt_state, buffers, lr, rng, *batch):
            def local(params, state_r, buffers, lr, rng, *batch_local):
                st = {n: (v if n == "__step__" else
                          {k2: (a[0] if k2 in ("dgc_u", "dgc_v") else a)
                           for k2, a in v.items()})
                      for n, v in state_r.items()}
                r = jax.random.fold_in(rng, jax.lax.axis_index(ax))

                def loss_fn(pp, b):
                    loss, nb, _ = fwd(pp, buffers, b, r)
                    return loss.astype(jnp.float32), nb

                # differentiate against VARYING params: grads stay rank-local
                # so top-k masks the local gradient and pmean below is the one
                # true cross-rank reduce (see _pvary)
                params_v = {n: _pvary(p, ax) for n, p in params.items()}
                (loss, new_buf), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params_v, batch_local)
                new_p, new_st = {}, {"__step__": st["__step__"] + 1}
                for n, p in params.items():
                    with jax.named_scope("optimizer"):
                        g = grads[n].astype(p.dtype)
                        u = m * st[n]["dgc_u"] + g
                        v = st[n]["dgc_v"] + u
                        kk = max(1, int(v.size * (1.0 - sparsity)))
                        thresh = jax.lax.top_k(jnp.abs(v).reshape(-1), kk)[0][-1]
                        mask = (jnp.abs(v) >= thresh).astype(v.dtype)
                        sparse = v * mask
                    # THE DGC allreduce: only the compressed tensor crosses ranks
                    with jax.named_scope("grad_sync"):
                        cross = jax.lax.pmean(sparse, ax)
                    with jax.named_scope("optimizer"):
                        new_p[n] = p - lr.astype(p.dtype) * cross
                        new_st[n] = {"velocity": st[n]["velocity"],
                                     "dgc_u": (u * (1 - mask))[None],
                                     "dgc_v": (v * (1 - mask))[None]}
                with jax.named_scope("grad_sync"):
                    loss = jax.lax.pmean(loss, ax)
                    new_buf = {n: jax.lax.pmean(v, ax) for n, v in new_buf.items()}
                return loss, new_p, new_st, new_buf

            state_spec = {n: (P() if n == "__step__" else
                              {k2: (P(ax) if k2 in ("dgc_u", "dgc_v") else P())
                               for k2 in st})
                          for n, st in opt_state.items()}
            in_specs = ({n: P() for n in params}, state_spec,
                        {n: P() for n in buffers}, P(),
                        P()) + tuple(P(ax) for _ in batch)
            out_specs = (P(), {n: P() for n in params}, state_spec,
                         {n: P() for n in buffers})
            return self._shard_map(local, in_specs, out_specs)(
                params, opt_state, buffers, lr, rng, *batch)

        batch_shard = NamedSharding(mesh, P(ax))
        repl = NamedSharding(mesh, P())
        in_shardings = (self.p_shardings, dict(self.s_shardings),
                        self.b_shardings, repl, repl) + tuple(batch_shard for _ in batch_arrays)
        out_shardings = (repl, self.p_shardings, dict(self.s_shardings), self.b_shardings)
        return jax.jit(_aot.named(step, "train.step_dgc"), in_shardings=in_shardings,
                       out_shardings=out_shardings,
                       donate_argnums=(0, 1, 2))  # buffers too (ISSUE 13)

    def _build_dp_compressed(self, batch_arrays):
        """Plain-dp train step with an EXPLICIT gradient exchange
        (shard_map over dp) replacing the jit path's XLA-inserted psum,
        so the wire format is ours to choose (docs/DISTRIBUTED.md):

        - FLAGS_quantized_allreduce (EQuARX, arXiv:2506.17615): eligible
          grads are padded to quantization blocks, error-feedback
          corrected, bundled, and moved through
          compress.quantized_all_reduce_ef — int8 on the wire, float32
          accumulation, stochastic rounding keyed off the step rng;
          per-layer residuals ride the opt-state pytree as
          __qar_residual__. Small/non-float grads stay on the exact fp32
          pmean.
        - FLAGS_shard_weight_update (arXiv:2004.13336): per param, grads
          are reduce-scattered, the optimizer update runs on each
          replica's 1/dp shard against its sharded moments, and only the
          UPDATED param all-gathers back — no replica computes the same
          update twice. Composed with the quantized flag, the quantized
          exchange's scatter phase feeds the sharded update directly
          (the fp32 all-reduce never exists in any form).

        The PR 4 guard threads through: the finiteness verdict is taken
        on the RAW local loss/grads before any quantization and pmin'd
        across ranks, and the where-select restores params, buffers, AND
        the residuals/sharded moments bit-exactly — a skipped step
        carries no quantization poison forward. The numerics telescope's
        stats leg reads the REDUCED grads, with the per-layer non-finite
        element counts psum'd from the raw local grads so a poisoned
        step still names the dying layer."""
        from . import collective as _coll
        from . import compress as _compress
        from ..optimizer.optimizer import _GLOBAL_NORM_TYPES

        mesh, ax = self.mesh, self.dp_axis
        ndp = mesh.shape[ax]
        opt = self.optimizer
        fwd = self._wrapped_forward()
        quant, shard_upd = self._quantized, self._shard_update
        bits, block = self._qar_bits, _compress.DEFAULT_BLOCK
        guard = self._guard_active()
        narmed = self._numerics_active()
        if narmed:
            from ..monitor import numerics as _numerics

            stat_layers = sorted(self.params)
        eligible = self._qar_eligible
        pnames = list(self.params)
        shapes = {n: (tuple(v.shape), int(v.size), v.dtype)
                  for n, v in self.params.items()}
        has_clip = (opt._grad_clip is not None
                    and isinstance(opt._grad_clip, _GLOBAL_NORM_TYPES))

        # static bundle plan for the fused quantized reduce (quant-only
        # mode): each eligible grad padded to whole blocks so no scale
        # spans two layers, then one exchange moves the whole bundle.
        # FLAGS_overlap_grad_comm instead plans one leg per eligible
        # layer: the legs are independent collectives XLA's scheduler is
        # free to interleave with the remaining backward compute (the
        # EQuARX hide-behind-compute condition; docs/PERF.md)
        plan, bundle, legs = [], 0, []
        if quant and not shard_upd:
            unit = block * ndp
            if self._overlap_comm:
                for name in pnames:
                    if name in eligible:
                        L = -(-shapes[name][1] // unit) * unit
                        legs.append((name, L))
            else:
                for name in pnames:
                    if name in eligible:
                        L = -(-shapes[name][1] // block) * block
                        plan.append((name, bundle, L))
                        bundle += L
                bundle = -(-bundle // unit) * unit if bundle else 0

        def step(params, opt_state, buffers, lr, rng, *batch):
            def local(params, state_r, buffers, lr, rng, *batch_local):
                res_in = state_r.get("__qar_residual__", {})
                st_in = {n: v for n, v in state_r.items()
                         if n != "__qar_residual__"}
                # differentiate against VARYING params so grads stay
                # rank-local and the explicit exchange below is the one
                # true cross-rank reduce (see _pvary)
                params_v = {n: _pvary(p, ax) for n, p in params.items()}

                def loss_fn(pp, b):
                    loss, nb, _ = fwd(pp, buffers, b, rng)
                    return loss.astype(jnp.float32), nb

                (loss, new_buf), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params_v, batch_local)
                qkey = jax.random.fold_in(rng, 0x514152)
                finite = None
                if guard:
                    # verdict on the RAW local values, agreed across
                    # ranks BEFORE any quantization touches the grads
                    finite = jnp.isfinite(loss)
                    for g in jax.tree_util.tree_leaves(grads):
                        finite = jnp.logical_and(
                            finite, jnp.all(jnp.isfinite(g)))
                    finite = jax.lax.pmin(
                        finite.astype(jnp.int32), ax) > 0
                raw_nonf = None
                if narmed:
                    raw_nonf = jax.lax.psum(jnp.stack([
                        jnp.sum(~jnp.isfinite(
                            grads[n].astype(jnp.float32))
                        ).astype(jnp.float32)
                        for n in stat_layers]), ax)

                with jax.named_scope("grad_sync"):
                    red = {}          # name -> full-shape MEAN grad (f32)
                    g_shards = {}     # name -> [ps] MEAN grad shard (f32)
                    res_out = {}
                    qerr_sq = jnp.zeros((), jnp.float32)
                    if legs:
                        # overlapped per-layer legs: each eligible grad is
                        # its own EF-corrected int8 exchange with a per-leg
                        # rounding key — independent ops the scheduler can
                        # pipeline against backward compute
                        for i, (name, L) in enumerate(legs):
                            shape, size, _ = shapes[name]
                            g32 = grads[name].astype(jnp.float32).ravel()
                            inp = (g32 + res_in[name][0]
                                   .astype(jnp.float32).ravel())
                            flat = jnp.pad(inp, (0, L - size))
                            _coll.record_compressed(
                                "quantized_all_reduce", size * 4,
                                L * bits // 8 + (L // block) * 4)
                            reduced, local_rt = \
                                _compress.quantized_all_reduce_ef(
                                    flat, ax, jax.random.fold_in(qkey, i),
                                    bits=bits, block=block)
                            red[name] = (reduced[:size] / ndp).reshape(shape)
                            r_new = (inp - local_rt[:size]).reshape(shape)
                            res_out[name] = r_new
                            qerr_sq = qerr_sq + jnp.sum(r_new * r_new)
                    if plan and bundle:
                        parts, logical = [], 0
                        for name, off, L in plan:
                            g32 = grads[name].astype(jnp.float32).ravel()
                            inp = (g32 + res_in[name][0]
                                   .astype(jnp.float32).ravel())
                            parts.append(jnp.pad(inp, (0, L - g32.shape[0])))
                            logical += shapes[name][1] * 4
                        tail = bundle - sum(L for _, _, L in plan)
                        if tail:
                            parts.append(jnp.zeros((tail,), jnp.float32))
                        flat = (jnp.concatenate(parts) if len(parts) > 1
                                else parts[0])
                        _coll.record_compressed(
                            "quantized_all_reduce", logical,
                            bundle * bits // 8 + (bundle // block) * 4)
                        reduced, local_rt = _compress.quantized_all_reduce_ef(
                            flat, ax, qkey, bits=bits, block=block)
                        for name, off, L in plan:
                            shape, size, _ = shapes[name]
                            red[name] = (reduced[off:off + size]
                                         / ndp).reshape(shape)
                            r_new = (flat[off:off + size]
                                     - local_rt[off:off + size]).reshape(shape)
                            res_out[name] = r_new
                            qerr_sq = qerr_sq + jnp.sum(r_new * r_new)
                    if shard_upd:
                        for i, name in enumerate(pnames):
                            shape, size, _ = shapes[name]
                            ps = self._shard_ps[name]
                            g32 = grads[name].astype(jnp.float32).ravel()
                            if name in eligible:
                                inp = (g32 + res_in[name][0]
                                       .astype(jnp.float32).ravel())
                                flat = jnp.pad(inp, (0, ps * ndp - size))
                                _coll.record_compressed(
                                    "quantized_reduce_scatter", size * 4,
                                    ps * ndp * bits // 8
                                    + (ps * ndp // block) * 4)
                                shard_sum, local_rt = _compress._exchange_reduce(
                                    flat, ax, jax.random.fold_in(qkey, i),
                                    bits, block)
                                r_new = (inp - local_rt[:size]).reshape(shape)
                                res_out[name] = r_new
                                qerr_sq = qerr_sq + jnp.sum(r_new * r_new)
                            else:
                                flat = jnp.pad(g32, (0, ps * ndp - size))
                                _monitor.record_collective(
                                    "reduce-scatter",
                                    _monitor.tensor_nbytes(flat))
                                shard_sum = jax.lax.psum_scatter(
                                    flat, ax, tiled=True)
                            g_shards[name] = shard_sum / ndp
                    else:
                        for name in pnames:
                            if name not in red:
                                g = grads[name]
                                _monitor.record_collective(
                                    "all-reduce", _monitor.tensor_nbytes(g))
                                red[name] = jax.lax.pmean(g, ax)

                # ---- optimizer update ---------------------------------
                with jax.named_scope("optimizer"):
                    if shard_upd:
                        wd = jnp.asarray(opt._wd, jnp.float32)
                        stats_red = None
                        if narmed:
                            # the telescope reads full-shape reduced grads
                            # (pre-clip, like the plain path); gathering them
                            # is diagnostic-only traffic
                            stats_red = {}
                            for name in pnames:
                                shape, size, _ = shapes[name]
                                full = jax.lax.all_gather(
                                    g_shards[name], ax, tiled=True)
                                stats_red[name] = full[:size].reshape(shape)
                        if has_clip:
                            local_sq = sum(jnp.sum(v * v)
                                           for v in g_shards.values())
                            gnorm = jnp.sqrt(jax.lax.psum(local_sq, ax))
                            clip_norm = opt._grad_clip.clip_norm
                            scale = clip_norm / jnp.maximum(gnorm, clip_norm)
                            g_shards = {k: v * scale
                                        for k, v in g_shards.items()}
                        idx = jax.lax.axis_index(ax)
                        new_params, new_st = {}, {}
                        for name in pnames:
                            shape, size, dtype = shapes[name]
                            ps = self._shard_ps[name]
                            p = params[name]
                            p_flat = jnp.pad(jnp.ravel(p),
                                             (0, ps * ndp - size))
                            p_shard = jax.lax.dynamic_slice_in_dim(
                                p_flat, idx * ps, ps)
                            sharded = self._shard_state_keys.get(name, set())
                            st_shard = {k: (v[0] if k in sharded else v)
                                        for k, v in st_in[name].items()}
                            new_p_shard, new_st_shard = opt._rule_with_decay(
                                p_shard, g_shards[name].astype(p.dtype),
                                st_shard, lr, wd)
                            _monitor.record_collective(
                                "all-gather",
                                _monitor.tensor_nbytes(new_p_shard) * ndp)
                            full = jax.lax.all_gather(new_p_shard, ax,
                                                      tiled=True)
                            new_params[name] = full[:size].reshape(shape)
                            new_st[name] = {
                                k: (v[None] if k in sharded else v)
                                for k, v in new_st_shard.items()}
                        new_st["__step__"] = st_in["__step__"] + 1
                    else:
                        stats_red = red
                        new_params, new_st = opt.functional_apply(
                            params, red, st_in, lr=lr)

                loss_red = jax.lax.pmean(loss, ax)
                nstats = None
                if narmed:
                    nstats = _numerics.device_stats(
                        stat_layers, loss_red, stats_red, params,
                        new_params)
                    # raw-grad attribution: the reduced grads a poisoned
                    # step produces are already NaN-scaled, but the
                    # per-layer ELEMENT counts must come from the raw
                    # local grads (psum'd above) to match the plain
                    # path's naming contract
                    nstats = dict(nstats)
                    nstats["nonfinite"] = raw_nonf
                if quant:
                    new_st = dict(new_st)
                    new_st["__qar_residual__"] = {
                        n: res_out[n][None] for n in res_out}
                qerr = None
                if quant:
                    if guard:
                        # a guard-skipped step restores the OLD
                        # residuals — report THEIR norm, not the
                        # poisoned one this step computed and discarded
                        old_sq = jnp.zeros((), jnp.float32)
                        for n in res_out:
                            r_old = res_in[n][0].astype(jnp.float32)
                            old_sq = old_sq + jnp.sum(r_old * r_old)
                        qerr_sq = jnp.where(finite, qerr_sq, old_sq)
                    qerr = jnp.sqrt(jax.lax.psum(qerr_sq, ax))
                new_buffers = {n: jax.lax.pmean(v, ax)
                               for n, v in new_buf.items()}
                if guard:
                    def keep(new, old):
                        return jnp.where(finite, new, old)

                    new_params = jax.tree_util.tree_map(
                        keep, new_params, params)
                    new_st = jax.tree_util.tree_map(
                        keep, new_st, dict(state_r))
                    new_buffers = jax.tree_util.tree_map(
                        keep, new_buffers, buffers)
                out = [loss_red, new_params, new_st, new_buffers]
                if narmed:
                    out.append(nstats)
                if guard:
                    out.append(finite)
                if quant:
                    out.append(qerr)
                return tuple(out)

            state_spec = {}
            for pname, st in opt_state.items():
                if pname == "__step__":
                    state_spec[pname] = P()
                elif pname == "__qar_residual__":
                    state_spec[pname] = {k: P(ax) for k in st}
                else:
                    sharded = self._shard_state_keys.get(pname, set())
                    state_spec[pname] = {
                        k: (P(ax) if k in sharded else P()) for k in st}
            in_specs = (
                {n: P() for n in params}, state_spec,
                {n: P() for n in buffers}, P(), P(),
            ) + tuple(P(ax) for _ in batch)
            out_specs = [P(), {n: P() for n in params}, state_spec,
                         {n: P() for n in buffers}]
            if narmed:
                out_specs.append({k: P() for k in _numerics.STAT_KEYS})
            if guard:
                out_specs.append(P())
            if quant:
                out_specs.append(P())
            return self._shard_map(local, in_specs, tuple(out_specs),
                                   check_vma=False)(
                params, opt_state, buffers, lr, rng, *batch)

        batch_shard = NamedSharding(mesh, P(ax))
        repl = NamedSharding(mesh, P())
        in_shardings = (self.p_shardings, dict(self.s_shardings),
                        self.b_shardings, repl,
                        repl) + tuple(batch_shard for _ in batch_arrays)
        out_shardings = [repl, self.p_shardings, dict(self.s_shardings),
                         self.b_shardings]
        if narmed:
            out_shardings.append(_numerics.stat_shardings(repl))
        if guard:
            out_shardings.append(repl)
        if quant:
            out_shardings.append(repl)
        return jax.jit(_aot.named(step, "train.step_dp_compressed"), in_shardings=in_shardings,
                       out_shardings=tuple(out_shardings),
                       donate_argnums=(0, 1, 2))   # buffers too (ISSUE 13)

    # -- compile (lazy or warm-start) ------------------------------------------
    @staticmethod
    def _batch_sig_key(batch_arrays):
        return tuple((tuple(a.shape), str(a.dtype)) for a in batch_arrays)

    def _guard_active(self):
        """FLAGS_check_nan_inf builds the step with the on-device non-
        finite guard (docs/ROBUSTNESS.md). localsgd/DGC shard_map programs
        don't thread the verdict — the flag is ignored there."""
        return (bool(_flags.get_flag("check_nan_inf"))
                and not self.localsgd_k and not self._is_dgc())

    def _numerics_active(self):
        """FLAGS_numerics appends the fused health-stats leg to the
        compiled step (monitor/numerics.py, docs/OBSERVABILITY.md
        "Numerics telescope"). localsgd/DGC shard_map programs don't
        thread it — the same carve-out as the non-finite guard. The flag
        lives in flags.py so this check never imports the telescope."""
        return (bool(_flags.get_flag("numerics"))
                and not self.localsgd_k and not self._is_dgc())

    def _exec_key(self, batch_arrays):
        # the guard/numerics legs change the compiled program's output
        # arity, so they are part of the executable's identity: toggling
        # either flag recompiles instead of mis-unpacking a stale
        # executable. The compressed-dp legs join too (quantized adds
        # the qerr output; both swap the whole program) — they are
        # construction-time static, but _compress_active/_shard_update_
        # active also make a post-hoc flag flip raise here instead of
        # silently reusing the wrong executable
        return (self._batch_sig_key(batch_arrays), self._guard_active(),
                self._numerics_active(), self._compress_active(),
                self._shard_update_active(), self._overlap_active(),
                self._mpmd_active(), self._elastic_active())

    def _aot_compile(self, batch_arrays, lr, rng, force=False):
        """Build the jitted step for THIS batch signature: the plain lazy
        jit, or, forced (aot_build, tracing, the perf ledger), its
        executable compiled now (framework/aot.py). Compiled steps are
        kept per batch signature (a trailing partial batch must not
        shadow the full-batch executable); batch_arrays may be
        jax.ShapeDtypeStructs (aot_build: nothing is executed)."""
        sig = _batch_sig_label(batch_arrays)
        guarded = self._guard_active()
        narmed = self._numerics_active()
        with (self._goodput.bucket("compile") if self._goodput is not None
              else contextlib.nullcontext()), \
                _RecordEvent("trainer/compile"), \
                _monitor.timed(_COMPILE_MS.labels(site="trainer")):
            jitted = self._build(batch_arrays)
            compiled, source = _aot.compile_cached(
                jitted,
                (self.params, self.opt_state, self.buffers, lr, rng,
                 *batch_arrays),
                # the perf ledger forces the eager (cost-accountable)
                # compile exactly as tracing does: MFU needs the
                # executable's flops, which a lazy bypass jit never
                # exposes — same program, so still non-structural
                force=force or _trace.is_enabled()
                or self._perf_ledger is not None)
        self._compiled_store[self._exec_key(batch_arrays)] = (
            compiled, guarded, narmed, self._quantized)
        self._compiled = compiled  # latest executable (back-compat handle)
        _aot.record_compile("trainer", sig, source)
        cost_entry = _costs.record("trainer", sig,
                                   _aot.executable_of(compiled))
        if cost_entry is not None:
            self._cost_entries[sig] = cost_entry
        return source

    def aot_build(self, batch_specs):
        """Warm-start: compile the train step from batch shape specs — no
        real data, nothing executed. One (shape, dtype) pair (or
        jax.ShapeDtypeStruct) per train_step positional arg::

            trainer.aot_build([((8, 128), "int32"), ((8, 128), "int32")])

        The step is compiled in memory (through jax's persistent cache
        where paddle.enable_compile_cache() turned it on), so the first
        train_step pays zero compile. Returns "fresh"."""
        from ..core.generator import default_generator

        specs = []
        for spec in batch_specs:
            if isinstance(spec, jax.ShapeDtypeStruct):
                specs.append(spec)
            else:
                shape, dtype = spec
                specs.append(jax.ShapeDtypeStruct(tuple(shape),
                                                  np.dtype(dtype)))
        lr = jnp.asarray(self.optimizer.get_lr(), dtype=jnp.float32)
        rng = default_generator().fold_in(self.optimizer._step_count)
        return self._aot_compile(specs, lr, rng, force=True)

    def compiled_text(self):
        """HLO text of the latest step executable — what the device really
        runs (chip_smoke.py reads it for the Pallas flash custom call).
        None while the step is only a lazy jit: compile through
        :meth:`aot_build` first. Also None once that executable rejected a
        live call and the trainer went back to the lazy jit, so a reader
        never inspects a program that is not the one running."""
        executable = _aot.executable_of(self._compiled)
        return None if executable is None else executable.as_text()

    # -- public ---------------------------------------------------------------
    def train_step(self, *batch):
        # window beacon around the whole step (compile included): a hung
        # compile or device dispatch leaves an active, non-advancing
        # trainer/step site for the stall sentinel; a finished training
        # run deactivates it instead of reading as stalled forever
        if self._goodput is not None:
            # goodput `step` bucket around the whole step — a compile
            # resolving inside nests its own bucket and PAUSES this one,
            # so productive time never double-books (FLAGS_goodput)
            with self._goodput.bucket("step"), \
                    _blackbox.progress("trainer/step"):
                return self._train_step_impl(*batch)
        with _blackbox.progress("trainer/step"):
            return self._train_step_impl(*batch)

    def _train_step_impl(self, *batch):
        from ..core.generator import default_generator

        _failpoints.failpoint("trainer/step")
        self._async_active()   # post-hoc toggle raises (ctor contract)
        # deferred guard (docs/PERF.md): settle PREVIOUS steps' verdicts
        # before this step's schedule position is read — a full window
        # drains in ONE device_get; a trailing skip rewinds the
        # schedule so this dispatch retries the skipped position.
        # deliver=True: a sticky abort a swallowed stats() drain left
        # behind is re-raised (and cleared) HERE, to train_step's caller
        self._drain_verdicts(deliver=True)
        with _trace.phase("train/step") as root:
            with _trace.phase("train/batch") as ph:
                pre, self._prefetched = self._prefetched, None
                hit = pre is not None and len(pre[0]) == len(batch) \
                    and all(a is b for a, b in zip(pre[0], batch))
                ph.counts["prefetch_hit"] = int(hit)
                if hit:
                    # prefetch() already staged THESE arrays on device
                    # while the previous step ran — consume the copies,
                    # skip marshalling. (A non-matching step discards the
                    # staging: stale copies must not linger to be consumed
                    # many steps later.)
                    batch_arrays = pre[1]
                    self._prefetch_hits += 1
                else:
                    batch_arrays = [b._data if isinstance(b, Tensor)
                                    else jnp.asarray(np.asarray(b))  # lint: allow(step-loop-host-sync)
                                    for b in batch]
                # value-transforming failpoint (scale:F) — chaos tests
                # inject a gradient spike / non-finite batch here; one
                # boolean check when nothing is armed (docs/ROBUSTNESS.md)
                batch_arrays = _failpoints.transform("trainer/batch",
                                                     batch_arrays)
                lr = jnp.asarray(self.optimizer.get_lr(), dtype=jnp.float32)
                # fresh per-step randomness (dropout etc.): deterministic
                # under paddle.seed, varies per step — a trace-time key
                # would bake ONE dropout mask into the compiled program
                rng = default_generator().fold_in(self.optimizer._step_count)
            with _trace.phase("train/resolve"):
                sig_label = _batch_sig_label(batch_arrays)
                self._last_sig = sig_label
                entry = self._compiled_store.get(
                    self._exec_key(batch_arrays))
                if entry is None:
                    source = self._aot_compile(batch_arrays, lr, rng)
                    entry = self._compiled_store[
                        self._exec_key(batch_arrays)]
                else:
                    source = "memory"
                    if _monitor.is_enabled():
                        _aot.record_compile("trainer", sig_label, "memory")
            root.counts.update(step=int(self.optimizer._step_count),
                               sig=sig_label, source=source)
            return self._run_step(root, entry, lr, rng, batch_arrays,
                                  sig_label, source)

    def _run_step(self, root, entry, lr, rng, batch_arrays, sig_label,
                  source):
        """Call the resolved executable and unpack it (`train/dispatch`),
        then the monitor tail (`train/finish`)."""
        compiled, guarded, narmed, qleg = entry
        if self._perf_ledger is not None:
            self._perf_cold = source != "memory"
        # step span: compile-cache source + batch signature (+sync time,
        # stamped by _finish_step); carries the step's trace identity
        # and the weight version this step advances FROM (ISSUE 20)
        self._step_span = _trace.start_span(
            "train_step", subsystem="trainer", sig=sig_label, source=source,
            step=int(self.optimizer._step_count), guarded=guarded,
            weight_version=str(self.weight_version))
        try:
            # the exec window starts HERE, after compile resolution:
            # stats()/MFU must divide flops by run time, not by jit-build
            # + AOT-compile time (step_latency_ms keeps its historical
            # include-compile meaning: the root phase's start)
            with _trace.phase("train/dispatch") as disp:
                if self.localsgd_k or self._is_dgc():
                    loss, self.params, self.opt_state, self.buffers = \
                        compiled(self.params, self.opt_state, self.buffers,
                                 lr, rng, *batch_arrays)
                else:
                    loss = self._unpack_step(list(compiled(
                        self.params, self.opt_state, self.buffers, lr, rng,
                        *batch_arrays)), guarded, narmed, qleg)
                self.optimizer._step_count += 1
            with _trace.phase("train/finish") as fin:
                return self._finish_step(loss, root, disp, fin)
        except BaseException:
            # the failing step still leaves its span (the very step a
            # trace gets pulled for); a stale handle must not leak into
            # the next step's _finish_step
            sp = self._step_span
            if sp is not None:
                sp.end(error=True)
                self._step_span = None
            raise

    def _unpack_step(self, out, guarded, narmed, qleg):
        """Fixed unpack order matching _build's packing: loss, state,
        then the optional legs — outputs / numerics stats / finite.
        Returns the loss."""
        loss = out.pop(0)
        self.params = out.pop(0)
        self.opt_state = out.pop(0)
        self.buffers = out.pop(0)
        if self.return_outputs:  # ctor rejects localsgd/dgc combinations
            self.last_outputs = jax.tree_util.tree_map(Tensor, out.pop(0))
        nstats = out.pop(0) if narmed else None
        finite = out.pop(0) if guarded else None
        if qleg:
            # the quantization-error norm stays device-resident until
            # quantize_error()/stats() asks for it — no new per-step
            # host sync
            self._qerr_device = out.pop(0)
        if nstats is not None:
            # keep the stats leg device-resident; the host fetch happens
            # only every FLAGS_numerics_interval steps
            self._numerics_note(nstats)
        if finite is not None:
            # DEFERRED verdict (docs/PERF.md): the skip already happened
            # on device if it happened at all — bank the device-resident
            # verdict instead of syncing on it here. The schedule
            # advances optimistically; _drain_verdicts rewinds it when a
            # skip is discovered, so the loss trajectory is bit-exact
            # with the old per-step fetch.
            self._pending_verdicts.append(
                (int(self.optimizer._step_count), finite))
        return loss

    def _finish_step(self, loss, root, disp, fin):
        """Monitor tail of train_step: optional FLAGS_benchmark device sync
        (so step_latency_ms measures device work) + the latency sample +
        the step-span/stats() accounting the MFU report reads. The times
        come from the step phases' own clock reads: the step ends where
        the sync ends (a `train/sync` phase) or, with no sync, where
        `train/finish` (`fin`) started. `step_ms` runs from the root
        phase's start and so includes any compile (the histogram's
        historical meaning); `exec_ms` runs from `train/dispatch`'s start
        and excludes it — that is what stats()/MFU accumulate, so a
        2-step run is not dominated by the first step's compile."""
        # the handle's schedule identity, captured BEFORE the benchmark
        # drain below may rewind the counter for this very step's skip
        sched = int(self.optimizer._step_count) - 1
        # the params this step produced are a NEW weight state (a
        # device-side skip still re-ran the program; the lineage tracks
        # states served/trained, not loss-improving updates)
        self.weight_version = self.weight_version.bump("step")
        sync_ms, end_ns = 0.0, fin.start_ns
        if _flags.get_flag("benchmark"):
            with _trace.phase("train/sync") as sync:
                if hasattr(loss, "block_until_ready"):
                    loss.block_until_ready()  # lint: allow(step-loop-host-sync)
                _BENCH_SYNC.labels(site="trainer").inc()
                # the device is drained anyway: settle pending guard
                # verdicts for free (same-call skip visibility under
                # FLAGS_benchmark, exactly the pre-deferral semantics);
                # deliver=True — this raise reaches train_step's caller
                self._drain_verdicts(force=True, deliver=True)
            sync_ms, end_ns = sync.ms, sync.end_ns
        step_ms = (end_ns - root.start_ns) / 1e6
        exec_ms = (end_ns - disp.start_ns) / 1e6
        if _monitor.is_enabled():
            _STEP_MS.labels(site="trainer").observe(step_ms)
        self._step_count += 1
        self._step_ms_sum += exec_ms
        self._sync_ms_sum += sync_ms
        sp = self._step_span
        if sp is not None:
            sp.end(sync_ms=sync_ms, step_ms=step_ms, exec_ms=exec_ms)
            self._step_span = None
        if self._perf_ledger is not None:
            self._ledger_step(step_ms, exec_ms, sync_ms)
        if self._async:
            from . import async_dispatch as _async_mod

            return _async_mod.StepHandle(loss, sched, trainer=self)
        return Tensor(loss)

    # -- perf ledger (FLAGS_perf_ledger) ---------------------------------------
    def _ledger_step(self, step_ms, exec_ms, sync_ms):
        """Armed-only per-step perf-ledger feed: the regression sentinel
        sees every step's wall times + t_exec-windowed MFU; a JSONL row
        (sig + mesh fingerprint) lands every FLAGS_perf_ledger_interval
        steps. A step that resolved a compile is recorded (``cold: 1``)
        but kept OUT of the baseline — its jit-build wall time is not
        the steady state the sentinel guards. Host-side bookkeeping only
        — the compiled step is the disarmed one."""
        m = {"step_ms": step_ms, "exec_ms": exec_ms, "sync_ms": sync_ms}
        if self._perf_cold:
            m["cold"] = 1
        entry = (self._cost_entries.get(self._last_sig)
                 or _costs.get("trainer", self._last_sig)
                 if self._last_sig else None)
        flops = entry.get("flops") if entry else None
        peak = _costs.peak_flops()
        if flops and exec_ms and peak:
            m["mfu"] = float(flops) / ((exec_ms / 1e3) * peak)
            m["flops_per_step"] = flops
        if entry and entry.get("bytes_accessed"):
            m["bytes_per_step"] = entry["bytes_accessed"]
        self._perf_ledger.on_step("trainer", m, sig=self._last_sig,
                                  mesh=self._perf_mesh_fp,
                                  check=not self._perf_cold)

    # -- quantized-reduce observability ----------------------------------------
    def quantize_error(self):
        """Host-fetch the last quantized step's global quantization-error
        L2 norm — the error-feedback residual about to be re-injected —
        and publish the lazy ``quantize_error_norm`` gauge. None until a
        FLAGS_quantized_allreduce step has run; between calls the scalar
        stays device-resident (no per-step host sync)."""
        if self._qerr_device is None:
            return None
        val = float(np.asarray(self._qerr_device))
        if _monitor.is_enabled() and np.isfinite(val):
            from . import compress as _compress

            _compress.error_gauge().set(val)
        return val

    # -- numerics telescope ----------------------------------------------------
    def _numerics_note(self, nstats):
        """Bank the step's device-resident stats leg; fetch to host only
        every FLAGS_numerics_interval steps — between fetches the arrays
        never cross the device boundary."""
        self._numerics_seen += 1
        self._numerics_last_device = nstats
        self._numerics_last_host = None
        interval = max(1, int(_flags.get_flag("numerics_interval", 1)))
        if self._numerics_seen % interval == 0:
            self.numerics_fetch()

    def numerics_fetch(self):
        """Fetch the latest on-device numerics stats to the host, feed
        the drift detectors, and return the host dict (STAT_KEYS ->
        np arrays, rows in ``sorted(self.params)`` order) — or None when
        FLAGS_numerics never armed a step. Idempotent per step (the
        parity harness force-fetches after every step without double-
        observing); emits a ``numerics/fetch`` span."""
        if self._numerics_last_host is not None:
            return self._numerics_last_host
        nstats = self._numerics_last_device
        if nstats is None:
            return None
        from ..monitor import numerics as _numerics_mod

        if self._numerics is None:
            # sorted order — matching _build's stat_layers (see there)
            self._numerics = _numerics_mod.NumericsMonitor(
                sorted(self.params), source="trainer")
        with _trace.span("numerics/fetch", subsystem="trainer",
                         step=int(self.optimizer._step_count)):
            if _monitor.is_enabled():
                with _monitor.timed(
                        _numerics_mod._metrics()["fetch_ms"]):
                    host = jax.device_get(nstats)
            else:
                host = jax.device_get(nstats)
        host = {k: np.asarray(v) for k, v in host.items()}
        self._numerics_last_host = host
        # stamp anomalies with the OPTIMIZER step — the same clock the
        # train_step/numerics-fetch spans carry, so a crash bundle's
        # anomaly cross-references its span tree (skipped guard steps
        # repeat a step number; that IS the schedule position retried)
        self._numerics.observe(host, step=int(self.optimizer._step_count))
        return host

    def stats(self):
        """Trainer observability snapshot: step counts/wall time joined
        with the device cost registry into an MFU estimate.

        ``mfu`` = per-step executable FLOPs (XLA ``cost_analysis()``,
        captured at compile under site="trainer") / (average measured
        step wall seconds × device peak FLOP/s). The flops source is the
        compiled train-step executable itself — forward+backward+update,
        exactly what ran — not an analytic 6·N·tokens formula. None until
        both a step has run and the cost registry holds this batch
        signature's entry (FLAGS_trace=1 or aot_build() populate it)."""
        # settle deferred guard verdicts first: the skip counters below
        # must reflect every dispatched step (one cheap device_get — by
        # stats() time the steps in question have long completed)
        self.guard_sync()
        # THIS trainer's entry first: the site-global table keys by batch
        # signature only, which two trainers over different models can
        # share (tools/metrics_dump.py --all does exactly that)
        entry = (self._cost_entries.get(self._last_sig)
                 or _costs.get("trainer", self._last_sig)
                 if self._last_sig else None)
        n = self._step_count
        avg_ms = self._step_ms_sum / n if n else None
        flops = entry.get("flops") if entry else None
        peak = _costs.peak_flops()
        mfu = None
        if flops and avg_ms and peak:
            mfu = float(flops) / ((avg_ms / 1e3) * peak)
        return {
            "steps": n,
            "step_ms_total": self._step_ms_sum,
            "step_ms_avg": avg_ms,
            "batch_sig": self._last_sig,
            "flops_per_step": flops,
            "hbm": ({k: entry[k] for k in ("peak_bytes", "argument_bytes",
                                           "output_bytes", "temp_bytes")
                     if k in entry} if entry else None),
            "peak_flops": peak,
            "mfu": mfu,
            "breakdown": {
                "sync_ms_total": self._sync_ms_sum,
                "dispatch_ms_total": max(
                    0.0, self._step_ms_sum - self._sync_ms_sum),
                "nonfinite_skipped_total": self._nonfinite_total,
                "nonfinite_streak": self._nonfinite_streak,
                # deferred-guard accounting (docs/PERF.md): host syncs
                # spent on verdicts and how far the host ran ahead
                "verdict_fetches": self._verdict_fetches,
                "verdict_window": self._async_window,
                "window_max_depth": self._window_max_depth,
                "prefetch_hits": self._prefetch_hits,
            },
            "device_memory": _costs.sample_device_memory(),
            # quantized-reduce health: the last step's EF-residual norm
            # (None unless FLAGS_quantized_allreduce built this trainer)
            "quantize_error_norm": (self.quantize_error()
                                    if self._quantized else None),
            # the numerics telescope's model-health snapshot (None until
            # FLAGS_numerics arms a step — the plain path never even
            # imports the module)
            "numerics": (self._numerics.snapshot()
                         if self._numerics is not None else None),
        }

    def sync_to_layer(self):
        """Write the (possibly sharded) params back into the Layer's tensors.

        Copies (never aliases) the trainer's arrays — the pipeline
        trainer's documented rule: the jitted step donates params, state
        AND buffers, so handing the live buffers to the Layer would let
        the next train_step invalidate the Layer's eager tensors on a
        donation-honoring backend. device_get lands an independent HOST
        copy (the pre-existing stage>=3 numpy-in-_data contract) — no
        re-upload, no second device-resident model."""
        named = dict(self.layer.named_parameters())
        for n, v in self.params.items():
            named[n]._data = jax.device_get(v)
        named_b = dict(self.layer.named_buffers())
        for n, v in self.buffers.items():
            named_b[n]._data = jax.device_get(v)

    # -- checkpoint / resume ---------------------------------------------------
    def _checkpoint_layout(self):
        """Logical [param, shard-spec] metadata for THIS trainer's state
        layout — the ``shard_specs`` leaf of every checkpoint it writes
        (CHECKPOINT_SCHEMA), and the restore target description when it
        reads one. Pure data (shapes, sizes, key sets) so it pickles
        through framework/io.py unchanged."""
        if self.localsgd_k:
            mode = "localsgd"
        elif self._is_dgc():
            mode = "dgc"
        elif self._quantized or self._shard_update:
            mode = "compressed"
        else:
            mode = "plain"
        return {
            "v": 1,
            "mode": mode,
            "ndp": int(self.mesh.shape[self.dp_axis]),
            "dp_axis": self.dp_axis,
            "shard_update": bool(self._shard_update),
            "quantized": bool(self._quantized),
            "sharding_stage": int(self.sharding_stage),
            "params": {k: {"shape": [int(d) for d in v.shape],
                           "size": int(v.size)}
                       for k, v in self.params.items()},
            "shard_ps": {k: int(ps) for k, ps in self._shard_ps.items()},
            "sharded_keys": {p: sorted(ks)
                             for p, ks in self._shard_state_keys.items()},
            "qar_eligible": sorted(self._qar_eligible),
        }

    def state_dict(self):
        """Host-side checkpoint of the FULL train state — params, buffers,
        optimizer moments, step counters, LR-scheduler state — gathered
        from whatever shardings are live. `paddle.save(trainer.state_dict(),
        path)` + `set_state_dict(paddle.load(path))` resumes bit-exact
        (asserted by tests/test_trainer_checkpoint.py). The snapshot also
        carries this trainer's shard-spec layout so it restores onto a
        DIFFERENT dp/mp factorization (docs/DISTRIBUTED.md "Elastic
        training")."""
        state = gather_train_state(self.params, self.opt_state,
                                   self.optimizer,
                                   layout=self._checkpoint_layout(),
                                   weight_version=self.weight_version)
        state["buffers"] = {k: _host_gather(v)
                            for k, v in self.buffers.items()}
        return state

    def set_state_dict(self, state):
        """Restore a state_dict() checkpoint, re-placing every array with
        the trainer's live shardings. A checkpoint written under a
        different dp/mp factorization (its ``shard_specs`` leaf differs
        from this trainer's layout) is re-laid-out on load —
        topology-aware resharding, counted in
        checkpoint_reshard_total{action}. Key mismatches (stale
        checkpoint vs a changed model) fail fast with names.

        Weight lineage (ISSUE 20): the restored state's
        ``__weight_version__`` leaf (absent — a pre-version checkpoint —
        reads as counter 0) re-joins this trainer's lineage at
        ``max(live, loaded) + 1`` so the counter stays monotone across
        restore AND replay, with origin ``restore`` (``reshard`` when
        the layouts differed and the moments were re-laid-out)."""
        src = state.get("shard_specs")
        layout = self._checkpoint_layout()
        resharded = src is not None and _layouts_differ(src, layout)
        gp_bucket = "reshard" if resharded else "ckpt_restore"
        with (self._goodput.bucket(gp_bucket)
              if self._goodput is not None
              else contextlib.nullcontext()):
            self.params, self.opt_state = restore_train_state(
                state, self.p_shardings, self.s_shardings, self.optimizer,
                layout=layout)
            _validate_state_keys("buffers", state.get("buffers", {}),
                                 self.b_shardings)
            self.buffers = {k: owned_device_put(jnp.asarray(v),
                                                self.b_shardings[k])
                            for k, v in state.get("buffers", {}).items()}
        loaded = _lineage.WeightVersion.from_dict(
            state.get("__weight_version__"),
            run_id=self.weight_version.run_id)
        self.weight_version = _lineage.WeightVersion(
            self.weight_version.run_id,
            max(self.weight_version.counter, loaded.counter) + 1,
            "reshard" if resharded else "restore")
        if resharded and self._goodput is not None:
            self._goodput.count("reshard")

    # -- elastic resize (FLAGS_elastic; docs/DISTRIBUTED.md) -------------------
    def resize(self, mesh):
        """Elastic topology change in place: drain the in-flight window,
        snapshot the live state at its logical shapes, swap the mesh,
        and re-place everything under the new dp factorization. The next
        train_step builds the step for the new mesh (the compiled store
        is cleared; a persistent jax compile cache, where on, is keyed by
        the program itself).

        Requires FLAGS_elastic at construction (the flag is structural);
        localsgd/DGC are rejected — their per-rank replicas/residuals
        have no topology-independent logical form. [dp, shard] moments
        re-lay bit-exactly; __qar_residual__ EF residuals fold their
        summed pending correction into rank 0 of the new factorization
        (counted residual_fold — total correction preserved, per-rank
        distribution is not)."""
        if self._goodput is None:
            return self._resize_impl(mesh)
        # goodput `reshard` bucket + event count around the whole
        # drain/snapshot/re-place leg (FLAGS_goodput; ISSUE 20)
        with self._goodput.bucket("reshard"):
            self._goodput.count("reshard")
            return self._resize_impl(mesh)

    def _resize_impl(self, mesh):
        self._elastic_active()
        if not self._elastic:
            raise RuntimeError(
                "SpmdTrainer.resize requires FLAGS_elastic=1 at trainer "
                "construction — the flag is structural (it keys every "
                "executable); build elastic trainers from the start")
        if self.localsgd_k or self._is_dgc():
            raise NotImplementedError(
                "resize() is not supported with localsgd/DGC per-rank "
                "state (no topology-independent logical form)")
        if self.dp_axis not in mesh.axis_names:
            raise ValueError(
                f"replacement mesh has axes {mesh.axis_names}, missing "
                f"this trainer's dp axis {self.dp_axis!r}")
        # drain: settle every deferred verdict (and surface a pending
        # FloatingPointError) before the state is captured
        self._drain_verdicts(force=True, deliver=True)
        state = self.state_dict()
        src = state["shard_specs"]
        old_fp = _aot.mesh_fingerprint(self.mesh)
        self.mesh = mesh
        # executables are keyed WITHOUT mesh identity (_exec_key) — a
        # stale store would silently run the old factorization's program
        self._compiled = None
        self._compiled_store.clear()
        self._prefetched = None
        self._cost_entries = {}
        if self._perf_ledger is not None:
            self._perf_mesh_fp = _aot.mesh_fingerprint(mesh)
        # logicalize the snapshot, then let _place_state re-derive the
        # whole placement vocabulary (shard_ps/sharded keys/zero
        # residuals) for the new mesh — a from-scratch layout of the
        # logical values, so moments land bit-exact
        folds = {}
        opt_l = {}
        for pname, st in state["opt_state"].items():
            if pname == "__step__":
                opt_l[pname] = st
                continue
            if pname == "__qar_residual__":
                for k, v in st.items():
                    folds[k] = np.asarray(v).sum(axis=0)
                continue
            sk = set((src or {}).get("sharded_keys", {}).get(pname, ()))
            sub = {}
            for k, v in st.items():
                arr = np.asarray(v)
                if k in sk:
                    meta = src["params"][pname]
                    arr = arr.reshape(-1)[:int(meta["size"])] \
                             .reshape(tuple(meta["shape"]))
                    _note_reshard("moment_reshard")
                sub[k] = arr
            opt_l[pname] = sub
        self.params = {k: np.asarray(v)
                       for k, v in state["params"].items()}
        self.buffers = {k: np.asarray(v)
                        for k, v in state["buffers"].items()}
        self.opt_state = opt_l
        self._shard_ps = {}
        self._shard_state_keys = {}
        self._place_state()
        if folds and "__qar_residual__" in self.opt_state:
            ndp = int(mesh.shape[self.dp_axis])
            res = {}
            for name, sh in self.s_shardings["__qar_residual__"].items():
                buf = np.zeros((ndp,) + folds[name].shape, np.float32)
                buf[0] = folds[name]
                res[name] = owned_device_put(buf, sh)
                _note_reshard("residual_fold")
            self.opt_state["__qar_residual__"] = res
        # the re-placed params are a new weight state in this lineage
        self.weight_version = self.weight_version.bump("reshard")
        _blackbox.note("trainer_resize", old_mesh=str(old_fp),
                       new_mesh=str(_aot.mesh_fingerprint(mesh)),
                       ndp=int(mesh.shape[self.dp_axis]))
        return self


def data_parallel_step_fn(layer, optimizer, loss_fn, mesh=None, **kw):
    return SpmdTrainer(layer, optimizer, loss_fn, mesh=mesh, **kw)


# -- shared checkpoint helpers (SpmdTrainer + PipelineTrainer) ----------------

def _host_gather(v):
    """device_get that stays correct on multi-process meshes: arrays spanning
    non-addressable devices gather via process_allgather."""
    try:
        return np.asarray(jax.device_get(v))
    except RuntimeError:
        from jax.experimental import multihost_utils

        # tiled=True: a global array sharded across processes assembles
        # into its global shape (non-tiled gather of non-fully-addressable
        # arrays is rejected by jax); fully-replicated arrays pass through
        return np.asarray(multihost_utils.process_allgather(v, tiled=True))


def _validate_state_keys(what, got, expected):
    missing = sorted(set(expected) - set(got))
    unexpected = sorted(set(got) - set(expected))
    if missing or unexpected:
        raise ValueError(
            f"checkpoint {what} mismatch — missing: {missing or 'none'}, "
            f"unexpected: {unexpected or 'none'} (stale checkpoint for a "
            "changed model?)")


def gather_train_state(params, opt_state, optimizer, layout=None,
                       weight_version=None):
    """Host-side {params, opt_state, step, lr_scheduler} snapshot.

    `layout` (SpmdTrainer._checkpoint_layout()) stamps the writer's
    logical [param, shard-spec] metadata into the snapshot's
    ``shard_specs`` leaf (CHECKPOINT_SCHEMA) so restore_train_state can
    re-lay-out onto a different dp/mp factorization; None (the
    PipelineTrainer / pre-elastic path) writes a same-topology-only
    checkpoint, exactly as before. `weight_version`
    (framework/lineage.py) stamps the writer's lineage into the
    ``__weight_version__`` leaf; None omits it (the checkpoint loads as
    version 0 — the pre-version contract)."""
    lr = optimizer._lr
    out = {
        "params": {k: _host_gather(v) for k, v in params.items()},
        "opt_state": {
            pname: (_host_gather(st) if pname == "__step__"
                    else {k: _host_gather(v) for k, v in st.items()})
            for pname, st in opt_state.items()},
        "optimizer_step_count": int(optimizer._step_count),
        "lr_scheduler": (lr.state_dict()
                         if hasattr(lr, "state_dict") else None),
        "shard_specs": layout,
    }
    if weight_version is not None:
        out["__weight_version__"] = weight_version.to_dict()
    return out


def _layouts_differ(src, dst):
    """Do two _checkpoint_layout() dicts describe different opt-state
    topologies? Only the fields that change the PLACED form matter —
    ndp alone is harmless for logical-shaped (plain/ZeRO) state."""
    return any(src.get(k) != dst.get(k)
               for k in ("mode", "ndp", "shard_ps", "sharded_keys",
                         "qar_eligible"))


def _reshard_opt_state(opt_host, src, dst):
    """Transform a host opt_state snapshot written under layout `src`
    into the placed form layout `dst` expects (ISSUE 19 topology-aware
    resharding; docs/DISTRIBUTED.md "Elastic training").

    [dp, shard] moments re-flatten to their logical param shape and
    re-pad to the destination factorization — bit-exact, the padding is
    zeros the sharded update never reads. ``__qar_residual__`` EF
    residuals are genuinely per-rank: each one is folded (summed over
    the writer's ranks) into rank 0 of the destination — the TOTAL
    pending error-feedback correction is preserved exactly, its per-rank
    distribution is not — or deterministically zeroed/dropped when only
    one side runs quantized. Every action lands in
    checkpoint_reshard_total{action}."""
    if src.get("mode") in ("localsgd", "dgc") \
            or dst.get("mode") in ("localsgd", "dgc"):
        raise ValueError(
            "cross-topology restore of localsgd/DGC state is not "
            "supported: per-rank replicas/residuals have no "
            "topology-independent logical form (docs/DISTRIBUTED.md)")
    ndp_t = int(dst["ndp"])
    out = {}
    for pname, st in opt_host.items():
        if pname == "__step__":
            out[pname] = st
            _note_reshard("step_passthrough")
            continue
        if pname == "__qar_residual__":
            continue   # handled below against dst's eligibility set
        src_sk = set(src.get("sharded_keys", {}).get(pname, ()))
        dst_sk = set(dst.get("sharded_keys", {}).get(pname, ()))
        meta = dst.get("params", {}).get(pname) \
            or src.get("params", {}).get(pname)
        sub = {}
        for k, v in st.items():
            arr = np.asarray(v)
            if k in src_sk:
                # placed [ndp_s, ps_s] -> logical (padding is zeros)
                arr = arr.reshape(-1)[:int(meta["size"])] \
                         .reshape(tuple(meta["shape"]))
            if k in dst_sk:
                ps_t = int(dst["shard_ps"][pname])
                flat = np.pad(arr.reshape(-1),
                              (0, ps_t * ndp_t - arr.size))
                sub[k] = flat.reshape(ndp_t, ps_t)
                _note_reshard("moment_reshard" if k in src_sk
                              else "moment_shard")
            else:
                sub[k] = arr
                if k in src_sk:
                    _note_reshard("moment_unshard")
        out[pname] = sub
    dst_eligible = list(dst.get("qar_eligible", ()))
    src_res = opt_host.get("__qar_residual__", {})
    if dst_eligible:
        res = {}
        for name in dst_eligible:
            meta = dst.get("params", {}).get(name) \
                or src.get("params", {}).get(name)
            shape = (ndp_t,) + tuple(meta["shape"])
            buf = np.zeros(shape, np.float32)
            if name in src_res:
                # fold: the summed pending EF correction lands on rank 0
                buf[0] = np.asarray(src_res[name]).sum(axis=0)
                _note_reshard("residual_fold")
            else:
                _note_reshard("residual_zero")
            res[name] = buf
        out["__qar_residual__"] = res
    dropped = set(src_res) - set(dst_eligible)
    if dropped:
        _note_reshard("residual_drop", n=len(dropped))
    return out


def restore_train_state(state, p_shardings, s_shardings, optimizer,
                        layout=None):
    """Re-place a gather_train_state snapshot onto live shardings; restores
    step counters and LR-scheduler state. Returns (params, opt_state).

    With `layout` (the DESTINATION trainer's _checkpoint_layout()) and a
    snapshot that carries its writer's ``shard_specs``, a checkpoint
    written under a different dp/mp factorization is re-laid-out first
    (_reshard_opt_state) — [dp, shard] moments bit-exact, EF residuals
    folded or zeroed, every action counted. Either side missing keeps
    the pre-elastic same-topology contract."""
    opt_host = state["opt_state"]
    src = state.get("shard_specs")
    if src is not None and layout is not None \
            and _layouts_differ(src, layout):
        opt_host = _reshard_opt_state(opt_host, src, layout)
    _validate_state_keys("params", state["params"], p_shardings)
    _validate_state_keys("opt_state", opt_host, s_shardings)
    params = {k: owned_device_put(jnp.asarray(v), p_shardings[k])
              for k, v in state["params"].items()}
    opt_state = {
        pname: (owned_device_put(jnp.asarray(st), s_shardings[pname])
                if pname == "__step__"
                else {k: owned_device_put(jnp.asarray(v),
                                          s_shardings[pname][k])
                      for k, v in st.items()})
        for pname, st in opt_host.items()}
    optimizer._step_count = int(state.get("optimizer_step_count", 0))
    lr = optimizer._lr
    if state.get("lr_scheduler") and hasattr(lr, "set_state_dict"):
        lr.set_state_dict(state["lr_scheduler"])
    return params, opt_state


def spmd_trainer_from_plan(config, layer, optimizer, loss_fn=None):
    """Realize a plan-search emission (analysis/plan_search.emit,
    ``kind="spmd"``) as a live :class:`SpmdTrainer`.

    The config is plain data — this function imports nothing from the
    analysis layer, so the plain-trainer closure stays planner-free.
    ``config["flags"]`` must already be SET: trainer construction
    consumes them (the _resolve_compress contract), so a mismatch here
    would silently build a different trainer than the plan scored —
    instead it raises naming the flag."""
    from .. import flags as _flags
    from .mesh import build_mesh
    from .split import collect_spmd_specs

    if config.get("kind") != "spmd":
        raise ValueError(
            f"config kind {config.get('kind')!r} is not 'spmd' — "
            "stage_graph configs realize via "
            "distributed/stage.py pipeline_trainer_from_plan")
    for name, want in (config.get("flags") or {}).items():
        got = bool(_flags.get_flag(name, False))
        if got != bool(want):
            raise ValueError(
                f"plan config wants FLAGS_{name}={want} but the process "
                f"has {got} — set the flag BEFORE realizing (trainer "
                "construction consumes it)")
    mesh_cfg = config["mesh"]
    import jax

    shape = tuple(int(s) for s in mesh_cfg["shape"])
    n = 1
    for s in shape:
        n *= s
    mesh = build_mesh(shape, tuple(mesh_cfg["axes"]),
                      devices=jax.devices()[:n])
    extra = collect_spmd_specs(layer) \
        if config.get("spmd", {}).get("tensor_parallel") else None
    return SpmdTrainer(layer, optimizer, loss_fn=loss_fn, mesh=mesh,
                       extra_param_specs=extra or None)
