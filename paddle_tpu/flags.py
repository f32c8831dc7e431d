"""Global flag registry.

Reference parity: paddle/fluid/platform/flags.cc (~40 process-level gflags, exposed to
Python as FLAGS_* via pybind/global_value_getter_setter.cc) and
paddle.set_flags/get_flags. Flags can be seeded from environment (FLAGS_xxx=...).
"""
import os

_REGISTRY = {}


def define_flag(name, default, help_str=""):
    env = os.environ.get("FLAGS_" + name)
    value = default
    if env is not None:
        if isinstance(default, bool):
            value = env.lower() in ("1", "true", "yes")
        elif isinstance(default, int):
            value = int(env)
        elif isinstance(default, float):
            value = float(env)
        else:
            value = env
    existing = _REGISTRY.get(name)
    if existing is not None:
        # Two real definitions disagreeing about the default is a bug:
        # whichever module imported first silently won (and its env
        # parsing keyed off ITS default's type). Raise instead — the
        # idempotent same-default path stays allowed, and entries a
        # set_flags() created before the defining module loaded
        # ("provisional": the user picked a value, never a default) are
        # adopted, not conflicted with.
        if not existing.get("provisional") \
                and repr(existing["default"]) != repr(default):
            raise ValueError(
                f"FLAGS_{name} re-defined with default {default!r} but "
                f"an earlier define_flag said {existing['default']!r} — "
                "conflicting defaults would be resolved by import order; "
                "one definition must own the default")
        # an explicit set_flags() made BEFORE the defining module loaded
        # wins: lazily-imported modules (monitor/numerics.py) define
        # their flags on first import, and defining must never clobber a
        # value the user already set
        value = existing["value"]
        if not help_str:
            help_str = existing["help"]
    _REGISTRY[name] = {"value": value, "default": default, "help": help_str}
    return value


def set_flags(flags):
    """paddle.set_flags parity."""
    for k, v in flags.items():
        k = k[6:] if k.startswith("FLAGS_") else k
        if k not in _REGISTRY:
            # provisional entry, NOT define_flag: an explicit set wins
            # over any FLAGS_* env var (exactly as it does for an
            # already-defined flag), and the defining module may load
            # later with the authoritative default + help (see
            # define_flag's provisional adoption)
            _REGISTRY[k] = {"value": v, "default": v, "help": "",
                            "provisional": True}
        else:
            _REGISTRY[k]["value"] = v


def get_flags(names):
    """paddle.get_flags parity."""
    if isinstance(names, str):
        names = [names]
    out = {}
    for k in names:
        key = k[6:] if k.startswith("FLAGS_") else k
        if key in _REGISTRY:
            out[k] = _REGISTRY[key]["value"]
    return out


def get_flag(name, default=None):
    e = _REGISTRY.get(name)
    return e["value"] if e else default


# core flags (platform/flags.cc parity where meaningful on TPU)
define_flag("check_nan_inf", False,
            "scan op outputs for NaN/Inf (flags.cc:44); SpmdTrainer builds "
            "its step with an on-device loss/grad finiteness check and "
            "SKIPS the update on a non-finite step (docs/ROBUSTNESS.md)")
define_flag("max_skip_steps", 3,
            "with FLAGS_check_nan_inf: how many CONSECUTIVE non-finite "
            "train steps may be skipped before train_step raises "
            "FloatingPointError (a transient loss spike recovers; a "
            "diverged run fails loudly)")
define_flag("sort_sum_gradient", False,  # lint: allow(orphan-flag) — reference-parity stub (flags.cc:527): tape accumulation is already deterministic here, kept for set_flags API compat
            "deterministic grad accumulation order (flags.cc:527); the "
            "TPU tape accumulates in recording order deterministically, "
            "so this is accepted-and-ignored for API compatibility")
define_flag("benchmark", False,
            "Executor.run blocks until fetches are device-complete so the "
            "monitor's step_latency_ms measures device work, not dispatch; "
            "each sync is counted as benchmark_sync_total")
define_flag("seed", 0,
            "initial global random seed: seeds the default RNG generator "
            "at process start (core/generator.py); paddle.seed() "
            "overrides it at runtime")
define_flag("trace_host_sync", "silent",
            "what Tensor._to_host does when a host pull (.numpy()/.item()) "
            "happens inside a jax trace: silent (jax's own tracer error), "
            "warn (explain the sync first), error (raise immediately). "
            "The analysis host-sync pass polices the compiled-in form.")
define_flag("numerics", False,
            "numerics telescope (monitor/numerics.py): SpmdTrainer builds "
            "its step with ONE fused on-device per-layer tensor-health "
            "aggregation (grad/param norms, update ratio, non-finite "
            "counts, quantile digest) feeding drift detectors; unset, the "
            "train step is bit-identical to the un-instrumented one. "
            "Defined here (not in the numerics module) so the trainer can "
            "gate on it without importing the telescope at all")
define_flag("numerics_interval", 1,
            "with FLAGS_numerics: fetch the on-device stats to the host "
            "every N train steps (the stats stay device-resident between "
            "fetches — no new per-step host sync)")
define_flag("quantized_allreduce", False,
            "EQuARX-style quantized gradient all-reduce "
            "(distributed/compress.py, docs/DISTRIBUTED.md): on the "
            "plain-dp SpmdTrainer path the per-step grad psum becomes an "
            "int8-wire reduce (stochastic rounding, fp32 accumulation) "
            "with per-layer error-feedback residuals riding the "
            "optimizer-state pytree. Read at TRAINER CONSTRUCTION (the "
            "residual state is laid out then) — changing it under a live "
            "trainer raises instead of silently mis-reducing. localsgd/"
            "DGC steps ignore it (they own their reduce), like the "
            "FLAGS_check_nan_inf carve-out. Unset, the trainer never "
            "imports the compress module and the step is byte-identical")
define_flag("quantized_allreduce_bits", 8,
            "wire width of the quantized all-reduce payload; 8 (int8) is "
            "the supported format — anything else fails loudly at "
            "trainer construction. Read at trainer construction")
define_flag("quantized_allreduce_min_size", 1024,
            "with FLAGS_quantized_allreduce: tensors smaller than this "
            "many elements (and all non-float gradients) skip "
            "quantization and stay on the exact fp32 reduce — the scale "
            "overhead and risk aren't worth <4KB of wire. Read at "
            "trainer construction")
define_flag("shard_weight_update", False,
            "arXiv:2004.13336-style cross-replica update sharding for "
            "plain dp (docs/DISTRIBUTED.md): reduce-scatter the grads, "
            "compute the optimizer update on each replica's 1/dp shard "
            "(optimizer moments stored sharded — ZeRO-2-like memory), "
            "all-gather the updated params; bit-compared EXACT against "
            "the replicated update by tools/parity_check.py. Composes "
            "with FLAGS_quantized_allreduce (the quantized exchange "
            "feeds the sharded update). Read at trainer construction; "
            "localsgd/DGC ignore it")
define_flag("async_dispatch", False,
            "double-buffered step dispatch (docs/PERF.md): SpmdTrainer "
            "returns a lazy StepHandle (distributed/async_dispatch.py), "
            "and the non-finite guard verdict is fetched in windows of "
            "FLAGS_async_window steps instead of per step. The trainer's "
            "alone (ServingEngine keeps a decode step in flight by "
            "itself). Read at TRAINER CONSTRUCTION — a post-construction "
            "toggle under a live trainer raises. Unset, the async module "
            "is never imported and behavior is byte-identical")
define_flag("async_window", 8,
            "with FLAGS_async_dispatch: how many steps the host may run "
            "ahead of the deferred non-finite-guard verdict fetch (the "
            "FLAGS_max_skip_steps/FloatingPointError contract holds — "
            "the host just learns about an on-device skip up to this "
            "many steps later). 1 = fetch every step (the non-async "
            "deferred-by-one behavior). Read at trainer construction")
define_flag("overlap_grad_comm", False,
            "with FLAGS_quantized_allreduce (quant-only mode): split the "
            "fused int8 gradient exchange into per-layer legs so XLA's "
            "scheduler can interleave the collective legs with backward "
            "compute (EQuARX hides the quantized exchange behind "
            "compute; docs/PERF.md overlap matrix). Changes the rounding "
            "rng per leg — parity-banded vs the fused bundle. Read at "
            "trainer construction; raises without quantized_allreduce "
            "or combined with shard_weight_update (already per-leg)")
define_flag("tpp_kernels", False,
            "TPP-style Pallas micro-kernel registry (ops/tpp.py, "
            "arXiv:2104.05755): GPT blocks route their fusion-hostile "
            "hot ops — the fused MLP block and the layernorm->matmul "
            "prologue — through blocked Pallas kernels (interpret-mode "
            "on CPU). Read at trace time in models/gpt.py; unset, the "
            "registry module is never imported and the traced program "
            "is byte-identical")
define_flag("mpmd", False,
            "MPMD stage-program runtime (distributed/stage.py, "
            "arXiv:2412.14374): PipelineTrainer schedules its stages as "
            "per-stage AOT-cached programs on their own mesh slices "
            "connected by typed, backpressured transfer edges (1F1B / "
            "F-then-B / interleaved tick orderings over the same edges), "
            "and DisaggregatedPool routes its prefill->decode hand-off "
            "over the same edge abstraction (compress=8 rides the "
            "EQuARX int8 row codec). Read at TRAINER/POOL CONSTRUCTION "
            "— a post-construction toggle under a live trainer raises. "
            "Unset, distributed/stage.py is never imported "
            "(manifest-lazy; analysis/import_graph.py) and behavior is "
            "byte-identical")
define_flag("paged_kv", False,
            "paged KV-cache + batched multi-LoRA serving "
            "(serving/paging.py, arXiv:2309.06180 recipe): ServingEngine "
            "replaces its dense [max_batch, max_seq] KV cache with a "
            "physical block pool + per-slot block tables — whole-budget "
            "reservation at admission (PagePoolFullError backpressure "
            "BEFORE any prefill compute), refcounted shared-prefix "
            "frames with copy-on-write boundary blocks, int8 cold-page "
            "compression (page_cold_steps=, EQuARX row codec), and "
            "named-adapter decode (load_adapter/submit(adapter=)) "
            "batched in the ONE jitted step via a gathered low-rank "
            "delta — no per-adapter programs, no recompiles. Read at "
            "ENGINE CONSTRUCTION — a post-construction toggle under a "
            "live paged engine raises. "
            "Unset, serving/paging.py is never imported (manifest-lazy; "
            "analysis/import_graph.py) and the engine is byte-identical")
define_flag("blackbox", False,
            "black-box flight recorder on/off (monitor/blackbox.py): "
            "progress beacons, the bounded event ring, and dump-bundle "
            "plumbing; off turns every beacon()/note() call site into "
            "one boolean check (tests/test_blackbox_gate.py pins "
            "<5us/call and zero drift). Defined here (not in the "
            "recorder module) so the monitor package can gate on it "
            "without importing the recorder at all — monitor/blackbox.py "
            "is manifest-lazy (analysis/import_graph.py)")
define_flag("flash_attention_block", 0,
            "force the flash-attention Pallas block size (128/256/512); "
            "0 = auto (largest of 512/256/128 dividing seq). For on-chip "
            "tuning sweeps: FLAGS_flash_attention_block=256 python bench.py")
define_flag("perf_ledger", False,
            "persistent perf ledger (monitor/perfledger.py, "
            "docs/OBSERVABILITY.md): trainer/engine/stage-graph/bench "
            "step telemetry (wall ms, MFU, collective bytes, dispatch "
            "fraction, latency digests) is appended as env-fingerprinted "
            "JSONL rows to FLAGS_perf_ledger_path, with an EMA/sigma "
            "regression sentinel firing perf_regression_total{site,"
            "metric}. DELIBERATELY NON-STRUCTURAL: the ledger only "
            "observes host-side timings and never changes any compiled "
            "program, so it does NOT join the executable keys (armed and "
            "disarmed runs train byte-identically — "
            "tests/test_perfledger_gate.py pins it). "
            "Unset, the ledger module is never imported and every hook "
            "is one boolean check. Defined here (not in the ledger "
            "module) so trainers can gate on it without importing it")
define_flag("perf_ledger_path", "",
            "with FLAGS_perf_ledger: path of the append-only JSONL "
            "ledger file. Appends are atomic (single write+flush+fsync "
            "per row) and readers tolerate a torn tail. Empty = rows "
            "are kept in-process only (sentinel "
            "and metrics still run; nothing persists)")
define_flag("perf_ledger_sigma", 4.0,
            "with FLAGS_perf_ledger: regression threshold — a step "
            "metric more than this many EMA standard deviations on the "
            "bad side of its per-(site,metric) baseline fires "
            "perf_regression_total and notes the blackbox ring")
define_flag("perf_ledger_warmup", 5,
            "with FLAGS_perf_ledger: observations of a (site,metric) "
            "series before the sentinel may fire (the EMA baseline "
            "needs points; the NumericsMonitor warmup contract)")
define_flag("perf_ledger_interval", 1,
            "with FLAGS_perf_ledger: append a ledger row every N "
            "observations per site (the sentinel still sees every "
            "observation; only row volume is throttled)")
define_flag("elastic", False,
            "elastic preemption-tolerant training "
            "(distributed/elastic.py supervisor + the spmd.py "
            "topology-aware checkpoint reshard, arXiv:2412.14374 "
            "posture): gather_train_state stamps logical [param, "
            "shard-spec] metadata into every checkpoint so "
            "restore_train_state re-lays-out [dp, shard] moments and "
            "__qar_residual__ EF residuals onto a DIFFERENT dp/mp "
            "factorization (checkpoint_reshard_total{action}), "
            "SpmdTrainer.resize(mesh) drains and re-places live state "
            "onto a replacement mesh, "
            "StageProgram.rebind/MpmdPipelineRunner.replace_stage swap "
            "one MPMD stage mesh without recompiling siblings, and "
            "ElasticSupervisor wires CheckpointSaver corrupt-fallback + "
            "blackbox crash bundles into retry-with-backoff resume on a "
            "shrunken mesh (elastic_resume_total{reason}). Read at "
            "TRAINER CONSTRUCTION — a post-construction toggle under a "
            "live trainer raises (_elastic_active). STRUCTURAL: the "
            "boolean joins _exec_key so an elastic world never aliases "
            "a plain executable. Unset, "
            "distributed/elastic.py is never imported (manifest-lazy; "
            "analysis/import_graph.py) and training is byte-identical")
define_flag("goodput", False,
            "goodput ledger + weight-version lineage metrics "
            "(monitor/goodput.py, docs/OBSERVABILITY.md): a per-run "
            "wall-clock accountant classifies every second into "
            "exclusive buckets {step, compile, ckpt_save, ckpt_restore, "
            "reshard, resume_backoff, stall, edge_wait, other} via hooks "
            "in the trainer/AOT path, checkpoint save/restore, the "
            "elastic supervisor, and the MPMD stage runtime — published "
            "as goodput_seconds_total{bucket} + goodput_fraction, one "
            "site=run/goodput perf-ledger row per run (FLAGS_perf_ledger "
            "also armed; goodput itself is sentinel-watched LOW_IS_BAD), "
            "and a blackbox dump provider naming the active bucket at "
            "crash time. Also gates the serving lineage families "
            "(serving_weight_version / serving_stale_sessions_total). "
            "DELIBERATELY NON-STRUCTURAL: host-side accounting only — "
            "it joins NO executable key (armed and disarmed runs share "
            "AOT entries and train byte-identically — "
            "tests/test_goodput_gate.py pins it). Unset, "
            "monitor/goodput.py is never imported and every hook is one "
            "cached boolean. Defined here (not in the accountant module) "
            "so hook sites can gate on it without importing it")
define_flag("goodput_stall_s", 2.0,
            "with FLAGS_goodput: an unattributed gap (no bucket active) "
            "at least this many seconds books as `stall`; shorter gaps "
            "book as `other` (loop/bookkeeping overhead)")
