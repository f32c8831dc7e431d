"""Ahead-of-time compilation and compile telemetry for every jit site.

The warm-start entry points — ``Program.aot_compile``,
``SpmdTrainer.aot_build``, ``ServingEngine.warmup`` — lower a program
from shape specs and compile it NOW, in memory, so the first live call
pays no compile. This module is their shared building block:

- ``compile_cached(jitted, args, force=)``: not forced, the jit itself
  comes back untouched (``"bypass"``: it compiles lazily on its first
  call, exactly as a bare ``jax.jit``); forced, it is lowered and
  compiled eagerly (``"fresh"``).
- ``CachedJit`` / ``cached_jit``: a ``jax.jit`` lookalike with
  ``warm(*specs)`` and an in-process table of executables per call
  signature. Nothing warmed and FLAGS_trace off: every call goes straight
  to the wrapped jit.
- an executable built from specs that rejects a live call
  (layout/sharding drift) falls back to the plain jit for that signature
  instead of crashing the caller.

Nothing here touches the disk. A compile cache that outlives the process
is jax's own: ``paddle.enable_compile_cache()`` (core/device.py) is the
one place it is turned on, and an eager compile here reads and feeds it
like any other (docs/AOT.md has the serve-deploy recipe).

Telemetry (paddle_tpu.monitor): the shared ``compile_cache_total`` family
carries a ``source`` label — ``memory`` (in-process hit) or ``fresh``
(an XLA compile was asked for) — beside ``compile_total`` and
``compile_ms``.
"""
import functools

import numpy as np
import jax

from .. import flags as _flags
from .. import monitor as _monitor
# the dotted form FIRST: it imports the paddle_tpu.trace module (the
# package attribute may still be the paddle.trace math op at this point)
from ..trace import costs as _costs
from .. import trace as _trace
from ..monitor import blackbox_lazy as _blackbox  # import-free recorder facade (ISSUE 12)
from ..profiler import RecordEvent as _RecordEvent

__all__ = ["args_signature", "mesh_fingerprint", "compile_cached",
           "CachedJit", "cached_jit", "executable_of", "named"]

# the compile_cache_total/compile_total families are DECLARED by their
# call sites (static/, distributed/spmd.py) with matching labels; these
# handles resolve to the same registry metrics
_COMPILE_CACHE = _monitor.counter(
    "compile_cache_total",
    "jit-cache lookups by feed-signature (event: hit|miss; source: "
    "memory|fresh)", labelnames=("site", "event", "sig", "source"))
_COMPILES = _monitor.counter(
    "compile_total", "XLA compiles asked for (in-memory hits excluded)",
    labelnames=("site",))
_COMPILE_MS = _monitor.histogram(
    "compile_ms", "wall time to obtain an executable", labelnames=("site",))


def record_compile(site, sig_label, source):
    """The ONE compile-cache telemetry mapping every site shares: a
    memory hit is hit/memory; everything else (an eager compile, or the
    bypass path's lazy jit that will compile on first call) is miss/fresh
    and counts in compile_total."""
    if source == "memory":
        if _monitor.is_enabled():
            _COMPILE_CACHE.labels(site=site, event="hit", sig=sig_label,
                                  source="memory").inc()
        return
    # flight-recorder tag for every non-memory resolution: compiles are
    # exactly the events a stalled run asks about
    _blackbox.note("compile", site=site, sig=sig_label, source=source)
    if _monitor.is_enabled():
        _COMPILE_CACHE.labels(site=site, event="miss", sig=sig_label,
                              source="fresh").inc()
    _COMPILES.labels(site=site).inc()


def executable_of(fn):
    """The underlying XLA executable of a compile_cached/CachedJit
    result, or None for bypass results (a plain lazy jit has no
    executable to cost-account until its first call)."""
    if isinstance(fn, _GuardedCompiled):
        return fn._compiled
    return None


def args_signature(args):
    """Hashable per-call signature: the pytree structure plus every leaf's
    (shape, dtype, weak_type) — the same specialization key jax.jit uses,
    so one entry per compiled program. ShapeDtypeStructs sign identically
    to the real arrays they describe (warm() relies on this); non-array
    leaves (python scalars, traced weakly) sign by type only."""
    leaves, treedef = jax.tree_util.tree_flatten(args)
    parts = []
    for x in leaves:
        shape = getattr(x, "shape", None)
        dtype = getattr(x, "dtype", None)
        if shape is not None and dtype is not None:
            parts.append((tuple(shape), str(dtype),
                          bool(getattr(x, "weak_type", False))))
        else:
            parts.append(("py", type(x).__name__))
    return treedef, tuple(parts)


def mesh_fingerprint(mesh):
    """Stable identity of a mesh's topology: axis names and sizes, device
    kinds, device and process counts — what the perf ledger keys its
    baselines on and what a resize or a stage replacement logs."""
    if mesh is None:
        return ("mesh", None)
    devs = list(np.asarray(mesh.devices).ravel())
    kinds = sorted({getattr(d, "device_kind", d.platform) for d in devs})
    return ("mesh", tuple(mesh.axis_names),
            tuple(int(mesh.shape[a]) for a in mesh.axis_names),
            tuple(kinds), len(devs), int(jax.process_count()))


def _canonical_specs(args):
    """Replace array leaves with ShapeDtypeStructs before lowering, so the
    lowered module is identical however the caller's arrays happen to be
    placed: a committed single-device array, an uncommitted eager result,
    and a warmup spec all lower to the same module. Only
    NamedShardings survive (they ARE program semantics — SPMD layouts);
    single-device/positional shardings are placement detail and dropped.
    Non-array leaves (python scalars) pass through and specialize weakly,
    exactly as a live call would."""
    from jax.sharding import NamedSharding

    def go(x):
        shape = getattr(x, "shape", None)
        dtype = getattr(x, "dtype", None)
        if shape is None or dtype is None:
            return x
        sh = getattr(x, "sharding", None)
        if not isinstance(sh, NamedSharding):
            sh = None
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sh,
                                    weak_type=bool(getattr(x, "weak_type",
                                                           False)))
    return jax.tree_util.tree_map(go, args)


class _GuardedCompiled:
    """An eagerly compiled executable with a recompile escape hatch: if
    it rejects a live call — it was built from specs, and the arrays'
    layout or sharding drifted from them — hand the signature back to
    the plain jit instead of crashing the caller."""

    __slots__ = ("_compiled", "_jit")

    def __init__(self, compiled, jitted):
        self._compiled = compiled
        self._jit = jitted

    def __call__(self, *args):
        compiled = self._compiled
        if compiled is None:
            return self._jit(*args)
        try:
            return compiled(*args)
        except (TypeError, ValueError):
            # pre-execution REJECTION only (signature/pytree/sharding
            # mismatch — raised before donation consumes any buffer):
            # fall back to the plain jit for good. Runtime failures
            # (XlaRuntimeError, OOM) propagate — retrying them with
            # already-donated inputs would destroy live state and mask
            # the real error.
            self._compiled = None
            return self._jit(*args)


def _goodput_compile():
    """`compile` wall-time attribution (FLAGS_goodput, ISSUE 20): a null
    context unless the goodput accountant is armed. Booked at THE
    compile chokepoint, so trainer warm starts, serving warmups, and
    elastic resize warm-restarts all attribute — nested inside the
    trainer's `step` bucket, the compile time pauses it (exclusive
    buckets). One flag read per compile; the disarmed path never imports
    monitor/goodput.py (manifest-lazy)."""
    import contextlib

    if not _flags.get_flag("goodput", False):
        return contextlib.nullcontext()
    from ..monitor import goodput as _goodput

    return _goodput.bucket("compile")


def compile_cached(jitted, example_args, *, force=False):
    """Obtain a callable for ``jitted`` at ``example_args`` (real arrays,
    or jax.ShapeDtypeStructs for data-free warmup).

    Returns ``(callable, source)``:

    - ``("bypass")`` — not forced: ``jitted`` itself, untouched (no
      lowering; jit compiles lazily on its first call);
    - ``("fresh")`` — ``force=True``, the warm-start APIs: lowered and
      compiled now, wrapped in a call-failure guard (an executable that
      rejects a live call falls back to the plain jit for good).
    """
    if not force:
        return jitted, "bypass"
    # the progress window brackets every eager XLA compile: a hung
    # compile leaves an ACTIVE, non-advancing aot/compile beacon for
    # the stall sentinel to name (monitor/blackbox.py)
    with _goodput_compile(), _blackbox.progress("aot/compile"):
        compiled = jitted.lower(*_canonical_specs(example_args)).compile()
    return _GuardedCompiled(compiled, jitted), "fresh"


def _name(fn, name):
    """`name` is what jax calls the program it traces from `fn` (the
    module `jit_<name>` of the lowered text, the compiled executable and
    the profiler's "XLA Modules" line): it reads `__name__` when it
    traces, not when `jax.jit` wraps."""
    try:
        fn.__name__ = fn.__qualname__ = name
    except (AttributeError, TypeError):
        pass    # a callable that takes no name keeps its own


def named(fn, name):
    """`fn` behind a function of that name (the caller's own is left as
    it is: it may be jitted elsewhere under another)."""
    @functools.wraps(fn)
    def program(*args, **kwargs):
        return fn(*args, **kwargs)

    _name(program, name)
    return program


class CachedJit:
    """A ``jax.jit`` lookalike that can be compiled ahead of time: per
    call-signature, lower once, compile, keep the executable in an
    in-process map. With nothing warmed and FLAGS_trace off, every call
    delegates straight to the wrapped jit after one empty-dict + flag
    check — behavior and cost identical to plain jit (the tier-1 gate
    pins it). Once warmed, each call pays a python-level signature
    flatten over the arg pytrees (~µs for a params+KV-cache tree) — well
    under 1% of a ms-scale decode step, but measurable; a
    latency-critical caller that truly has one static signature can hold
    the plain jit.

    The program is named ``<site>.<label>``, the pair the cost registry
    and ``record_compile`` key on, so that a profile's "XLA Modules" line
    says the same word (docs/OBSERVABILITY.md "Device scopes").

    ``warm(*specs)`` AOT-compiles one signature from
    ``jax.ShapeDtypeStruct`` specs (plus plain python scalars for
    weakly-typed args) without real data and without executing anything —
    the ServingEngine.warmup / SpmdTrainer.aot_build building block.
    """

    def __init__(self, fn=None, *, site, jit=None, label=None,
                 donate_argnums=(), sig_label=None, record_event=None):
        self._site = site
        self._label = label or getattr(fn, "__name__", "jit")
        if jit is None:
            jit = jax.jit(named(fn, f"{site}.{self._label}"),
                          donate_argnums=donate_argnums)
        else:
            _name(getattr(jit, "__wrapped__", None), f"{site}.{self._label}")
        self._jit = jit
        self._sig_label = sig_label  # callable(args) -> str, or None
        self._record_event = record_event or f"{site}/compile"
        self._store = {}
        self._cost_entries = {}   # sig -> trace.costs entry (exact per
        #                           signature: bucketed families differ)
        # wrapper-LOCAL execution accounting: two engines sharing the
        # 'serving' site must not average each other's program flops
        # (callers are effectively single-threaded per wrapper; these are
        # observability counters, not the registry's locked metrics)
        self._exec_calls = 0
        self._exec_flops = 0.0

    def lower(self, *args, **kwargs):
        return self._jit.lower(*args, **kwargs)

    def _label_of(self, args):
        return self._label if self._sig_label is None \
            else self._sig_label(args)

    def _compile(self, sig, args):
        with _RecordEvent(self._record_event), \
                _monitor.timed(_COMPILE_MS.labels(site=self._site)):
            # a warmed (or traced) signature must never retrace at call
            # time: always the eager compile
            compiled, source = compile_cached(self._jit, args, force=True)
        record_compile(self._site, self._label_of(args), source)
        # device cost registry: every executable this wrapper obtains
        # lands its cost_analysis()/memory_analysis() under (site,
        # program label); the exact per-signature entry is also kept so
        # executions of a bucketed family account each bucket's own flops
        entry = _costs.record(self._site, self._label_of(args),
                              executable_of(compiled))
        if entry is not None:
            self._cost_entries[sig] = entry
        self._store[sig] = compiled
        return compiled

    def warm(self, *specs):
        """Compile one signature ahead of time from shape specs. Returns
        True if a compile happened, False if that signature was already
        warm."""
        sig = args_signature(specs)
        if sig in self._store:
            return False
        self._compile(sig, specs)
        return True

    def __call__(self, *args):
        store = self._store
        if not store and not _trace.is_enabled():
            return self._jit(*args)
        sig = args_signature(args)
        compiled = store.get(sig)
        if compiled is None:
            if not _trace.is_enabled():
                return self._jit(*args)  # warmed, but not for this sig
            # FLAGS_trace forces the eager compile so the cost registry
            # sees an executable for every program
            compiled = self._compile(sig, args)
        else:
            record_compile(self._site, self._label_of(args), "memory")
        entry = self._cost_entries.get(sig)
        if entry is not None:   # wrapper-local: no lock on the hot path
            self._exec_calls += 1
            self._exec_flops += entry.get("flops", 0.0)
        return compiled(*args)

    def executed(self):
        """THIS wrapper's execution accounting: {"calls", "flops"} summed
        over every signature it dispatched (per-bucket exact). Empty
        until cost entries exist (FLAGS_trace / warm())."""
        return {"calls": self._exec_calls, "flops": self._exec_flops}


def cached_jit(fn=None, **kwargs):
    """Factory form of :class:`CachedJit` (accepts ``jit=`` for an
    already-built jit object, e.g. a jit(shard_map(...)) wrapper)."""
    return CachedJit(fn, **kwargs)
