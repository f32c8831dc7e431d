"""Persistent AOT executable cache: compile once per machine, not per process.

Every jit compile today is paid per-process — the Executor's jit cache
lives on the Program, SpmdTrainer rebuilds its step on the first
train_step, ServingEngine re-jits its whole program family on
construction. On a TPU v5e the GPT-2-small serving family compiles in about
50 s cold (chip run, PR 22), so a restarted server pays the full XLA
optimization bill before serving its first token. This module converts that into a
one-time cost: executables are lowered, compiled ONCE, serialized with
``jax.experimental.serialize_executable``, and content-addressed on disk;
every later process (same machine class, same jax) deserializes in
milliseconds instead of recompiling. Ahead-of-time specialization for
portability/efficiency is the Tensor Processing Primitives argument
(arXiv:2104.05755) applied at the executable level instead of the kernel
level.

Cache key: sha256 over the lowered StableHLO text (which already pins the
program, input avals, shardings, and donation), plus jax version, backend
platform + platform version, compile-relevant FLAGS (``use_bfloat16``,
``flash_attention_block``), and per-site extras (mesh topology
fingerprints, donation tuples, program labels).

Safety contract:

- ``FLAGS_jit_cache_dir`` unset (the default): NOTHING here runs — call
  sites get their plain ``jax.jit`` object back untouched; no lowering,
  no hashing, no disk I/O (tests/test_aot_cache_gate.py pins this).
- corrupt or stale entries (truncated file, different jax/platform
  version, undeserializable payload): silently evicted and recompiled —
  a bad cache file must never crash training or serving.
- a deserialized executable that rejects its first live call (layout or
  sharding drift the key missed) falls back to the plain jit for that
  signature and evicts the entry.
- writes are single-writer safe for concurrent processes: serialize to a
  private temp file, ``os.replace`` into place (atomic on POSIX).
- ``FLAGS_jit_cache_max_bytes`` caps the directory byte size with LRU
  eviction (mtime recency, bumped on every hit); the newest entry is
  always kept so one giant executable cannot disable its own cache.

Telemetry (paddle_tpu.monitor): the shared ``compile_cache_total`` family
carries a ``source`` label — ``memory`` (in-process hit), ``disk``
(deserialized from this cache), ``fresh`` (real XLA compile) — plus
``aot_serialize_ms``/``aot_deserialize_ms``/``aot_bytes`` histograms,
``aot_store_total{site,event}`` and ``aot_evict_total{reason}`` counters.

Warm-start entry points built on this module: ``Program.aot_compile``,
``SpmdTrainer.aot_build``, ``ServingEngine.warmup``, and the
``tools/aot_warm.py`` CLI (docs/AOT.md has the serve-deploy recipe).
"""
import os
import pickle
import time
import uuid

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

__all__ = ["cache_dir", "enabled", "args_signature", "mesh_fingerprint",
           "compile_cached", "CachedJit", "cached_jit", "executable_of"]

_flags.define_flag(
    "jit_cache_dir", "",
    "persistent AOT executable cache directory shared across processes "
    "(framework/aot.py); empty = disabled: no lowering, hashing or disk "
    "I/O on any compile path")
_flags.define_flag(
    "jit_cache_max_bytes", 1 << 30,
    "LRU byte-size cap for FLAGS_jit_cache_dir (oldest entries evicted; "
    "the newest entry is always kept)")

_FORMAT = 1
_SUFFIX = ".aotx"

#: flags whose value changes what a trace produces without necessarily
#: changing the python call signature — part of every cache key
_KEYED_FLAGS = ("use_bfloat16", "flash_attention_block")

# the compile_cache_total/compile_total families are DECLARED by their
# call sites (static/, distributed/spmd.py) with matching labels; these
# handles resolve to the same registry metrics
_COMPILE_CACHE = _monitor.counter(
    "compile_cache_total",
    "jit-cache lookups by feed-signature (event: hit|miss; source: "
    "memory|disk|fresh)", labelnames=("site", "event", "sig", "source"))
_COMPILES = _monitor.counter(
    "compile_total", "fresh XLA compiles (disk/memory cache hits excluded)",
    labelnames=("site",))
_COMPILE_MS = _monitor.histogram(
    "compile_ms", "wall time to obtain an executable (fresh compile, or "
    "lower+deserialize on an AOT-cache hit)", labelnames=("site",))
_SER_MS = _monitor.histogram(
    "aot_serialize_ms", "executable serialize wall time",
    labelnames=("site",))
_DES_MS = _monitor.histogram(
    "aot_deserialize_ms", "executable deserialize wall time",
    labelnames=("site",))
_BYTES_BUCKETS = (1 << 10, 1 << 12, 1 << 14, 1 << 16, 1 << 18, 1 << 20,
                  1 << 22, 1 << 24, 1 << 26, 1 << 28, 1 << 30)
_AOT_BYTES = _monitor.histogram(
    "aot_bytes", "serialized executable entry size",
    labelnames=("site", "event"), buckets=_BYTES_BUCKETS)
_STORE_TOTAL = _monitor.counter(
    "aot_store_total", "cache-entry writes by outcome (ok|error); error = "
    "the executable could not be serialized/written (it still runs, the "
    "next process just recompiles)", labelnames=("site", "event"))
_EVICT_TOTAL = _monitor.counter(
    "aot_evict_total", "cache entries dropped (corrupt|version|lru) and "
    "executables disabled after rejecting a live call (call; also counts "
    "in-memory warmed executables with no disk entry)",
    labelnames=("reason",))


def record_compile(site, sig_label, source):
    """The ONE compile-cache telemetry mapping every site shares: a disk
    load is event=hit/source=disk; a memory hit is hit/memory; everything
    else (fresh compile, or the bypass path's lazy jit that will compile
    on first call) is miss/fresh and counts in compile_total."""
    if source == "memory":
        if _monitor.is_enabled():
            _COMPILE_CACHE.labels(site=site, event="hit", sig=sig_label,
                                  source="memory").inc()
        return
    # flight-recorder tag for every non-memory resolution: disk loads and
    # fresh compiles are exactly the events a stalled run asks about
    _blackbox.note("compile", site=site, sig=sig_label, source=source)
    if _monitor.is_enabled():
        _COMPILE_CACHE.labels(
            site=site, event="hit" if source == "disk" else "miss",
            sig=sig_label,
            source="disk" if source == "disk" else "fresh").inc()
    if source != "disk":
        _COMPILES.labels(site=site).inc()


def executable_of(fn):
    """The underlying XLA executable of a compile_cached/CachedJit
    result, or None for bypass results (a plain lazy jit has no
    executable to cost-account until its first call)."""
    if isinstance(fn, _GuardedCompiled):
        return fn._compiled
    return None


def cache_dir():
    """The configured cache directory, or '' when the cache is disabled."""
    return _flags.get_flag("jit_cache_dir", "") or ""


def enabled():
    return bool(cache_dir())


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
    """Stable identity of a mesh's topology for cache keys: axis names and
    sizes, device kinds, device and process counts — an executable
    compiled for one topology must never be offered to another."""
    if mesh is None:
        return ("mesh", None)
    devs = list(np.asarray(mesh.devices).ravel())
    kinds = sorted({getattr(d, "device_kind", d.platform) for d in devs})
    return ("mesh", tuple(mesh.axis_names),
            tuple(int(mesh.shape[a]) for a in mesh.axis_names),
            tuple(kinds), len(devs), int(jax.process_count()))


def _canonical_specs(args):
    """Replace array leaves with ShapeDtypeStructs before lowering, so the
    lowered text (the cache key) is identical however the caller's arrays
    happen to be placed: a committed single-device array, an uncommitted
    eager result, and a warmup spec all lower to the same module. Only
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


def _backend():
    from jax.extend import backend as _jex_backend

    return _jex_backend.get_backend()


def _cache_key(lowered, extra_key=()):
    import hashlib

    be = _backend()
    h = hashlib.sha256()
    h.update(lowered.as_text().encode())
    h.update(jax.__version__.encode())
    h.update(f"{be.platform}:{be.platform_version}".encode())
    for name in _KEYED_FLAGS:
        h.update(f"{name}={_flags.get_flag(name)!r};".encode())
    for part in extra_key:
        h.update(repr(part).encode())
    return h.hexdigest()


def _entry_path(key):
    return os.path.join(cache_dir(), key + _SUFFIX)


class _StaleEntry(Exception):
    """Entry written by a different cache format / jax / platform."""


def _evict(path, reason):
    _EVICT_TOTAL.labels(reason=reason).inc()
    try:
        os.remove(path)
    except OSError:
        pass


def _load_entry(path, site):
    """Deserialize one cache entry; any failure evicts the file and
    returns None (silent recompile — never crash on a bad entry)."""
    try:
        with open(path, "rb") as f:
            blob = f.read()
    except OSError:
        return None  # plain miss
    t0 = time.perf_counter()
    try:
        # import inside the guard: a jax build without the serializer must
        # degrade to a silent recompile, not crash the compile path
        from jax.experimental.serialize_executable import \
            deserialize_and_load

        entry = pickle.loads(blob)
        be = _backend()
        if (not isinstance(entry, dict)
                or entry.get("format") != _FORMAT
                or entry.get("jax") != jax.__version__
                or entry.get("platform") != be.platform
                or entry.get("platform_version") != be.platform_version):
            raise _StaleEntry
        compiled = deserialize_and_load(entry["payload"], entry["in_tree"],
                                        entry["out_tree"])
    except Exception as e:
        _evict(path, "version" if isinstance(e, _StaleEntry) else "corrupt")
        return None
    if _monitor.is_enabled():
        _DES_MS.labels(site=site).observe((time.perf_counter() - t0) * 1e3)
        _AOT_BYTES.labels(site=site, event="deserialize").observe(len(blob))
    try:
        os.utime(path, None)  # LRU recency: a hit is a use
    except OSError:
        pass
    return compiled


def _store_entry(key, compiled, site):
    """Serialize `compiled` into the cache (atomic rename; never raises —
    a non-serializable executable still runs, the next process just
    recompiles) and enforce the LRU byte cap. Returns True on success."""
    d = cache_dir()
    tmp = None
    try:
        from jax.experimental.serialize_executable import serialize

        t0 = time.perf_counter()
        payload, in_tree, out_tree = serialize(compiled)
        be = _backend()
        blob = pickle.dumps(
            {"format": _FORMAT, "jax": jax.__version__,
             "platform": be.platform,
             "platform_version": be.platform_version,
             "site": site, "key": key, "payload": payload,
             "in_tree": in_tree, "out_tree": out_tree}, protocol=4)
        if _monitor.is_enabled():
            _SER_MS.labels(site=site).observe(
                (time.perf_counter() - t0) * 1e3)
            _AOT_BYTES.labels(site=site, event="serialize").observe(
                len(blob))
        os.makedirs(d, exist_ok=True)
        tmp = os.path.join(
            d, f".tmp-{key[:16]}-{os.getpid()}-{uuid.uuid4().hex[:8]}")
        with open(tmp, "wb") as f:
            f.write(blob)
        os.replace(tmp, _entry_path(key))  # atomic: concurrent writers race
        tmp = None                         # benignly (same content per key)
        _STORE_TOTAL.labels(site=site, event="ok").inc()
        _enforce_lru(d)
        return True
    except Exception:
        _STORE_TOTAL.labels(site=site, event="error").inc()
        if tmp is not None:
            try:
                os.remove(tmp)
            except OSError:
                pass
        return False


def _enforce_lru(d):
    """Evict oldest entries (mtime) until the directory fits the byte cap.
    The newest entry always survives — one oversized executable must not
    evict itself into a cache that can never hit."""
    cap = int(_flags.get_flag("jit_cache_max_bytes", 1 << 30))
    entries = []
    try:
        names = os.listdir(d)
    except OSError:
        return
    now = time.time()
    for name in names:
        p = os.path.join(d, name)
        if name.startswith(".tmp-"):
            # orphan from a crashed writer (killed between write and
            # rename): sweep once safely aged past any live write
            try:
                if now - os.stat(p).st_mtime > 3600:
                    os.remove(p)
            except OSError:
                pass
            continue
        if not name.endswith(_SUFFIX):
            continue
        try:
            st = os.stat(p)
        except OSError:
            continue
        entries.append((st.st_mtime, st.st_size, p))
    total = sum(size for _, size, _ in entries)
    entries.sort()
    for _, size, p in entries[:-1]:  # keep the newest no matter what
        if total <= cap:
            break
        try:
            os.remove(p)
        except OSError:
            continue
        total -= size
        _EVICT_TOTAL.labels(reason="lru").inc()


class _GuardedCompiled:
    """A cache-loaded (or spec-warmed) executable with a recompile escape
    hatch: if it rejects a live call — layout/sharding drift the key
    missed, machine-feature mismatch — evict the entry and hand the
    signature back to the plain jit instead of crashing the caller."""

    __slots__ = ("_compiled", "_jit", "_path")

    def __init__(self, compiled, jitted, path=None):
        self._compiled = compiled
        self._jit = jitted
        self._path = path

    def __call__(self, *args):
        compiled = self._compiled
        if compiled is None:
            return self._jit(*args)
        try:
            return compiled(*args)
        except (TypeError, ValueError):
            # pre-execution REJECTION only (signature/pytree/sharding
            # mismatch — raised before donation consumes any buffer):
            # drop the entry and fall back to the plain jit. Runtime
            # failures (XlaRuntimeError, OOM) propagate — retrying them
            # with already-donated inputs would destroy live state and
            # mask the real error.
            self._compiled = None
            if self._path is not None:
                _evict(self._path, "call")
            else:
                _EVICT_TOTAL.labels(reason="call").inc()
            return self._jit(*args)


def _goodput_compile():
    """`compile` wall-time attribution (FLAGS_goodput, ISSUE 20): a null
    context unless the goodput accountant is armed. Booked at THE
    compile chokepoint, so trainer AOT misses, serving warmups, and
    elastic resize warm-restarts all attribute — nested inside the
    trainer's `step` bucket, the compile time pauses it (exclusive
    buckets). One flag read per compile; the disarmed path never imports
    monitor/goodput.py (manifest-lazy)."""
    import contextlib

    if not _flags.get_flag("goodput", False):
        return contextlib.nullcontext()
    from ..monitor import goodput as _goodput

    return _goodput.bucket("compile")


def compile_cached(jitted, example_args, *, site, extra_key=(),
                   force=False):
    """Obtain an executable for ``jitted`` at ``example_args`` (real
    arrays, or jax.ShapeDtypeStructs for data-free warmup), through the
    on-disk cache when enabled.

    Returns ``(callable, source)``:

    - ``("bypass")`` — FLAGS_jit_cache_dir unset: ``jitted`` itself is
      returned untouched (no lowering, no disk I/O; jit compiles lazily
      on first call exactly as before). ``force=True`` — the warm-start
      APIs — compiles eagerly in memory instead, so warmup works without
      a cache dir (source ``fresh``, nothing written);
    - ``("disk")`` — deserialized from the cache;
    - ``("fresh")`` — lowered and compiled now, then serialized into the
      cache (best effort).

    Both non-bypass results are wrapped in a call-failure guard: an
    executable that rejects a live call (pytree/layout/sharding drift the
    key missed) falls back to the plain jit for good instead of crashing.
    """
    if not enabled():
        if not force:
            return jitted, "bypass"
        # the progress window brackets every eager XLA compile: a hung
        # compile leaves an ACTIVE, non-advancing aot/compile beacon for
        # the stall sentinel to name (monitor/blackbox.py)
        with _goodput_compile(), _blackbox.progress("aot/compile"):
            compiled = jitted.lower(
                *_canonical_specs(example_args)).compile()
        return _GuardedCompiled(compiled, jitted), "fresh"
    with _goodput_compile(), _blackbox.progress("aot/compile"):
        lowered = jitted.lower(*_canonical_specs(example_args))
        key = _cache_key(lowered, extra_key)
        compiled = _load_entry(_entry_path(key), site)
        if compiled is not None:
            return _GuardedCompiled(compiled, jitted,
                                    _entry_path(key)), "disk"
        compiled = lowered.compile()
        stored = _store_entry(key, compiled, site)
    # the guard knows the entry path so a call-rejected executable also
    # removes its own just-written file (a later process must not
    # deserialize a binary this one already proved uncallable)
    return _GuardedCompiled(compiled, jitted,
                            _entry_path(key) if stored else None), "fresh"


class CachedJit:
    """A ``jax.jit`` lookalike whose compilations go through the
    persistent cache: per call-signature, lower once, load-or-compile
    from disk, keep the executable in an in-process map. With
    FLAGS_jit_cache_dir unset and nothing warmed, every call delegates
    straight to the wrapped jit after one empty-dict + flag check —
    behavior and cost identical to plain jit (the tier-1 gate pins it).
    Once warmed/enabled, each call pays a python-level signature flatten
    over the arg pytrees (~µs for a params+KV-cache tree) — well under
    1% of a ms-scale decode step, but measurable; a latency-critical
    caller that truly has one static signature can hold the plain jit.

    ``warm(*specs)`` AOT-compiles one signature from
    ``jax.ShapeDtypeStruct`` specs (plus plain python scalars for
    weakly-typed args) without real data and without executing anything —
    the ServingEngine.warmup / SpmdTrainer.aot_build building block.
    """

    def __init__(self, fn=None, *, site, jit=None, label=None,
                 donate_argnums=(), sig_label=None, record_event=None,
                 extra_key=()):
        if jit is None:
            jit = jax.jit(fn, donate_argnums=donate_argnums)
        self._jit = jit
        self._site = site
        self._label = label or getattr(fn, "__name__", "jit")
        self._sig_label = sig_label  # callable(args) -> str, or None
        self._record_event = record_event or f"{site}/compile"
        self._extra_key = tuple(extra_key) + (self._label,)
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
            # force: warm() without a cache dir still AOT-compiles in
            # memory (a warmed signature must never retrace at call time)
            compiled, source = compile_cached(
                self._jit, args, site=self._site,
                extra_key=self._extra_key, force=True)
        record_compile(self._site, self._label_of(args), source)
        # device cost registry: every executable this wrapper obtains —
        # fresh, warmed, or an AOT-cache deserialize hit — lands its
        # cost_analysis()/memory_analysis() under (site, program label);
        # the exact per-signature entry is also kept so executions of a
        # bucketed family account each bucket's own flops
        entry = _costs.record(self._site, self._label_of(args),
                              executable_of(compiled))
        if entry is not None:
            self._cost_entries[sig] = entry
        self._store[sig] = compiled
        return compiled

    def warm(self, *specs):
        """Compile one signature ahead of time from shape specs. Returns
        True if a compile (or disk load) happened, False if that
        signature was already warm."""
        sig = args_signature(specs)
        if sig in self._store:
            return False
        self._compile(sig, specs)
        return True

    def __call__(self, *args):
        store = self._store
        if not store and not enabled() and not _trace.is_enabled():
            return self._jit(*args)
        sig = args_signature(args)
        compiled = store.get(sig)
        if compiled is None:
            if not enabled() and not _trace.is_enabled():
                return self._jit(*args)  # warmed, but not for this sig
            # FLAGS_trace forces eager AOT (in memory when no cache dir)
            # so the cost registry sees an executable for every program
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
        until cost entries exist (FLAGS_trace / cache dir / warm())."""
        return {"calls": self._exec_calls, "flops": self._exec_flops}


def cached_jit(fn=None, **kwargs):
    """Factory form of :class:`CachedJit` (accepts ``jit=`` for an
    already-built jit object, e.g. a jit(shard_map(...)) wrapper)."""
    return CachedJit(fn, **kwargs)
