"""paddle.static parity (python/paddle/static/__init__.py).

Reference parity: the Program/Executor static-graph world — Program/Block/
Operator/Variable graph construction (fluid/framework.py:4174 Program,
:978 Block/append_op) and Executor.run(feed, fetch_list)
(fluid/executor.py:916). There, every fluid API call appends OpDescs to the
default program; Executor interprets the graph against a Scope.

TPU-native design: ops still EXECUTE eagerly at build time (placeholders are
zero arrays, so shapes are concrete), but while static mode is on every
dispatched op is also RECORDED into the default Program as
(pure_jnp_fn, arg_specs, out_ids). Executor.run slices the recorded op list
to what the fetch_list needs, replays it as one pure function of
(params, feed) and jax.jit-compiles that per feed-signature — the ParallelExecutor/
interpreter world collapses into XLA compilation. `minimize` attaches the
optimizer functionally (jax.value_and_grad over the replay + functional_apply),
the append_backward program-surgery equivalent.
"""
import contextlib
import time

import numpy as np
import jax
import jax.numpy as jnp

from .. import flags as _flags
from .. import monitor as _monitor
from ..monitor import blackbox_lazy as _blackbox  # import-free recorder facade (ISSUE 12)
from ..trace import costs as _costs
from .. import trace as _trace
from ..core import dtype as dtype_mod
from ..core import dispatch as _dispatch
from ..core.tensor import Tensor, ParamBase
from ..framework import aot as _aot
from ..jit import InputSpec  # noqa: F401
from ..profiler import RecordEvent as _RecordEvent
from ..testing import failpoints as _failpoints

_STATIC_MODE = [False]

# compile_total/compile_cache_total are declared (and recorded) by
# framework/aot.py's record_compile — one mapping for every site; this
# module reports under site="executor" with the feed-signature label
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


def _feed_sig_label(sig):
    """Compact feed-signature label, e.g. 'x:float32[2,8]|y:int32[2]'.
    Cardinality is capped by the registry's overflow series."""
    if not sig:
        return "-"
    return "|".join(
        f"{k}:{dt}[{','.join(str(d) for d in shape)}]"
        for k, shape, dt in sig)


def _record_compile(sig, source):
    """Executor compile-cache telemetry — the shared aot.record_compile
    mapping under site=executor with the feed-signature label."""
    _aot.record_compile("executor", _feed_sig_label(sig), source)


def enable_static():
    _STATIC_MODE[0] = True


def disable_static():
    _STATIC_MODE[0] = False


def in_static_mode():
    return _STATIC_MODE[0]


def in_dynamic_mode():
    return not _STATIC_MODE[0]


class _OpRecord:
    __slots__ = ("fn", "arg_specs", "kwargs", "out_ids")

    def __init__(self, fn, arg_specs, kwargs, out_ids):
        self.fn = fn
        self.arg_specs = arg_specs  # [("var", id) | ("const", value)]
        self.kwargs = kwargs
        self.out_ids = out_ids


class Program:
    """Recorded op-list program (fluid Program/Block collapse).

    vars holds strong refs to every Tensor the graph touches; params are the
    persistable leaves (scope state), placeholders the feed slots. `_scope`
    is shared with clones — the Scope of the reference's executor."""

    def __init__(self):
        self.ops = []
        self.vars = {}          # id(tensor) -> Tensor
        self._data_ids = {}     # id(tensor._data) -> var id: functionals often
                                # RE-WRAP args (Tensor(x) shares x._data, new
                                # object id); resolving through the underlying
                                # immutable jax array keeps the var chain intact
                                # instead of baking the build-time value
        self.placeholders = {}  # feed name -> var id
        self.placeholder_shapes = {}  # feed name -> declared shape (None dims)
        self.params = {}        # var id -> param name
        self.param_names = {}   # param name -> var id
        self._initial = {}      # param name -> np.ndarray (startup values)
        self._scope = {"params": None, "opt_state": None}
        self._exec_cache = {}
        self._optimizer = None
        self._loss_id = None
        self._train_param_names = None  # None = all params the loss reaches
        self._paired_main = None        # set on startup programs by program_guard
        self._version = 0
        self.random_seed = None

    # -- building --------------------------------------------------------------
    def _register_placeholder(self, name, t, declared_shape):
        self.vars[id(t)] = t
        self._data_ids[id(t._data)] = id(t)
        self.placeholders[name] = id(t)
        self.placeholder_shapes[name] = tuple(declared_shape)

    def _register_param(self, t):
        name = t.name or f"param_{len(self.param_names)}"
        if name in self.param_names and self.param_names[name] != id(t):
            name = f"{name}_{len(self.param_names)}"
        self.vars[id(t)] = t
        self._data_ids[id(t._data)] = id(t)
        self.params[id(t)] = name
        self.param_names[name] = id(t)
        self._initial[name] = np.asarray(t._data)
        return name

    def _resolve_var(self, t):
        """SSA resolution of a Tensor to its var id. _data identity is checked
        FIRST: functionals re-wrap tensors (new object, same array) and
        apply_inplace rebinds a target's _data to the op output — in both
        cases the underlying immutable array names the current value, while
        the object id may point at a stale binding."""
        vid = self._data_ids.get(id(t._data))
        if vid is not None:
            return vid
        return id(t) if id(t) in self.vars else None

    def _record(self, fn, args, kwargs, outs):
        specs = []
        for a in args:
            if isinstance(a, Tensor):
                vid = self._resolve_var(a)
                if vid is None:
                    if isinstance(a, ParamBase) or a.persistable:
                        self._register_param(a)
                        vid = id(a)
                    else:
                        # a tensor created eagerly outside the graph: bake it
                        specs.append(("const", a._data))
                        continue
                specs.append(("var", vid))
            else:
                specs.append(("const", a))
        kw = {k: (v._data if isinstance(v, Tensor) else v)
              for k, v in kwargs.items()}
        for o in outs:
            self.vars[id(o)] = o
            self._data_ids[id(o._data)] = id(o)
        self.ops.append(_OpRecord(fn, specs, kw, [id(o) for o in outs]))
        self._version += 1

    def _rebind(self, old, new_t):
        """apply_inplace rebound new_t._data to old's value: keep a strong
        ref to new_t; _resolve_var already routes future uses through the
        shared array to `old`'s record (SSA — the old producer op stays the
        sole producer of its id)."""
        if id(old) in self.vars:
            self.vars[id(new_t)] = new_t

    # -- optimizer attachment (append_backward + optimize-op insertion) --------
    def set_optimizer(self, optimizer, loss, parameters=None,
                      no_grad_set=None):
        lid = self._resolve_var(loss) if isinstance(loss, Tensor) else None
        if lid is None:
            raise ValueError(
                "minimize(loss): loss was not built in this program "
                "(build it from static.data placeholders under program_guard)")
        self._optimizer = optimizer
        self._loss_id = lid
        self._train_param_names = None
        if parameters:
            names = set()
            for p in parameters:
                pid = self._resolve_var(p) if isinstance(p, Tensor) else None
                if pid in self.params:
                    names.add(self.params[pid])
                elif isinstance(p, str) and p in self.param_names:
                    names.add(p)
            self._train_param_names = names
        if no_grad_set:
            frozen = set()
            for p in no_grad_set:
                pid = self._resolve_var(p) if isinstance(p, Tensor) else None
                if pid in self.params:
                    frozen.add(self.params[pid])
                elif isinstance(p, str):
                    frozen.add(p)
            base = (self._train_param_names
                    if self._train_param_names is not None
                    else set(self.param_names))
            self._train_param_names = base - frozen
        self._version += 1

    # -- scope/state -----------------------------------------------------------
    def _ensure_scope(self):
        if self._scope["params"] is None:
            self._scope["params"] = {}
        # top-up: params registered since the last run initialize lazily
        for name in self.param_names:
            if name not in self._scope["params"]:
                self._scope["params"][name] = jnp.asarray(self._initial[name])

    def _reset_scope(self):
        self._scope["params"] = {
            name: jnp.asarray(self._initial[name]) for name in self.param_names
        }
        self._scope["opt_state"] = None

    def _sync_params_to_tensors(self):
        for vid, name in self.params.items():
            t = self.vars.get(vid)
            if t is not None and self._scope["params"] is not None:
                t._data = self._scope["params"][name]

    def state_dict(self):
        self._ensure_scope()
        return {n: Tensor(v) for n, v in self._scope["params"].items()}

    # -- reference API surface -------------------------------------------------
    def global_block(self):
        return self

    def all_parameters(self):
        return [self.vars[vid] for vid in self.params]

    def list_vars(self):
        return list(self.vars.values())

    def clone(self, for_test=False):
        """Shares ops/vars/scope (the reference clones the graph but runs in
        the same Scope); for_test drops the optimizer so Executor.run does
        pure inference — the canonical `test_program = main.clone(True)`."""
        c = Program.__new__(Program)
        c.__dict__ = dict(self.__dict__)
        if for_test:
            c._optimizer = None
            c._loss_id = None
        return c

    def analysis_jaxpr(self, feed=None, fetch_list=None):
        """Trace the recorded program — exactly as Executor.run would
        replay it — to a jax ClosedJaxpr for paddle_tpu.analysis.

        This is the Program-level hook for the pass registry (the
        reference's REGISTER_PASS layer inspects the Program graph; here
        the passes inspect the jaxpr of its jitted replay). The pure
        replay fn is the SAME one Executor._compile jits, so findings
        refer to the graph that actually runs. Nothing is compiled or
        executed — tracing only.

            prog.analysis_jaxpr(feed={"x": np.zeros((4, 8), "float32")})

        fetch_list defaults to the outputs of the last recorded op (or
        the attached loss when an optimizer is set). A program with an
        optimizer attached traces the TRAIN step (forward + grads +
        optimizer update), matching what Executor.run executes for it.
        """
        feed = {k: jnp.asarray(np.asarray(v))
                for k, v in (feed or {}).items()}
        self._ensure_scope()
        exe = Executor()
        if fetch_list:
            fetch_ids = tuple(exe._fetch_id(self, f) for f in fetch_list)
        elif self._loss_id is not None:
            fetch_ids = (self._loss_id,)
        elif self.ops:
            fetch_ids = tuple(self.ops[-1].out_ids)
        else:
            raise ValueError("analysis_jaxpr: empty program (no recorded "
                             "ops) and no fetch_list")
        train = self._optimizer is not None and self._loss_id is not None
        fn = _build_program_fn(self, tuple(feed), fetch_ids, train=train)
        params = self._scope["params"]
        if not train:
            return jax.make_jaxpr(fn)(params, feed)
        opt = self._optimizer
        opt_state = (self._scope["opt_state"]
                     if self._scope["opt_state"] is not None
                     else opt.functional_init(params))
        lr = jnp.asarray(opt.get_lr(), jnp.float32)
        return jax.make_jaxpr(fn)(params, opt_state, lr, feed)

    def aot_compile(self, feed_specs, fetch_list=None):
        """Warm-start: compile the EXACT executable Executor.run would jit
        for this feed signature — from shape specs, no real batch — and
        park it in the program's jit cache.

            prog.aot_compile({"x": ((8, 13), "float32"),
                              "y": ((8, 1), "float32")},
                             fetch_list=[loss])

        feed_specs: {name: (shape, dtype) | InputSpec | ShapeDtypeStruct}.
        fetch_list defaults to the attached loss (train programs) or the
        last recorded op's outputs — pass the same fetch_list the serving
        run will use, since the cache key includes the fetch set. A
        program with an optimizer attached compiles the TRAIN step.
        Returns where the executable came from: "memory"|"fresh"."""
        feed = {}
        for name in sorted(feed_specs):
            spec = feed_specs[name]
            if isinstance(spec, jax.ShapeDtypeStruct):
                shape, dtype = spec.shape, spec.dtype
            elif isinstance(spec, InputSpec):
                shape, dtype = spec.shape, spec.dtype
            else:
                shape, dtype = spec
            feed[name] = jax.ShapeDtypeStruct(
                tuple(shape), dtype_mod.convert_dtype(dtype))
        self._ensure_scope()
        exe = Executor()
        if fetch_list:
            fetch_ids = tuple(exe._fetch_id(self, f) for f in fetch_list)
        elif self._loss_id is not None:
            fetch_ids = (self._loss_id,)
        elif self.ops:
            fetch_ids = tuple(self.ops[-1].out_ids)
        else:
            raise ValueError("aot_compile: empty program (no recorded ops) "
                             "and no fetch_list")
        train, sig, key, lr, example = _exec_key_and_example(
            self, feed, fetch_ids)
        if key in self._exec_cache:
            _record_compile(sig, "memory")  # warm audits count this too
            return "memory"
        with _RecordEvent("executor/compile"), \
                _monitor.timed(_COMPILE_MS.labels(site="executor")):
            compiled, source = exe._compile(self, tuple(feed), fetch_ids,
                                            train, example, force=True)
        self._exec_cache[key] = compiled
        _record_compile(sig, source)
        _costs.record("executor", _feed_sig_label(sig),
                            _aot.executable_of(compiled))
        return source


_default_main = [Program()]
_default_startup = [Program()]


def default_main_program():
    return _default_main[0]


def default_startup_program():
    return _default_startup[0]


@contextlib.contextmanager
def program_guard(main_program, startup_program=None):
    old_m, old_s = _default_main[0], _default_startup[0]
    _default_main[0] = main_program
    if startup_program is not None:
        _default_startup[0] = startup_program
        # running the startup later must initialize THIS main program's
        # params, wherever the defaults point at that moment
        startup_program._paired_main = main_program
    try:
        yield
    finally:
        _default_main[0], _default_startup[0] = old_m, old_s


def data(name, shape, dtype="float32", lod_level=0):
    """paddle.static.data parity: a named feed placeholder.

    Build-time value is zeros with None dims -> 1, so downstream ops execute
    (and shape-infer) concretely; Executor.run replaces it with the fed batch."""
    declared = list(shape)
    concrete = [1 if (s is None or s < 0) else s for s in shape]
    t = Tensor(jnp.zeros(concrete, dtype=dtype_mod.convert_dtype(dtype)))
    t.name = name
    t.stop_gradient = True
    if _STATIC_MODE[0]:
        _default_main[0]._register_placeholder(name, t, declared)
    return t


def append_backward(loss, parameter_list=None, no_grad_set=None):
    """fluid.backward.append_backward parity: in this design gradients are
    derived by jax.value_and_grad over the recorded replay at run time, so
    this only validates that `loss` belongs to the default program."""
    prog = _default_main[0]
    if id(loss) not in prog.vars:
        raise ValueError("append_backward: loss is not a var of the default "
                         "main program")
    return []


# -- the dispatch hooks --------------------------------------------------------

from ..core.tape import global_tape as _global_tape  # noqa: E402


def _record_hook(fn, args, kwargs, outs):
    if not _STATIC_MODE[0]:
        return
    # tape paused == inside a jitted trainer/StaticFunction trace: those
    # compile their own programs; recording their tracer ops would leak
    if not _global_tape().enabled:
        return
    _default_main[0]._record(fn, args, kwargs, outs)


def _rebind_hook(old, new_t):
    if not _STATIC_MODE[0]:
        return
    _default_main[0]._rebind(old, new_t)


_dispatch._STATIC_RECORDER[0] = _record_hook
_dispatch._STATIC_REBIND[0] = _rebind_hook


# -- execution -----------------------------------------------------------------

def _exec_key_and_example(program, feed, fetch_ids):
    """The ONE source of the executor's jit-cache key and AOT example
    args, shared by Executor._run_program and Program.aot_compile so a
    warm-started entry is exactly the one run() later looks up. `feed`
    maps name -> array or ShapeDtypeStruct in canonical (sorted) order;
    materializes optimizer state (train programs) as a side effect.
    Returns (train, sig, key, lr, example_args)."""
    train = program._optimizer is not None and program._loss_id is not None
    sig = tuple((k, v.shape, str(v.dtype)) for k, v in feed.items())
    key = (program._version, train, fetch_ids, sig)
    scope = program._scope
    lr = None
    if train:
        # optimizer state materializes BEFORE compile: the AOT path
        # lowers against the live (params, opt_state, lr, feed) values
        opt = program._optimizer
        if scope["opt_state"] is None:
            scope["opt_state"] = opt.functional_init(scope["params"])
        else:
            for n, v in scope["params"].items():
                if n not in scope["opt_state"]:
                    scope["opt_state"][n] = opt.functional_init({n: v})[n]
        lr = jnp.asarray(opt.get_lr(), jnp.float32)
        example = (scope["params"], scope["opt_state"], lr, feed)
    else:
        example = (scope["params"], feed)
    return train, sig, key, lr, example


def _slice_ops(program, target_ids):
    """Backward slice: only ops the targets (+loss) actually need run."""
    producer = {}
    for idx, op in enumerate(program.ops):
        for oid in op.out_ids:
            producer[oid] = idx
    needed = set()
    stack = [t for t in target_ids if t is not None]
    while stack:
        vid = stack.pop()
        idx = producer.get(vid)
        if idx is None or idx in needed:
            continue
        needed.add(idx)
        for spec in program.ops[idx].arg_specs:
            if spec[0] == "var":
                stack.append(spec[1])
    return [program.ops[i] for i in sorted(needed)]


class Executor:
    """fluid/executor.py:916 Executor parity: run(feed, fetch_list) over the
    recorded program, jax.jit-compiled per (program version, feed signature,
    fetch set). Running an empty program (the startup program) initializes
    the default main program's parameters — the startup-initializer-ops run."""

    def __init__(self, place=None):
        self.place = place

    def run(self, program=None, feed=None, fetch_list=None, return_numpy=True,
            scope=None):
        feed = feed or {}
        if program is None:
            program = default_main_program()
        if callable(program) and not isinstance(program, Program):
            # legacy path: a plain python callable "program"
            out = program(**feed)
            outs = out if isinstance(out, (list, tuple)) else [out]
            return [np.asarray(o._data) if isinstance(o, Tensor) and return_numpy
                    else o for o in outs]
        if not isinstance(program, Program):
            return []
        if not program.ops:
            # startup program: (re)run parameter initialization for the main
            # program it was paired with (fallback: the current default)
            main = program._paired_main or default_main_program()
            main._reset_scope()
            return []
        return self._run_program(program, feed, fetch_list or [], return_numpy)

    def train_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100):
        """fluid/executor.py train_from_dataset parity: drive the recorded
        program from an InMemoryDataset/QueueDataset — slot names feed the
        matching static.data placeholders batch by batch (the reference's
        hogwild_worker.cc:195-211 DataFeed->Program loop).

        Ragged slots pad per batch; a new pad width jit-compiles a new feed
        signature (fixed-length slots compile exactly once)."""
        if dataset is None:
            raise ValueError("train_from_dataset requires dataset=")
        program = program or default_main_program()
        names = set(program.placeholders) if isinstance(program, Program) \
            else None
        last = None
        for step, batch in enumerate(dataset.batch_iter()):
            feed = {k: v for k, v in batch.items()
                    if names is None or k in names}
            last = self.run(program, feed=feed, fetch_list=fetch_list)
            if debug and fetch_list and step % max(1, print_period) == 0:
                info = fetch_info or [f"fetch{i}"
                                      for i in range(len(fetch_list))]
                vals = ", ".join(f"{n}={np.asarray(v).mean():.6f}"
                                 for n, v in zip(info, last))
                print(f"[train_from_dataset] step {step}: {vals}")
        return last

    def infer_from_dataset(self, program=None, dataset=None, **kwargs):
        """Inference twin: NEVER runs the optimizer — a program that has one
        attached is evaluated through its for_test clone (is_infer=True
        semantics; the reference skips gradient push on this path)."""
        program = program or default_main_program()
        if isinstance(program, Program) and program._optimizer is not None:
            program = program.clone(for_test=True)
        return self.train_from_dataset(program=program, dataset=dataset,
                                       **kwargs)

    # -- internals -------------------------------------------------------------
    def _fetch_id(self, program, f):
        if isinstance(f, Tensor):
            vid = program._resolve_var(f)  # handles re-wraps and in-place
            if vid is not None:
                return vid
            raise ValueError(f"fetch var {getattr(f, 'name', f)} is not part "
                             "of the program")
        if isinstance(f, str):
            if f in program.placeholders:
                return program.placeholders[f]
            if f in program.param_names:
                return program.param_names[f]
            for t in program.vars.values():
                if getattr(t, "name", None) == f:
                    return id(t)
            raise ValueError(f"fetch name '{f}' not found in program")
        raise TypeError(f"cannot fetch {type(f).__name__}")

    def _run_program(self, program, feed, fetch_list, return_numpy):
        # window beacon: watched only while a run (compile included) is
        # actually in flight — a finished session never reads as a stall
        with _blackbox.progress("executor/run"):
            return self._run_program_impl(program, feed, fetch_list,
                                          return_numpy)

    def _run_program_impl(self, program, feed, fetch_list, return_numpy):
        t_step = time.perf_counter()
        program._ensure_scope()
        fetch_ids = tuple(self._fetch_id(program, f) for f in fetch_list)
        # canonical (sorted) feed order: the jit-cache key sorts the
        # signature, so the compiled closure must be built from the same
        # order — otherwise two insertion orders of the same feed dict
        # alias one cache entry built from whichever order arrived first
        feed_arrays = {k: jnp.asarray(np.asarray(feed[k]))
                       for k in sorted(feed)}
        train, sig, key, lr, example = _exec_key_and_example(
            program, feed_arrays, fetch_ids)
        # cache lives ON the program (not the executor) so dropped programs
        # release their compiled closures and baked arrays with them
        cache = program._exec_cache
        scope = program._scope
        sig_label = _feed_sig_label(sig)   # computed ONCE per run
        if key not in cache:
            with _RecordEvent("executor/compile"), \
                    _monitor.timed(_COMPILE_MS.labels(site="executor")):
                # FLAGS_trace forces an eager AOT compile (in memory) so
                # the cost registry can read the executable's
                # cost_analysis(); flag unset keeps the lazy-jit bypass
                cache[key], source = self._compile(
                    program, tuple(feed_arrays), fetch_ids, train, example,
                    force=_trace.is_enabled())
            _aot.record_compile("executor", sig_label, source)
            _costs.record("executor", sig_label,
                          _aot.executable_of(cache[key]))
        else:
            source = "memory"
            _aot.record_compile("executor", sig_label, "memory")
        compiled = cache[key]
        # step span: compile-cache source + feed signature + sync time —
        # the executor half of the ISSUE-5 end-to-end trace propagation
        sp = _trace.span("executor/run", subsystem="executor",
                         sig=sig_label, source=source, train=train)
        with sp, _RecordEvent("executor/run"):
            if train:
                opt = program._optimizer
                new_p, new_s, fetches = compiled(scope["params"],
                                                 scope["opt_state"], lr,
                                                 feed_arrays)
                scope["params"] = new_p
                scope["opt_state"] = new_s
                opt._step_count += 1
                program._sync_params_to_tensors()
            else:
                fetches = compiled(scope["params"], feed_arrays)
            if _flags.get_flag("benchmark"):
                # step timings measure DEVICE work, not dispatch: block on
                # every fetch (train steps also pin the updated params so
                # a fetchless run(feed=...) still syncs the real step)
                t_sync = time.perf_counter()
                sync_on = list(fetches)
                if train and scope["params"]:
                    sync_on.append(next(iter(scope["params"].values())))
                for f in sync_on:
                    if hasattr(f, "block_until_ready"):
                        f.block_until_ready()
                _BENCH_SYNC.labels(site="executor").inc()
                sp.set(sync_ms=(time.perf_counter() - t_sync) * 1e3)
        if _monitor.is_enabled():
            _STEP_MS.labels(site="executor").observe(
                (time.perf_counter() - t_step) * 1e3)
        if return_numpy:
            return [np.asarray(f) for f in fetches]
        return [Tensor(f) for f in fetches]

    def _compile(self, program, feed_names, fetch_ids, train, example_args,
                 force=False):
        """jit the pure replay. Returns (callable, source: bypass|fresh);
        `example_args` may mix live arrays and jax.ShapeDtypeStructs.
        force=True (aot_compile, tracing) compiles eagerly — warm-start
        must never hand back a lazy jit (framework/aot.py)."""
        _failpoints.failpoint("exe/compile")
        jitted = jax.jit(_build_program_fn(program, feed_names, fetch_ids,
                                           train))
        return _aot.compile_cached(jitted, example_args, force=force)


def _build_program_fn(program, feed_names, fetch_ids, train):
    """Build the pure replay fn Executor jits: (params, feed) -> fetches
    for eval, (params, opt_state, lr, feed) -> (params', state', fetches)
    for train. Shared with Program.analysis_jaxpr so the analysis passes
    see the exact graph the executor runs."""
    targets = list(fetch_ids) + ([program._loss_id] if train else [])
    ops = _slice_ops(program, targets)

    # validate feeds BEFORE jit: every needed placeholder must be fed
    bound = set()
    for name in feed_names:
        if name not in program.placeholders:
            raise ValueError(f"feed '{name}' is not a static.data "
                             "placeholder of this program")
        bound.add(program.placeholders[name])
    bound |= set(program.params)
    def _missing(vid, what):
        for n, pvid in program.placeholders.items():
            if pvid == vid:
                raise ValueError(f"placeholder '{n}' is required by the "
                                 f"{what} but missing from feed")
        raise ValueError(f"{what} references a var with no producer "
                         "(was it built in a different program?)")

    for op in ops:
        for spec in op.arg_specs:
            if spec[0] == "var" and spec[1] not in bound:
                _missing(spec[1], "fetch_list")
        bound |= set(op.out_ids)
    for fid in targets:
        if fid is not None and fid not in bound:
            _missing(fid, "fetch_list")

    ph = program.placeholders
    params_map = dict(program.params)

    def forward(param_arrays, feed_arrays):
        env = {}
        for name, arr in feed_arrays.items():
            env[ph[name]] = arr
        for vid, name in params_map.items():
            env[vid] = param_arrays[name]
        for op in ops:
            vals = [env[s[1]] if s[0] == "var" else s[1]
                    for s in op.arg_specs]
            out = op.fn(*vals, **op.kwargs)
            outs = out if isinstance(out, (tuple, list)) else (out,)
            for oid, o in zip(op.out_ids, outs):
                env[oid] = o
        return env

    if not train:
        def ev(param_arrays, feed_arrays):
            env = forward(param_arrays, feed_arrays)
            return [env[i] for i in fetch_ids]

        return ev

    opt = program._optimizer
    loss_id = program._loss_id  # snapshot: closures must not pin program
    # update ONLY params the sliced loss graph actually uses (a second
    # model in the same program must not weight-decay toward zero), and
    # honor minimize(parameters=/no_grad_set=)
    used = set()
    for op in ops:
        for s in op.arg_specs:
            if s[0] == "var" and s[1] in params_map:
                used.add(params_map[s[1]])
    train_names = (used if program._train_param_names is None
                   else used & program._train_param_names)

    def step(param_arrays, opt_state, lr, feed_arrays):
        sub = {n: param_arrays[n] for n in train_names}

        def loss_fn(sp):
            env = forward({**param_arrays, **sp}, feed_arrays)
            return env[loss_id].astype(jnp.float32), env

        (_, env), grads = jax.value_and_grad(loss_fn, has_aux=True)(sub)
        sub_state = {n: opt_state[n] for n in train_names}
        sub_state["__step__"] = opt_state["__step__"]
        new_sub, new_sub_state = opt.functional_apply(sub, grads,
                                                      sub_state, lr=lr)
        new_p = {**param_arrays, **new_sub}
        new_s = {**opt_state, **new_sub_state}
        return new_p, new_s, [env[i] for i in fetch_ids]

    return step


# re-exports for API-surface parity
from ..nn import ParamAttr  # noqa: E402,F401
from . import nn  # noqa: E402,F401
from .io import load_inference_model, save_inference_model  # noqa: E402,F401


# --------------------------------------------------------------------------
# fluid compat surface (python/paddle/static/__init__.py parity): scope /
# places / program-state helpers. Scopes collapse onto the Program's param
# store; places map to jax devices.
# --------------------------------------------------------------------------

Variable = object  # recorded vars are plain Tensors; kept for isinstance-free code


class _GlobalScope:
    def find_var(self, name):
        prog = default_main_program()
        t = prog._params_by_name.get(name) if hasattr(prog, "_params_by_name") else None

        class _Var:
            def __init__(self, t):
                self._t = t

            def get_tensor(self):
                return self._t

        return _Var(t) if t is not None else None


_global_scope = _GlobalScope()


def global_scope():
    return _global_scope


class scope_guard:
    """Compat context manager: scopes are implicit (one per Program)."""

    def __init__(self, scope):
        self.scope = scope

    def __enter__(self):
        return self.scope

    def __exit__(self, *a):
        return False


import contextlib as _contextlib


@_contextlib.contextmanager
def name_scope(prefix=None):
    yield


def cpu_places(device_count=None):
    import jax

    devs = [d for d in jax.devices() if d.platform == "cpu"] or jax.devices()
    return devs[: device_count or len(devs)]


def cuda_places(device_ids=None):
    import jax

    return list(jax.devices())


def xpu_places(device_ids=None):
    import jax

    return list(jax.devices())


def accuracy(input, label, k=1, correct=None, total=None):
    from ..metric import accuracy as _acc

    return _acc(input, label, k=k, correct=correct, total=total)


def auc(input, label, curve="ROC", num_thresholds=4095, topk=1, slide_steps=1):
    from ..metric import Auc

    m = Auc(curve=curve, num_thresholds=num_thresholds)
    m.update(input, label)
    import numpy as np

    from ..core.tensor import Tensor
    import jax.numpy as jnp

    return Tensor(jnp.asarray(np.float32(m.accumulate())))


def gradients(targets, inputs, target_gradients=None, no_grad_set=None):
    from ..autograd import grad as _grad

    outs = _grad(targets, inputs, grad_outputs=target_gradients,
                 allow_unused=True)
    return outs


def py_func(func, x, out, backward_func=None, skip_vars_in_backward_input=None):
    """py_func_op.cc parity: host-python op on tensor values. With
    `backward_func`, gradients flow: it is attached as the op's VJP and
    receives (*inputs, *outputs, *output_grads) host arrays, returning the
    input grads (the reference's backward py_func contract). Without it the
    outputs are detached — same as the reference, whose py_func has no grad
    op unless backward_func is given."""
    import numpy as np

    import jax
    from ..core.dispatch import apply
    from ..core.tensor import Tensor
    import jax.numpy as jnp

    xs = x if isinstance(x, (list, tuple)) else [x]
    ts = [v if isinstance(v, Tensor) else Tensor(jnp.asarray(np.asarray(v)))
          for v in xs]

    if backward_func is None:
        host = [np.asarray(v._data) for v in ts]
        res = func(*host)
        if not isinstance(res, (list, tuple)):
            res = [res]
        outs = [Tensor(jnp.asarray(np.asarray(r))) for r in res]
        for o in outs:
            o.stop_gradient = True
        return outs if len(outs) > 1 else outs[0]

    multi = [None]  # whether func returned a tuple (fixed at first call)

    @jax.custom_vjp
    def _op(*arrs):
        res = func(*[np.asarray(a) for a in arrs])
        multi[0] = isinstance(res, (list, tuple))
        res = res if multi[0] else [res]
        out = tuple(jnp.asarray(np.asarray(r)) for r in res)
        return out if len(out) > 1 else out[0]

    def _fwd(*arrs):
        out = _op(*arrs)
        return out, (arrs, out if isinstance(out, tuple) else (out,))

    def _bwd(resid, gout):
        arrs, outs_v = resid
        gs = gout if isinstance(gout, tuple) else (gout,)
        host = ([np.asarray(a) for a in arrs]
                + [np.asarray(o) for o in outs_v]
                + [np.asarray(g) for g in gs])
        gx = backward_func(*host)
        if not isinstance(gx, (list, tuple)):
            gx = [gx]
        return tuple(jnp.asarray(np.asarray(g)) for g in gx)

    _op.defvjp(_fwd, _bwd)
    result = apply(_op, *ts)
    return result


def save(program, model_path, protocol=4):
    import pickle

    state = {k: v for k, v in (program.state_dict() or {}).items()}
    import numpy as np

    with open(model_path + ".pdparams" if not model_path.endswith(".pdparams")
              else model_path, "wb") as f:
        pickle.dump({k: np.asarray(t._data) for k, t in state.items()}, f,
                    protocol=protocol)


def _write_program_params(program, arrs):
    """Write named arrays into the Program's parameter scope (state_dict()
    hands out copies, so mutating those would be a silent no-op)."""
    import jax.numpy as jnp

    program._ensure_scope()
    store = program._scope["params"]
    for k, v in arrs.items():
        if k in store:
            store[k] = jnp.asarray(v)
    program._sync_params_to_tensors()


def load(program, model_path, executor=None, var_list=None):
    import pickle

    path = model_path if model_path.endswith(".pdparams") else model_path + ".pdparams"
    with open(path, "rb") as f:
        arrs = pickle.load(f)
    _write_program_params(program, arrs)


def save_vars(executor, dirname, main_program=None, vars=None, predicate=None,
              filename=None):
    save(main_program or default_main_program(),
         __import__("os").path.join(dirname, filename or "params"))


def load_vars(executor, dirname, main_program=None, vars=None, predicate=None,
              filename=None):
    load(main_program or default_main_program(),
         __import__("os").path.join(dirname, filename or "params"))


def load_program_state(model_path, var_list=None):
    import pickle

    path = model_path if model_path.endswith(".pdparams") else model_path + ".pdparams"
    with open(path, "rb") as f:
        return pickle.load(f)


def set_program_state(program, state):
    _write_program_params(program, state)


def Print(input, first_n=-1, message=None, summarize=20, print_tensor_name=True,
          print_tensor_type=True, print_tensor_shape=True,
          print_tensor_layout=True, print_tensor_lod=True,
          print_phase="both"):
    """print_op.cc parity: prints the tensor when the program runs (eager:
    immediately; traced: via jax.debug.print) and passes it through."""
    from ..core.tensor import Tensor
    from ..core.dispatch import apply
    import jax

    def fn(v):
        jax.debug.print((message or "") + "{}", v)
        return v

    return apply(fn, input if isinstance(input, Tensor) else Tensor(input))


def Assert(cond, data=None, summarize=20, name=None):
    """assert_op.cc parity (fluid.layers.Assert): halt with the tensor data
    when `cond` is false. Traced predicates check host-side via debug
    callback (the reference op prints `data` then throws); concrete ones
    raise immediately."""
    from ..jit.dy2static import convert_assert

    items = list(data) if isinstance(data, (list, tuple)) else (
        [data] if data is not None else [])

    def msg():
        shown = []
        for d in items:
            v = d._data if isinstance(d, Tensor) else d
            try:
                shown.append(str(np.asarray(v).reshape(-1)[:summarize]))
            except Exception:  # still-traced aux data: name it, don't crash
                shown.append(f"<traced {getattr(v, 'shape', '?')}>")
        return "Assert failed: " + "; ".join(shown) if shown else \
            "Assert failed"

    convert_assert(cond, msg)


class BuildStrategy:
    """Compat knobs (reference pass toggles). XLA owns fusion/layout here;
    attributes are accepted and ignored."""

    def __setattr__(self, k, v):
        object.__setattr__(self, k, v)


class ExecutionStrategy:
    def __setattr__(self, k, v):
        object.__setattr__(self, k, v)


class CompiledProgram:
    """Compat wrapper: Executor.run already jits the recorded Program, so
    with_data_parallel is a no-op that remembers its Program."""

    def __init__(self, program, build_strategy=None):
        self._program = program

    def with_data_parallel(self, loss_name=None, build_strategy=None,
                           exec_strategy=None, places=None):
        return self


class ParallelExecutor:
    def __init__(self, use_cuda=False, loss_name=None, main_program=None,
                 build_strategy=None, exec_strategy=None, scope=None):
        self._program = main_program or default_main_program()

    def run(self, fetch_list=None, feed=None, return_numpy=True):
        exe = Executor()
        return exe.run(self._program, feed=feed, fetch_list=fetch_list,
                       return_numpy=return_numpy)


class WeightNormParamAttr:
    """Compat: weight-norm reparameterization is applied via
    paddle.nn.utils.weight_norm on layers; this records the intent."""

    def __init__(self, dim=None, name=None, **kwargs):
        self.dim = dim
        self.name = name
        self.kwargs = kwargs
