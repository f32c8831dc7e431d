"""Profiler.

Reference parity: paddle/fluid/platform/profiler.{h,cc} (RecordEvent:127,
EnableProfiler/DisableProfiler:210-213, event trees -> Profile proto) +
fluid/profiler.py context manager + tools/timeline.py chrome-trace conversion.

TPU-native design: host events keep the RecordEvent tree in pure python; device-side
capture delegates to jax.profiler (XPlane -> TensorBoard / Perfetto, replacing the CUPTI
DeviceTracer). `export_chrome_tracing` emits chrome://tracing JSON like timeline.py.
"""
import contextlib
import threading
import time

import jax

_LOCAL = threading.local()
_ENABLED = [False]
_EVENTS = []  # (name, start_ns, end_ns, thread_id, depth)
_LOCK = threading.Lock()
# the jax device trace is PROCESS state (one trace per process), so its
# on/off flag must be module state: keeping it in threading.local meant a
# stop_profiler from any thread other than the starter silently leaked
# the running trace (the watchdog/monitor threads are exactly such callers)
_JAX_TRACE = [False]


class RecordEvent:
    """platform/profiler.h:127 RAII RecordEvent parity."""

    def __init__(self, name, event_type=None):
        self.name = name
        self._start = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *a):
        self.end()

    def begin(self):
        if not hasattr(_LOCAL, "depth"):
            _LOCAL.depth = 0
        self._start = time.perf_counter_ns()
        _LOCAL.depth += 1

    def end(self):
        if self._start is None or not _ENABLED[0]:
            if hasattr(_LOCAL, "depth") and _LOCAL.depth > 0:
                _LOCAL.depth -= 1
            return
        end = time.perf_counter_ns()
        _LOCAL.depth -= 1
        with _LOCK:
            _EVENTS.append((self.name, self._start, end, threading.get_ident(), _LOCAL.depth))


def start_profiler(state="All", tracer_option="Default", log_dir=None):
    """EnableProfiler parity; also starts the jax device trace when a log_dir is given."""
    _ENABLED[0] = True
    with _LOCK:
        _EVENTS.clear()
    if log_dir:
        with _LOCK:
            jax.profiler.start_trace(log_dir)
            _JAX_TRACE[0] = True


def stop_profiler(sorted_key=None, profile_path="/tmp/profile"):
    _ENABLED[0] = False
    with _LOCK:
        if _JAX_TRACE[0]:
            jax.profiler.stop_trace()
            _JAX_TRACE[0] = False
    return summary(sorted_key)


def host_events():
    """Snapshot of the recorded host events, sorted by start time —
    (name, start_ns, end_ns, thread_id, depth) tuples. The read is taken
    under _LOCK: concurrent RecordEvent.end appends must never be seen
    half-way (list.append is atomic, but iterating while appending from
    another thread can observe a torn ordering)."""
    with _LOCK:
        evts = list(_EVENTS)
    evts.sort(key=lambda e: e[1])
    return evts


def summary(sorted_key=None):
    agg = {}
    for name, s, e, tid, depth in host_events():
        st = agg.setdefault(name, [0, 0.0, float("inf"), 0.0])
        dur = (e - s) / 1e6
        st[0] += 1
        st[1] += dur
        st[2] = min(st[2], dur)
        st[3] = max(st[3], dur)
    rows = [
        {"name": k, "calls": v[0], "total_ms": v[1], "min_ms": v[2], "max_ms": v[3],
         "avg_ms": v[1] / v[0] if v[0] else 0.0}
        for k, v in agg.items()
    ]
    return _sort_rows(rows, sorted_key)


def _sort_rows(rows, sorted_key):
    if sorted_key in ("total", None):
        rows.sort(key=lambda r: -r["total_ms"])
    elif sorted_key == "calls":
        rows.sort(key=lambda r: -r["calls"])
    elif sorted_key in ("avg", "ave"):
        rows.sort(key=lambda r: -r["avg_ms"])
    elif sorted_key == "max":
        rows.sort(key=lambda r: -r["max_ms"])
    elif sorted_key == "min":
        rows.sort(key=lambda r: -r["min_ms"])
    return rows


def export_chrome_tracing(path):
    """tools/timeline.py parity: chrome://tracing JSON. Delegates to the
    merged exporter (paddle_tpu.trace.export_chrome), so host events are
    emitted sorted by start time — nested RecordEvents render as a tree
    from ts/dur ordering instead of unordered same-tier slices — and the
    old API's output gains whatever trace spans / step phases exist."""
    from .. import trace as _trace

    _trace.export_chrome(path)
    return path


@contextlib.contextmanager
def profiler(state="All", sorted_key="total", profile_path="/tmp/profile", log_dir=None):
    """fluid/profiler.py profiler context-manager parity."""
    start_profiler(state, log_dir=log_dir)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


class Profiler:
    """paddle.profiler.Profiler (2.x API shape) — wraps the same machinery."""

    def __init__(self, targets=None, scheduler=None, on_trace_ready=None, timer_only=False, log_dir=None):
        self._log_dir = log_dir
        self._rows = None

    def start(self):
        start_profiler(log_dir=self._log_dir)

    def stop(self):
        self._rows = stop_profiler()

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *a):
        self.stop()

    def summary(self, sorted_by=None, **kw):
        """Rows from the last stop() (or the live buffer), honoring
        sorted_by ("total"|"calls"|"avg"|"max"|"min") — previously the
        argument was silently ignored."""
        if self._rows is None:
            return summary(sorted_by)
        return _sort_rows(list(self._rows), sorted_by)
