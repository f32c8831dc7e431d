"""The benchmark's own spans, the profiler's trace, and compile counting.

Spans are recorded by the benchmark around its calls into the program
(perf_counter seconds). In a traced run each is also a
jax.profiler.TraceAnnotation ("bench:<name>"), so that it lands in the
profiler's trace on the same clock as the device's operations.
"""
import contextlib
import functools
import glob
import os
import time

import jax

from benchmark import reduce

#: lines of a device plane that are not single operations
_NOT_OPS = ("Steps", "XLA Modules", "XLA TraceMe", "Framework Ops",
            "Framework Name Scope", "Source code", "Launch Stats")


class Spans:
    def __init__(self):
        self.items = []          # (name, start_s, end_s), perf_counter
        self.annotate = False

    @contextlib.contextmanager
    def span(self, name):
        t0 = time.perf_counter()
        try:
            if self.annotate:
                with jax.profiler.TraceAnnotation("bench:" + name):
                    yield
            else:
                yield
        finally:
            self.items.append((name, t0, time.perf_counter()))

    def durations(self, name, since=0.0):
        return [b - a for n, a, b in self.items if n == name and a >= since]


class CompileCounter:
    """Counts XLA backend compilations (every one, cached or not: a
    persistent-cache hit does not reach the backend)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, duration, **kw):
        if name == self.EVENT:
            self.count += 1

    def take(self):
        n, self.count = self.count, 0
        return n


class Trace:
    """What a traced window holds. Times in seconds on the trace's clock.
    devices: one list of (name, start, end) operations a device.
    host:    the benchmark's own annotations (name without "bench:")."""

    def __init__(self, devices, host, window, what=None):
        self.devices, self.host, self.window = devices, host, window
        self.what = what or {}     # operation name -> what it computes

    @property
    def window_s(self):
        return self.window[1] - self.window[0]

    @functools.cached_property
    def busy_s(self):
        """Seconds of the window in which an operation ran on the device,
        averaged over the devices; 0.0 where none did."""
        busy = [reduce.busy(d, self.window) for d in self.devices]
        return sum(busy) / len(busy) if busy else 0.0


def start(trace_dir):
    """Device operations and the benchmark's own annotations only: the
    profiler's Python tracer (on by default) hooks every Python call, and
    a host loop that it slows reads as device idle time."""
    os.makedirs(trace_dir, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def stop():
    jax.profiler.stop_trace()


def newest_xplane(trace_dir):
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"the profiler left no trace in {trace_dir}")
    return max(files, key=os.path.getmtime)


def planes(path):
    """[(plane name, [(line name, [(event name, start_ns, dur_ns)])])]."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    return [(p.name, [(ln.name, [(ev.name, ev.start_ns, ev.duration_ns)
                                 for ev in ln.events])
                      for ln in p.lines])
            for p in data.planes]


def op_name(text):
    """The profiler names a TPU operation by its whole HLO instruction
    ("%fusion.9 = f32[..] fusion(...)"): the instruction's own name."""
    return text.split(" = ", 1)[0].lstrip("%")


def load(path, n_devices):
    """Read an .xplane.pb into a Trace. Device planes are "/device:TPU:n";
    their operations are the "XLA Ops" line (else every line that is not
    a grouping). On a backend without device planes (the CPU rehearsal)
    the host's XLA threads stand in, so that the path still runs."""
    all_planes = planes(path)
    dev = [(n, lines) for n, lines in all_planes if n.startswith("/device:")
           and "TPU" in n]
    devices, what = [], {}
    for _, lines in sorted(dev)[:n_devices]:
        ops = [ln for ln in lines if ln[0] == "XLA Ops"] or \
              [ln for ln in lines if ln[0] not in _NOT_OPS]
        devices.append([(op_name(name), s * 1e-9, (s + d) * 1e-9)
                        for _, evs in ops for name, s, d in evs])
        for _, evs in ops:
            for name, _, _ in evs:
                if " = " in name:
                    what.setdefault(op_name(name),
                                    name.split(" = ", 1)[1][:72])
    host = []
    for n, lines in all_planes:
        if n.startswith("/device:"):
            continue
        for ln_name, evs in lines:
            for name, s, d in evs:
                if name.startswith("bench:"):
                    host.append((name[6:], s * 1e-9, (s + d) * 1e-9))
            if not dev and ("XLA" in ln_name or "Eigen" in ln_name
                            or "pjrt" in ln_name.lower()):
                devices.append([(name, s * 1e-9, (s + d) * 1e-9)
                                for name, s, d in evs
                                if not name.startswith("bench:")])
    devices = [d for d in devices if d]
    win = [h for h in host if h[0] == "window"]
    if win:
        window = (win[0][1], win[0][2])
    else:
        every = [t for d in devices for _, s, e in d for t in (s, e)]
        window = (min(every), max(every)) if every else (0.0, 0.0)
    return Trace(devices, host, window, what)
