"""Device cost registry: per-executable FLOPs/bytes/HBM accounting.

XLA already knows what every compiled program costs —
``compiled.cost_analysis()`` (flops, bytes accessed) and
``compiled.memory_analysis()`` (argument/output/temp/peak HBM) — but
until now that data only surfaced in ad-hoc scripts
(tools/profile_gpt.py, tools/pipeline_memory.py). This module captures
it ONCE at every compile site — ``Executor._compile``,
``SpmdTrainer._aot_compile``, the ``ServingEngine``/``Predictor``
``CachedJit`` program family, including AOT-cache deserialize hits in
framework/aot.py — into a per-executable table keyed ``(site, sig)``,
and exports it as gauges:

- ``program_flops{site,sig}`` — per-execution FLOPs of the executable;
- ``program_hbm_bytes{site,kind}`` — kind in
  ``peak|argument|output|temp|generated_code`` for the site's most
  recently captured executable (full per-sig detail: :func:`table`);
- ``device_hbm_used_bytes{device}`` — sampled from
  ``device.memory_stats()`` where the backend provides it
  (:func:`sample_device_memory`; TPU yes, CPU no).

Joined with measured step wall time this is the roofline/MFU layer
(Tensor Processing Primitives, arXiv:2104.05755): a step's model FLOPs
over ``wall_time × peak_flops`` — ``SpmdTrainer.stats()["mfu"]`` and
``ServingEngine.stats()["breakdown"]`` read through :func:`get`.

Capture never raises: a backend whose executables lack cost analysis
degrades to an absent entry, not a crashed compile path.
"""
import threading

from .. import flags as _flags
from .. import monitor as _monitor

__all__ = ["record", "record_manual", "get", "table", "reset",
           "sample_device_memory", "peak_flops", "peak_hbm_bandwidth"]

_flags.define_flag(
    "device_peak_flops", 0.0,
    "peak device FLOP/s used as the MFU denominator; 0 = auto from the "
    "device kind table (a TPU kind the table does not know raises; the "
    "CPU test harness gets a nominal 1e12 so MFU stays finite there)")

_LOCK = threading.Lock()
_TABLE = {}   # (site, sig) -> entry dict

_FLOPS_G = None
_HBM_G = None
_DEV_G = None

_HBM_KINDS = ("peak", "argument", "output", "temp", "generated_code")

#: bf16 peak FLOP/s per chip by device-kind substring (TPU datasheet
#: numbers); matched case-insensitively, first hit wins
_PEAK_FLOPS_BY_KIND = (
    ("v6e", 918e12),
    ("v5p", 459e12),
    ("v5e", 197e12),
    ("v5 lite", 197e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)
_NOMINAL_PEAK = 1e12

#: HBM bytes/s per chip by device-kind substring (approximate datasheet
#: numbers — the bandwidth side of the roofline the plan-search cost
#: model prices against); same matching rules as the FLOPs table
_PEAK_HBM_BW_BY_KIND = (
    ("v6e", 1.6e12),
    ("v5p", 2.8e12),
    ("v5e", 0.8e12),
    ("v5 lite", 0.8e12),
    ("v4", 1.2e12),
    ("v3", 0.9e12),
    ("v2", 0.7e12),
)
_NOMINAL_HBM_BW = 1e11


def _gauges():
    global _FLOPS_G, _HBM_G, _DEV_G
    if _FLOPS_G is None:
        _FLOPS_G = _monitor.gauge(
            "program_flops",
            "per-execution FLOPs of a compiled executable "
            "(XLA cost_analysis)", labelnames=("site", "sig"))
        _HBM_G = _monitor.gauge(
            "program_hbm_bytes",
            "HBM footprint of the site's most recently captured "
            "executable by kind (XLA memory_analysis; per-sig detail in "
            "trace.costs.table())", labelnames=("site", "kind"))
        _DEV_G = _monitor.gauge(
            "device_hbm_used_bytes",
            "live device memory in use (device.memory_stats(), where the "
            "backend provides it)", labelnames=("device",))
    return _FLOPS_G, _HBM_G, _DEV_G


def _cost_dict(compiled):
    """cost_analysis() returns a dict on some backends, a one-element
    list of dicts on others — normalize to one merged dict."""
    ca = compiled.cost_analysis()
    if isinstance(ca, dict):
        return ca
    out = {}
    for d in ca or []:
        for k, v in d.items():
            out[k] = out.get(k, 0.0) + float(v)
    return out


def record(site, sig, compiled):
    """Capture one executable's cost+memory analysis under (site, sig).
    `compiled` may be None (bypass paths) — a no-op then. Returns the
    entry dict or None. Never raises."""
    if compiled is None:
        return None
    try:
        cost = _cost_dict(compiled)
        entry = {"site": str(site), "sig": str(sig),
                 "flops": float(cost.get("flops", 0.0)),
                 "bytes_accessed": float(cost.get("bytes accessed", 0.0))}
        try:
            ma = compiled.memory_analysis()
            arg = int(getattr(ma, "argument_size_in_bytes", 0))
            out = int(getattr(ma, "output_size_in_bytes", 0))
            tmp = int(getattr(ma, "temp_size_in_bytes", 0))
            gen = int(getattr(ma, "generated_code_size_in_bytes", 0))
            # donated buffers appear in BOTH argument and output sizes;
            # alias_size is that overlap — subtract it or the serving
            # decode programs (which donate the KV caches, their largest
            # buffers) overstate peak HBM by up to 2x
            alias = int(getattr(ma, "alias_size_in_bytes", 0))
            entry.update(argument_bytes=arg, output_bytes=out,
                         temp_bytes=tmp, generated_code_bytes=gen,
                         alias_bytes=alias,
                         peak_bytes=arg + out + tmp + gen - alias)
        except Exception:
            pass
    except Exception:
        return None
    with _LOCK:
        _TABLE[(str(site), str(sig))] = entry
    if _monitor.is_enabled():
        flops_g, hbm_g, _ = _gauges()
        flops_g.labels(site=site, sig=sig).set(entry["flops"])
        for kind in _HBM_KINDS:
            v = entry.get(f"{kind}_bytes")
            if v is not None:
                hbm_g.labels(site=site, kind=kind).set(v)
    return entry


def record_manual(site, sig, flops=0.0, bytes_accessed=0.0):
    """Capture an ANALYTIC cost entry under (site, sig) — for work that
    has no standalone executable to ask, e.g. a Pallas micro-kernel
    living inside a larger jitted program (ops/tpp.py registers each
    op's per-call FLOPs/bytes here under site="tpp"). Repeated calls
    ACCUMULATE (a kernel invoked N times per trace reports N times its
    per-call cost) and bump a ``calls`` field; the same gauges as
    :func:`record` are updated. Never raises."""
    try:
        with _LOCK:
            entry = _TABLE.get((str(site), str(sig)))
            if entry is None:
                entry = {"site": str(site), "sig": str(sig),
                         "flops": 0.0, "bytes_accessed": 0.0, "calls": 0}
                _TABLE[(str(site), str(sig))] = entry
            entry["flops"] += float(flops)
            entry["bytes_accessed"] += float(bytes_accessed)
            entry["calls"] += 1
            snap = dict(entry)
        if _monitor.is_enabled():
            flops_g, _, _ = _gauges()
            flops_g.labels(site=site, sig=sig).set(snap["flops"])
        return snap
    except Exception:
        return None


def get(site, sig):
    """The captured entry for (site, sig), or None."""
    with _LOCK:
        return _TABLE.get((str(site), str(sig)))


def table():
    """Snapshot of every captured entry (list of dicts)."""
    with _LOCK:
        return [dict(v) for v in _TABLE.values()]


def reset():
    with _LOCK:
        _TABLE.clear()


def sample_device_memory():
    """Set device_hbm_used_bytes{device} from device.memory_stats() for
    every device that reports it; returns {device_str: bytes_in_use}.
    CPU backends report nothing — the gauge simply stays absent."""
    import jax

    out = {}
    for d in jax.devices():
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if not stats:
            continue
        used = stats.get("bytes_in_use")
        if used is None:
            continue
        out[str(d)] = int(used)
        if _monitor.is_enabled():
            _gauges()[2].labels(device=str(d)).set(int(used))
    return out


def _peak_for(device, table, nominal, what):
    """Look the device's kind up in `table`. The nominal constant is for
    the CPU test harness only: on a TPU an unknown ``device_kind`` raises
    — a utilization against an invented peak is worse than none."""
    import jax

    d = device or jax.devices()[0]
    kind = str(getattr(d, "device_kind", d.platform)).lower()
    for needle, peak in table:
        if needle in kind:
            return peak
    if d.platform == "tpu":
        raise ValueError(
            f"no {what} on record for TPU device_kind {d.device_kind!r}; "
            f"add it to trace/costs.py (known: "
            f"{', '.join(n for n, _ in table)})")
    return nominal


def peak_flops(device=None):
    """The MFU denominator: FLAGS_device_peak_flops when set, else the
    device-kind table; off-TPU a nominal 1e12 (keeps MFU finite on the
    CPU test harness), on an unknown TPU kind an error."""
    override = float(_flags.get_flag("device_peak_flops", 0.0) or 0.0)
    if override > 0:
        return override
    return _peak_for(device, _PEAK_FLOPS_BY_KIND, _NOMINAL_PEAK,
                     "peak FLOP/s")


def peak_hbm_bandwidth(device=None):
    """Peak HBM bytes/s from the device-kind table — the bandwidth
    denominator of the roofline (analysis/cost_model.py prices
    ``max(flops/peak, bytes/bw)`` with it). Off-TPU a nominal 1e11; on an
    unknown TPU kind an error, like :func:`peak_flops`."""
    return _peak_for(device, _PEAK_HBM_BW_BY_KIND, _NOMINAL_HBM_BW,
                     "peak HBM bandwidth")
