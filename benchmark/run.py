"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds everything by name: benchmark/workloads/<cell>.json (the runner, the
configuration, the traffic mix, the limits), benchmark/configs/<config>.json,
benchmark/traffic/<mix>.json, benchmark/runners/<runner>.py, and for a
traced run every per-layer metric of BENCHMARK.json that lists the cell,
each with its benchmark/metrics/<metric>.json and benchmark/readers/<reader>.py.
A later cell, configuration or metric is files and manifest entries only.

Needs the chips the cell asks for: without a TPU it exits 2 and prints no
result. The last line of stdout is one JSON object (see README.md).
A workload file marked "rehearsal" runs on any backend at a tiny size; its
last line never reads "correct": true.
"""
import time

T_START = time.perf_counter()   # before anything heavy is imported

import argparse      # noqa: E402
import gc            # noqa: E402
import importlib     # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import shutil        # noqa: E402
import sys           # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _load(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


class Ctx:
    """What a runner and the readers are handed: data, never code paths."""

    def __init__(self, **kw):
        self.__dict__.update(kw)
        self._mark = time.perf_counter()

    def mark(self, phase):
        """Seconds since the last mark, noted under notes["setup"]."""
        now = time.perf_counter()
        self.notes.setdefault("setup", {})[phase] = now - self._mark
        self._mark = now


def _applies(metric, cell):
    return "workloads" not in metric or cell in metric["workloads"]


def device_info(devices):
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def open_cell(workload, seed, require_chip=True):
    """Load a cell's files, look for its chips, switch the compile cache
    on. Returns a Ctx, or None (after a message) where the chips are not
    there."""
    wl = _load("workloads", workload + ".json")
    rehearsal = bool(wl.get("rehearsal"))

    import jax

    devices = jax.devices()
    chips = int(wl["chips"])
    if require_chip and not rehearsal and (
            devices[0].platform != "tpu" or len(devices) < chips):
        print(f"benchmark/run.py: cell {workload!r} needs {chips} TPU "
              f"chip(s); jax found {len(devices)} x {devices[0].platform}. "
              "Nothing was run.", file=sys.stderr)
        return None
    devices = devices[:chips]

    import paddle_tpu as paddle

    from benchmark import counts, tracing, traffic

    on_chip = devices[0].platform == "tpu"
    if on_chip:
        # jax's persistent cache at the program's one fixed place (inside
        # the checkout, or where JAX_COMPILATION_CACHE_DIR says); every
        # program is kept, also those that compile in under a second
        paddle.enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return Ctx(name=workload, workload=wl, cell=wl.get("stands_for", workload),
               cfg=_load("configs", wl["config"] + ".json"),
               mix=traffic.load(wl["traffic"]), seed=int(seed),
               devices=devices, chips=chips, spans=tracing.Spans(),
               compiles=tracing.CompileCounter(), rehearsal=rehearsal,
               trace=None, counters=None, notes={},
               peak=counts.peaks(devices[0].device_kind if on_chip
                                 else "TPU v5 lite"))


def make_runner(ctx):
    return importlib.import_module(
        "benchmark.runners." + ctx.workload["runner"]).Runner(ctx)


def run_cell(workload, seed, seconds, trace, require_chip=True,
             keep_trace=None):
    """Run one cell; returns (exit code, result dict or None).
    keep_trace: a directory to leave the profiler's trace in."""
    ctx = open_cell(workload, seed, require_chip)
    if ctx is None:
        return 2, None
    from benchmark import compare, tracing

    wl, cell, devices = ctx.workload, ctx.cell, ctx.devices
    spans, compiles = ctx.spans, ctx.compiles
    trace_dir = keep_trace
    ctx.notes["setup"] = {"imports_s": ctx._mark - T_START}
    runner = make_runner(ctx)
    runner.setup()
    setup_s = time.perf_counter() - T_START
    compiles.take()

    if trace:
        seconds = min(float(seconds), float(wl.get("trace_seconds", 3)))
        trace_dir = trace_dir or os.path.join(ROOT, ".bench_trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
        tracing.start(trace_dir)
        spans.annotate = True
    t_window = time.perf_counter()
    try:
        with spans.span("window"):
            ctx.counters = runner.window(float(seconds))
    finally:
        if trace:
            spans.annotate = False
            t_stop = time.perf_counter()
            tracing.stop()
            ctx.notes["trace_stop_s"] = time.perf_counter() - t_stop
    compiled_in_window = compiles.take()
    device = device_info(devices)
    runner.release()
    gc.collect()

    t_check = time.perf_counter()
    numbers = runner.check()
    ctx.notes["check_s"] = time.perf_counter() - t_check
    numbers["compiles_in_window"] = compiled_in_window
    numbers["failed"] = ctx.counters["failed"]
    checked, ok, ctx.notes["not_compared"] = compare.verdict(numbers,
                                                             wl["limits"])

    man = manifest()
    metrics = {}
    breakdown = None
    if trace:
        t_load = time.perf_counter()
        ctx.trace = tracing.load(tracing.newest_xplane(trace_dir),
                                 len(devices))
        ctx.notes["trace_load_s"] = time.perf_counter() - t_load
        if not keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
        from benchmark import reduce

        device["busy_s"] = ctx.trace.busy_s
        device["window_s"] = ctx.trace.window_s
        for m in man["per_layer"]:
            if not _applies(m, cell):
                continue
            spec = _load("metrics", m["name"] + ".json")
            reader = importlib.import_module(
                "benchmark.readers." + spec["reader"])
            value = reader.read(ctx, spec.get("params", {}))
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        ops = [ev for d in ctx.trace.devices for ev in d]
        n_dev = max(1, len(ctx.trace.devices))
        idle = [g for d in ctx.trace.devices
                for g in reduce.gaps(d, ctx.trace.window)]
        # the kinds of operation that took most time (one entry for the
        # same operation of every layer), then the single longest ones
        # with what they compute
        breakdown = {
            "device_ops": [["kind:" + k, v / n_dev] for k, v in
                           reduce.top_by_kind(ops, 6)]
            + [[f"op:{k} {ctx.trace.what.get(k, '')}".strip(), v / n_dev]
               for k, v in reduce.top_by_name(ops, 4)],
            "idle_gaps": [[k, v / n_dev] for k, v in reduce.attribute_gaps(
                idle, [h for h in ctx.trace.host if h[0] != "window"], 10)]}
    else:
        values = dict(ctx.counters["end_to_end"], setup_s=setup_s)
        for m in man["end_to_end"]:
            if _applies(m, cell):
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}

    result = {"correct": bool(ok), "attempted": ctx.counters["attempted"],
              "failed": ctx.counters["failed"], "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["run"] = {"workload": workload, "seed": int(seed),
                     "setup_s": setup_s,
                     "window_s": ctx.counters["window_s"],
                     "window_to_end_s": time.perf_counter() - t_window,
                     "notes": ctx.notes}
    result["checked"] = {n: {"value": v, "limit": lim}
                         for n, v, lim in checked}
    return 0, result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    seconds = args.seconds if args.seconds is not None \
        else manifest()["run_seconds"]
    rc, result = run_cell(args.workload, args.seed, seconds, bool(args.trace))
    if result is None:
        return rc
    if _load("workloads", args.workload + ".json").get("rehearsal"):
        # a rehearsal's last line can never be read as a result
        result = dict({"rehearsal": "passed" if result["correct"]
                       else "failed"}, **result)
        result["correct"] = False
    for name, c in result["checked"].items():
        print(f"checked {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
