"""The device's time under the program's own names, from one kept capture.

    python3 benchmark/tools/scope_table.py <kept trace dir or .xplane.pb>
        [--workload <cell>] [--line <the run's result line, a .json file>]
        [--fixture <out.json.gz> [--fixture-seconds 0.3]]

Prints what the capture's lines hold; self time by program, scope and
direction (benchmark/scopes.py; seconds, % of the window's busy time, ms
a run of the program); what is left `unscoped`, by kind; and the device's
idle gaps by the program's innermost step phase TAKEN FROM THE CAPTURE
ITSELF (every `trace.phase` is a TraceAnnotation, so it is in the profile
on the device's clock), with --line beside what `phase_idle` made of the
program's ring through the offset of the two `window` spans
(`run.notes.idle_by_phase`): the check of that offset against the
profiler's own clock. A capture is kept by `run.run_cell(..., keep_trace=)`.
--workload gives the chips the cell uses (default 1).
"""
import argparse
import collections
import gzip
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def capture(path, n_devices=1):
    """A `tracing.Trace` of the capture with the two lines `tracing.load`
    drops handed to it (`programs`, `scopes`: readers/scope_time.py) and the
    program's own phases as the capture holds them (`phases`)."""
    from benchmark import scopes, tracing

    path = path if os.path.isfile(path) else tracing.newest_xplane(path)
    lines = tracing.planes(path)
    tr = tracing.load(path, n_devices)
    tr.programs, tr.scopes = scopes.load(path, lines, n_devices,
                                         tracing.op_name)
    tr.phases = scopes.host_phases(lines)
    tr.lines = [(plane, [(ln, len(evs)) for ln, evs in lns if evs])
                for plane, lns in lines]
    return tr


def hand(ctx, keep_trace, n_devices=1):
    """Hand a finished traced run's Ctx the kept profile's two lines, as
    `tracing.load` will once it keeps them (PERF.md section 7)."""
    full = capture(keep_trace, n_devices)
    ctx.trace.programs, ctx.trace.scopes = full.programs, full.scopes
    return ctx


def cut(tr, seconds, start=0.0):
    """The first device's capture from `start` seconds into the window, for
    `seconds`: what a recorded fixture holds."""
    from benchmark import reduce

    lo = tr.window[0] + start
    win = (lo, lo + seconds)
    ops, programs = [], []
    if tr.scopes:        # a capture with a device plane
        ops = [[n, s, e, p] for (n, s, e), p in zip(tr.devices[0],
                                                     tr.scopes[0])
               if e > win[0] and s < win[1]]
        programs = reduce.clip_events(tr.programs[0], win)
    return {"window": win, "ops": ops, "programs": programs,
            "phases": reduce.clip_events(tr.phases, win)}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("path")
    ap.add_argument("--workload")
    ap.add_argument("--line")
    ap.add_argument("--fixture")
    ap.add_argument("--fixture-seconds", type=float, default=0.3)
    ap.add_argument("--fixture-start", type=float, default=1.0)
    args = ap.parse_args(argv)
    from benchmark import reduce, scopes

    chips = 1
    if args.workload:
        with open(os.path.join(ROOT, "benchmark", "workloads",
                               args.workload + ".json")) as f:
            chips = int(json.load(f)["chips"])
    line = None
    if args.line:
        with open(args.line) as f:
            line = json.load(f)
    tr = capture(args.path, chips)
    if args.fixture:
        with gzip.open(args.fixture, "wt") as f:
            json.dump(cut(tr, args.fixture_seconds, args.fixture_start), f)
    for plane, lns in tr.lines:
        if lns:
            print(f"PLANE {plane!r}: " + ", ".join(
                f"{ln!r} {n}" for ln, n in lns))
    n_dev = len(tr.devices)
    total = collections.Counter()
    unscoped = collections.Counter()
    for ops, paths, programs in zip(tr.devices, tr.scopes, tr.programs):
        for key, v in scopes.table(ops, paths, programs, tr.window).items():
            total[key] += v / n_dev
        for kind, v in scopes.unscoped_kinds(ops, paths, tr.window, 40):
            unscoped[kind] += v / n_dev
    busy = tr.busy_s or float("nan")
    print(f"\nwindow {tr.window_s:.4f} s, busy {tr.busy_s:.4f} s, table "
          f"total {sum(total.values()):.4f} s ({len(tr.devices)} device(s))")
    # how often each program ran in the window: its ms a run
    runs = collections.Counter(
        name for name, s, e in (tr.programs[0] if tr.programs else ())
        if tr.window[0] <= (s + e) / 2 < tr.window[1])

    def row(label, v, n):
        each = f"{1e3 * v / n:9.3f}" if n else " " * 9
        print(f"{label} {v:9.4f} {100 * v / busy:7.2f} {each}")

    print(f"{'program':34} {'scope':13} {'dir':5} {'seconds':>9} "
          f"{'% busy':>7} {'ms a run':>9}")
    by_program = collections.Counter()
    for (program, scope, way), v in sorted(
            total.items(), key=lambda kv: (kv[0][0], -kv[1])):
        by_program[program] += v
        if v >= 0.0005 * busy:
            row(f"{program:34} {scope:13} {way:5}", v, runs[program])
    print("\nby program (runs in the window):")
    for program, v in by_program.most_common():
        row(f"  {program:34} x{runs[program]:<6}", v, runs[program])
    print("\nby scope and direction, every program:")
    by_scope = collections.Counter()
    for (_, scope, way), v in total.items():
        by_scope[(scope, way)] += v
    for (scope, way), v in by_scope.most_common():
        row(f"  {scope:13} {way:5}", v, 0)
    left = sum(v for (_, scope, _), v in total.items()
               if scope == scopes.UNSCOPED)
    print(f"\nunscoped {left:.4f} s, {100 * left / busy:.2f} % of busy, "
          "by kind:")
    for kind, v in unscoped.most_common(16):
        print(f"  {kind:44} {v:9.4f} {100 * v / busy:7.2f}")
    idle = scopes.idle_by_phase(tr.devices, tr.phases, tr.window)
    print("\nidle by the program's innermost phase, from the capture's own "
          "annotations (s):")
    ring = dict(line["run"]["notes"].get("idle_by_phase") or []) \
        if line else {}
    for name, v in idle:
        beside = (f"   ring through the offset {ring[name]:.6f}, "
                  f"difference {1e6 * (v - ring[name]):+.1f} us"
                  if name in ring else "")
        print(f"  {name:24} {v:.6f}{beside}")
    if line:
        print("  skew the ring's mapping noted: "
              f"{line['run']['notes'].get('phase_clock_skew_us')} us")
    return 0


if __name__ == "__main__":
    sys.exit(main())
