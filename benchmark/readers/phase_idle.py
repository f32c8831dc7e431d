"""Device idle time by what the PROGRAM was doing: the idle gaps of the
traced window, cut at the boundaries of the program's own step phases, each
piece named by the innermost phase that covers it (a gap that spans several
phases is shared among them; `breakdown.idle_gaps` gives a whole gap to the
span at its midpoint). % of the traced window spent idle under the phases
that params["under"] names (or anything inside them), averaged over the
chips used.

The phases are on the host's perf_counter clock, the device's operations on
the trace's. The `window` span exists on both (the benchmark's span list
and its `bench:window` annotation), and the offset between its two starts
puts the phases on the trace's clock.

Notes the whole table, seconds by innermost phase ("no_span" where none
covers the gap), under notes["idle_by_phase"]; and under
notes["phase_clock_skew_us"] the worst amount by which a mapped phase
without a parent sticks out of the benchmark's own annotation that
encloses it (`bench:eng.step`, `bench:train_step`): the offset's error.
`program_phase` asks for the same notes in a traced run, so that a cell
with no metric of this reader still gets its table.

Returns nothing — never 0 — where the program has no such timeline, where
its ring lost part of the window, where no phase is found, or where the
skew is over 100 us or cannot be checked."""
import bisect

from benchmark import reduce

MAX_SKEW_US = 100.0


def window_phases(ctx, whole=True):
    """The program's phases of the measured window as `(name, start_s,
    end_s, parent_name, step_no, counts)`, perf_counter seconds, oldest
    first. whole=False keeps those that only overlap the window too. None
    where the program records none or its ring lost part of the window."""
    try:
        from paddle_tpu import trace

        phases = trace.phases
    except (ImportError, AttributeError):
        return None
    c = ctx.counters
    lo, hi = c["t_open"], c["t_open"] + c["window_s"]
    rows, lost = phases(since_ns=int(lo * 1e9))
    if lost:
        return None
    rows = [(n, s * 1e-9, e * 1e-9, parent, step, counts)
            for n, s, e, parent, step, counts in rows]
    if whole:
        return [r for r in rows if lo <= r[1] and r[2] <= hi]
    return [r for r in rows if r[2] >= lo and r[1] <= hi]


def clock_skew_us(roots, host):
    """Worst overhang, in us, of a root phase over the innermost of the
    benchmark's annotations that covers its midpoint; None where no root
    lies under one."""
    worst = None
    for _, s, e in roots:
        mid = (s + e) / 2
        cover = [(b - a, a, b) for _, a, b in host if a <= mid <= b]
        if not cover:
            continue
        _, a, b = min(cover)
        worst = max(worst or 0.0, a - s, e - b)
    return None if worst is None else worst * 1e6


def cut(gaps, spans):
    """The gaps cut at every start and end of `spans` that falls inside
    one, so that each piece lies in one innermost span."""
    bounds = sorted({t for _, s, e in spans for t in (s, e)})
    out = []
    for s, e in gaps:
        at = s
        for b in bounds[bisect.bisect_right(bounds, s):
                        bisect.bisect_left(bounds, e)]:
            out.append((at, b))
            at = b
        out.append((at, e))
    return out


def idle_table(ctx):
    """(idle gaps, phases on the trace's clock, chips), after noting the
    skew and the whole table; None where any of the conditions above
    fails. Computed once a run."""
    if hasattr(ctx, "_phase_idle"):
        return ctx._phase_idle
    ctx._phase_idle = None
    tr = ctx.trace
    if tr is None or not tr.devices or tr.window_s <= 0:
        return None
    rows = window_phases(ctx, whole=False)
    here = [s for n, s, _ in ctx.spans.items if n == "window"]
    if not rows or not here:
        return None
    offset = tr.window[0] - here[-1]
    mapped = [(n, s + offset, e + offset) for n, s, e, *_ in rows]
    skew = clock_skew_us(
        [m for m, r in zip(mapped, rows) if r[3] is None],
        [h for h in tr.host if h[0] != "window"])
    ctx.notes["phase_clock_skew_us"] = skew
    if skew is None or skew > MAX_SKEW_US:
        return None
    n_dev = len(tr.devices)
    idle = cut([g for d in tr.devices for g in reduce.gaps(d, tr.window)],
               mapped)
    ctx.notes["idle_by_phase"] = [
        [k, v / n_dev] for k, v in
        reduce.attribute_gaps(idle, mapped, len(mapped) + 1)]
    ctx._phase_idle = idle, mapped, n_dev
    return ctx._phase_idle


def read(ctx, params):
    table = idle_table(ctx)
    if table is None:
        return None
    idle, mapped, n_dev = table
    under = set(params["under"])
    inside = reduce.attribute_gaps(
        idle, [m for m in mapped if m[0] in under], len(under) + 1)
    idle_s = sum(v for k, v in inside if k != "no_span") / n_dev
    return 100.0 * idle_s / ctx.trace.window_s
