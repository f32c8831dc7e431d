"""A number from the program's own step-phase timeline
(`paddle_tpu.trace.phases()`: one `(name, start_ns, end_ns, parent_name,
step_no, counts)` a phase, on the host's perf_counter clock, recorded by the
program where the work happens) over the measured window.

Only phases that lie whole inside the window count: `t_open <= start` and
`end <= t_open + window_s`.

params: "root": the name of the phase that is read; "minus": names of
phases inside it (same `step_no`) whose time is taken out of each one
(optional); "reduce": "median" (over the root's occurrences) or
"share_of_window" (their sum over the window's seconds); "scale": a factor
(1000 -> ms, 100 -> %).

Also notes the median of every phase name it saw, in ms, under
notes["phase_ms"], and in a traced run the table of `phase_idle`. Returns
nothing — never 0 — where the program has no such timeline (an older
program), where its ring lost part of the window, or where no phase of that
name is found."""
import collections
import statistics

from benchmark.readers import phase_idle


def note_medians(ctx, rows):
    by_name = collections.defaultdict(list)
    for name, s, e, *_ in rows:
        by_name[name].append(e - s)
    ctx.notes["phase_ms"] = {n: 1e3 * statistics.median(xs)
                             for n, xs in sorted(by_name.items())}


def read(ctx, params):
    rows = phase_idle.window_phases(ctx)
    if not rows:
        return None
    note_medians(ctx, rows)
    phase_idle.idle_table(ctx)    # a traced run: its notes ride along
    minus = set(params.get("minus", ()))
    taken_out = collections.Counter()
    for name, s, e, _, step, _ in rows:
        if name in minus:
            taken_out[step] += e - s
    own = [e - s - taken_out[step] for name, s, e, _, step, _ in rows
           if name == params["root"]]
    if not own:
        return None
    if params["reduce"] == "median":
        value = statistics.median(own)
    elif params["reduce"] == "share_of_window":
        value = sum(own) / ctx.counters["window_s"]
    else:
        raise ValueError(f"unknown reduce {params['reduce']!r}")
    return value * params.get("scale", 1.0)
