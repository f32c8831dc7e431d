"""A count the program notes on its own step phases
(`paddle_tpu.trace.phases()`: one `(name, start_ns, end_ns, parent_name,
step_no, counts)` a phase), summed over the phases of one name that lie
whole inside the measured window.

params: "root": the name of the phase; "count": the key in its counts that
is summed; "over": another key of the same phases whose sum divides it
(optional); "scale": a factor (100 -> %).

Returns nothing — never 0 and never 100 — where the program has no such
timeline or its ring lost part of the window (`phase_idle.window_phases`),
where no phase of that name carries the count (an older program: the phase
is there, the count is not), or where the divisor sums to nothing."""
from benchmark.readers import phase_idle


def read(ctx, params):
    rows = phase_idle.window_phases(ctx)
    if not rows:
        return None
    keys = [params["count"]] + ([params["over"]] if "over" in params else [])
    counted = [counts for name, *_, counts in rows
               if name == params["root"] and counts
               and all(k in counts for k in keys)]
    if not counted:
        return None
    value = sum(c[params["count"]] for c in counted)
    if "over" in params:
        total = sum(c[params["over"]] for c in counted)
        if not total:
            return None
        value = value / total
    return value * params.get("scale", 1.0)
