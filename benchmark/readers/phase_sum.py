"""Wall time inside several of the program's step phases, summed, over the
measured window (`paddle_tpu.trace.phases()`; phases that lie whole inside
the window, as `program_phase` takes them).

params: "roots": the names of the phases whose durations are summed (they
must not enclose one another); "scale": a factor (100 -> %).

Returns nothing — never 0 — where the program has no such timeline, where
its ring lost part of the window, or where no phase of the FIRST name is
found (a program or a cell that never takes that path)."""
from benchmark.readers import phase_idle


def read(ctx, params):
    rows = phase_idle.window_phases(ctx)
    if not rows:
        return None
    roots = list(params["roots"])
    if not any(name == roots[0] for name, *_ in rows):
        return None
    total = sum(e - s for name, s, e, *_ in rows if name in roots)
    return total / ctx.counters["window_s"] * params.get("scale", 1.0)
