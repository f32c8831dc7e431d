"""A count the runner read from the program's own counters over the window.

params: "counter": its key in the runner's counters; "divide_by": another
key (optional); "scale": a factor (100 -> %)."""


def read(ctx, params):
    c = ctx.counters
    value = c.get(params["counter"])
    if value is None:
        return None
    if "divide_by" in params:
        if not c.get(params["divide_by"]):
            return None
        value = value / c[params["divide_by"]]
    return value * params.get("scale", 1.0)
