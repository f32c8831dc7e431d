"""Median host time of one of the benchmark's own spans inside the window.

params: "span": its name; "scale": multiplies seconds (1000 -> ms)."""
import statistics


def read(ctx, params):
    xs = ctx.spans.durations(params["span"], since=ctx.counters["t_open"])
    if not xs:
        return None
    return statistics.median(xs) * params.get("scale", 1.0)
