"""Device idle share of the traced window: 1 - (union of the device's
operation intervals) / window, averaged over the chips used. %."""


def read(ctx, params):
    tr = ctx.trace
    if tr is None or tr.window_s <= 0 or not tr.busy_s:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
