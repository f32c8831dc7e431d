"""A kernel's share of its roofline: the least time the chip could take for
the work the traced steps needed (benchmark/counts.py over peaks.json), over
the summed device time of the kernel's events. %.

params: "kernels": substrings of the event names that are this kernel;
"least": the function of counts.py that gives (seconds, bound) a step.
Finds no such event -> returns nothing (never 0)."""
from benchmark import counts, reduce


def read(ctx, params):
    tr = ctx.trace
    if tr is None or not tr.devices:
        return None
    per_device = [sum(e - s for _, s, e in
                      reduce.clip_events(reduce.matching(d, params["kernels"]),
                                         tr.window))
                  for d in tr.devices]
    kernel_s = sum(per_device) / len(per_device)
    if kernel_s <= 0:
        return None
    c = ctx.counters
    least_s, bound = getattr(counts, params["least"])(
        ctx.cfg, c["batch"], c["seq_len"], ctx.peak)
    ctx.notes[params.get("note", "roofline_bound")] = bound
    # the work of all steps, shared by the chips of the cell
    return 100.0 * least_s * c["steps"] / ctx.chips / kernel_s
