"""A decode kernel's share of its roofline, for a kernel that reads the
live columns of the cache's `kv` state: the least time the chip could take
for the columns the traced window's decode steps had to read, over the
summed device time of the kernel's events. %.

The live columns come from what the engine counted: `state_bytes_moved["kv"]`
is, a step, (the rows' positions summed + 2 a row) x the bytes a column holds
over all layers as stored; a row at position p has to read p + 1 columns, so
one column a row a step is taken off again. Partial tiles and the stored
row's padding to whole lanes are the kernel's, not the mathematics': not
counted.

params: "kernels": substrings of the event names that are this kernel;
"counts": the module under benchmark/ and "least": its function
`(cfg, layer_columns, peak) -> (seconds, bound)`, `layer_columns` the live
columns summed over rows, steps and layers. Which cells it is read in is
the metric's `workloads` list's to say. Finds no such event, or a program
without the counts -> returns nothing (never 0)."""
import importlib

from benchmark import reduce


def read(ctx, params):
    tr, c = ctx.trace, ctx.counters
    if tr is None or not tr.devices or not c:
        return None
    per_device = [sum(e - s for _, s, e in
                      reduce.clip_events(reduce.matching(d, params["kernels"]),
                                         tr.window))
                  for d in tr.devices]
    kernel_s = sum(per_device) / len(per_device)
    moved = c.get("state_bytes_moved", {}).get("kv")
    held = c.get("state_bytes_held", {}).get("kv")
    if kernel_s <= 0 or not moved or not held:
        return None
    column = held / (c["max_batch"] * int(ctx.workload["max_seq_len"]))
    columns = moved / column - c["decode_steps"] * c["max_batch"]
    least = getattr(importlib.import_module("benchmark." + params["counts"]),
                    params["least"])
    least_s, bound = least(ctx.cfg, columns * ctx.cfg["num_hidden_layers"],
                           ctx.peak)
    ctx.notes[params.get("note", "roofline_bound")] = bound
    return 100.0 * least_s / ctx.chips / kernel_s
