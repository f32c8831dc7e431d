"""Device time under the program's own names: self time of the traced
window's operations by (program, scope, direction), as `benchmark/scopes.py`
reduces a device plane, summed over the rows the params select and averaged
over the chips used.

params, all optional: "programs": substrings of program names
("serving.prefill" also takes `serving.prefill_chunk`); "scopes": scope
paths, each with everything under it ("attn/core", "moe"); "direction":
"fwd", "bwd" or "none"; "per": a key of the runner's counters that holds a
number (`decode_steps`, `steps`): the result is ms for each of them. Without
"per" it is % of the traced window.

Reads `ctx.trace.programs` (a list a device of (name, start, end), the
plane's "XLA Modules" line, names as `scopes.program_name` gives them) and
`ctx.trace.scopes` (a list a device of each operation's name-stack path, in
the order of `ctx.trace.devices`). Returns nothing — never 0 — where the
Trace it is handed lacks either (every run of run.py until `tracing.load`
keeps them: PERF.md section 7), where the rows selected hold no time (a
cell whose programs have no such scope), or where the counter is 0."""
from benchmark import scopes


def tables(ctx):
    """One table a device, computed once a run; None where the trace does
    not hold the two lines."""
    if hasattr(ctx, "_scope_tables"):
        return ctx._scope_tables
    ctx._scope_tables = None
    tr = ctx.trace
    programs = getattr(tr, "programs", None)
    paths = getattr(tr, "scopes", None)
    if not tr or not tr.devices or not programs or not paths:
        return None
    ctx._scope_tables = [scopes.table(ops, p, prog, tr.window)
                         for ops, p, prog in zip(tr.devices, paths, programs)]
    return ctx._scope_tables


def read(ctx, params):
    tabs = tables(ctx)
    if not tabs:
        return None
    seconds = sum(scopes.select(t, params.get("programs"),
                                params.get("scopes"),
                                params.get("direction"))
                  for t in tabs) / len(tabs)
    if seconds <= 0:
        return None
    if "per" not in params:
        return 100.0 * seconds / ctx.trace.window_s
    count = ctx.counters.get(params["per"])
    if not isinstance(count, (int, float)) or count <= 0:
        return None
    return 1e3 * seconds / count
