"""The device's time under the program's own names: from one device plane of
a capture to self time by (program, scope, direction). Pure functions over
lists of (name, start, end), in the style of reduce.py; the tests check them
on synthetic planes and on a cut of a recorded capture.

The program names its work (docs/OBSERVABILITY.md "Device scopes"): every
jitted program is a module `jit_<site>.<label>` on the plane's "XLA Modules"
line, and every operation carries the `jax.named_scope` path it was traced
under as its HLO instruction's `op_name`. On this chip and this jax (TPU v5
lite, jax 0.9.0, libtpu 0.0.34) the raw capture has NO "Framework Name
Scope" line (a viewer derives it) and an "XLA Ops" event carries no
statistic but its times: the paths are in the capture all the same, in the
`/host:metadata` plane, which holds every module's HLO ("Hlo Proto") under
the module's name as the "XLA Modules" line spells it. `load` reads them
there: a module's instructions by name, each with its path; an "XLA Ops"
event is named by its instruction, and the module is the one that runs
then; an instruction the compiler itself put in (a weight's prefetch) has
no path and takes that of what it feeds. `jax.profiler.ProfileData` does not show that plane's statistics, so
`module_paths` reads the file's few protobuf fields itself (PERF.md section
5 says what each line holds).
"""
import bisect
import collections
import re

from benchmark import reduce

#: the words a program may name its work by, parents before children
VOCABULARY = (
    "embed", "attn", "attn/proj", "attn/core", "kda", "kda/proj", "kda/conv",
    "kda/state", "mlp", "moe", "moe/router", "moe/experts", "moe/shared",
    "moe/combine", "cache", "cache/store", "cache/admit", "head", "pick",
    "loss", "optimizer", "grad_sync")
_WORDS = frozenset(VOCABULARY)
UNSCOPED = "unscoped"

_WRAPPED = re.compile(r"^(?:[\w.]+\()+|\)+$")
_MODULE = re.compile(r"^(?:jit_|pjit_)?(.*?)(?:\(\d+\))?$")


def program_name(module):
    """"jit_serving.step_greedy(1234)" -> "serving.step_greedy": an "XLA
    Modules" event's name without jax's prefix and the run's number."""
    return _MODULE.match(module).group(1)


def scope_of(path):
    """(scope, direction) of one operation's name-stack path, e.g.
    "jit(train.step)/transpose(jvp(GPTForCausalLM))/gpt/blocks/attn/core/
    dot_general" -> ("attn/core", "bwd").

    scope: the innermost VOCABULARY path the components spell, read left to
    right: a component continues the current scope where that is a word
    ("attn" then "core"), starts anew where it is a word by itself ("attn"
    then "cache" then "store": "cache/store"), and is skipped otherwise (a
    layer's registered name, a function jax jitted on the way, the
    primitive). UNSCOPED where no component is a word.
    direction: "bwd" under a `transpose(`, "fwd" under a `jvp(` alone,
    "none" elsewhere (a program that differentiates nothing; the
    optimizer)."""
    scope = None
    for part in path.split("/"):
        if part.startswith(("jit(", "pjit(")):
            continue        # a function's name, not a scope
        word = _WRAPPED.sub("", part)
        if scope is not None and scope + "/" + word in _WORDS:
            scope += "/" + word
        elif word in _WORDS:
            scope = word
    direction = ("bwd" if "transpose(" in path
                 else "fwd" if "jvp(" in path else "none")
    return scope or UNSCOPED, direction


def self_times(ops):
    """Self seconds of each of `ops`, (name, start, end) of ONE device's
    "XLA Ops" line, in their order: every instant goes to the operation
    that started last among those running then. A `while` holds its body's
    operations and a `conditional` its branch's, so a loop's body is
    counted once, under the operations that did the work; a copy that runs
    beside a kernel shares no instant with it twice. The values sum to the
    union of the intervals."""
    out = [0.0] * len(ops)
    running = []                    # indices, in the order they started
    at = 0.0

    def advance(until):
        nonlocal at
        while running:
            end = ops[running[-1]][2]
            if end > at:
                out[running[-1]] += min(end, until) - at
                at = min(end, until)
            if end > until:
                return
            running.pop()
        at = until

    for i in sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2])):
        advance(ops[i][1])
        running.append(i)
    advance(float("inf"))
    return out


def _clipped(ops, paths, window):
    if window is None:
        return ops, paths
    kept = [(i, max(s, window[0]), min(e, window[1]))
            for i, (_, s, e) in enumerate(ops)
            if min(e, window[1]) > max(s, window[0])]
    return ([(ops[i][0], s, e) for i, s, e in kept],
            [paths[i] for i, _, _ in kept])


def running_at(spans):
    """spans: (name, start, end) that do not overlap (one device's modules).
    Returns f(t) -> the name of the one that runs at t, or None."""
    spans = sorted(spans, key=lambda p: p[1])
    starts = [p[1] for p in spans]

    def at(t):
        i = bisect.bisect_right(starts, t) - 1
        return spans[i][0] if i >= 0 and t < spans[i][2] else None

    return at


def table(ops, paths, programs, window=None):
    """{(program, scope, direction): self seconds} of one device.

    ops: (name, start, end) of its "XLA Ops" line; paths: each operation's
    name-stack path ("" where the profile has none), in the same order;
    programs: (name, start, end) of its "XLA Modules" line, names as
    `program_name` gives them. window: count only what lies inside it.
    An operation under no vocabulary scope is `unscoped`; `unscoped_kinds`
    lists those. An operation that straddles two programs goes to the one
    that runs at its midpoint. The values sum to `reduce.busy(ops, window)`
    where operations of one device overlap only by nesting."""
    ops, paths = _clipped(ops, paths, window)
    total = collections.Counter()
    program_at = running_at(programs)
    known = {}
    for i, own in enumerate(self_times(ops)):
        if own <= 0:
            continue
        _, s, e = ops[i]
        if paths[i] not in known:
            known[paths[i]] = scope_of(paths[i])
        program = program_at((s + e) / 2) or "no_program"
        total[(program,) + known[paths[i]]] += own
    return dict(total)


def unscoped_kinds(ops, paths, window=None, n=12):
    """[[kind, self seconds]] of the operations `table` files under
    `unscoped`, by `reduce.kind`, largest first."""
    ops, paths = _clipped(ops, paths, window)
    total = collections.Counter()
    for i, own in enumerate(self_times(ops)):
        if own > 0 and scope_of(paths[i])[0] == UNSCOPED:
            total[reduce.kind(ops[i][0])] += own
    return [[k, v] for k, v in total.most_common(n)]


def select(tab, programs=None, scopes=None, direction=None):
    """Seconds of the table's rows whose program holds any of `programs`
    (substrings), whose scope is any of `scopes` or lies under one (path
    prefixes), and whose direction is `direction`; each filter optional."""
    out = 0.0
    for (program, scope, way), seconds in tab.items():
        if programs and not any(p in program for p in programs):
            continue
        if scopes and not any(scope == s or scope.startswith(s + "/")
                              for s in scopes):
            continue
        if direction and way != direction:
            continue
        out += seconds
    return out


# -- the capture's own record of what each operation is -------------------------

def _varint(buf, i):
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return value, i


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a varint,
    a memoryview for a length-delimited field; fixed-width fields are
    skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield number, value
        elif wire == 2:
            size, i = _varint(buf, i)
            yield number, buf[i:i + size]
            i += size
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")


def _first(buf, number, default=None):
    for n, v in _fields(buf):
        if n == number:
            return v
    return default


def _varints(buf):
    out, i = [], 0
    while i < len(buf):
        value, i = _varint(buf, i)
        out.append(value)
    return out


def _instruction_paths(hlo_proto):
    """{instruction name: op_name} of every computation of one HloProto
    (hlo_module 1 > computations 3 > instructions 2 > name 1, metadata 7 >
    op_name 2, id 35, operand_ids 36).

    An instruction the compiler put in has no op_name: the asynchronous
    copies that fetch a weight ahead of its use (`copy-start` / `-done`,
    `slice-start` / `-done`: 19 % of the GPT serving cell's busy time, 7 %
    of the training cell's), the call that joins their pieces. It takes
    the path of the first instruction that uses its result, through as
    many such as lie between: the wait for a layer's weights is that
    layer's time. One with no user (a root) stays without."""
    out = {}
    module = _first(hlo_proto, 1)
    if module is None:
        return out
    for n, comp in _fields(module):
        if n != 3:
            continue
        rows = []                               # [name, path, id, operands]
        for m, inst in _fields(comp):
            if m != 2:
                continue
            row = [None, "", None, []]
            for k, v in _fields(inst):
                if k == 1:
                    row[0] = bytes(v).decode()
                elif k == 7:
                    row[1] = bytes(_first(v, 2, b"")).decode()
                elif k == 35:
                    row[2] = v
                elif k == 36:
                    row[3] += [v] if isinstance(v, int) else _varints(v)
            if row[0] is not None:
                rows.append(row)
        first_user = {}
        for row in rows:                        # in the computation's order
            for operand in row[3]:
                first_user.setdefault(operand, row)
        for row in rows:
            at, hops = row, 0
            while not at[1] and at[2] in first_user and hops < 8:
                at, hops = first_user[at[2]], hops + 1
            out[row[0]] = at[1]
    return out


def module_paths(xplane_file):
    """{module name as the "XLA Modules" line spells it: {instruction name:
    name-stack path}} from the `/host:metadata` plane of an .xplane.pb
    (XSpace.planes 1 > name 2, event_metadata 4 > value 2 > name 2, stats 5
    > bytes_value 6). {} where the capture has no such plane."""
    with open(xplane_file, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for n, plane in _fields(space):
        if n != 1 or bytes(_first(plane, 2, b"")) != b"/host:metadata":
            continue
        for m, entry in _fields(plane):
            if m != 4:
                continue
            meta = _first(entry, 2)
            if meta is None:
                continue
            name, protos = None, []
            for k, v in _fields(meta):
                if k == 2:
                    name = bytes(v).decode()
                elif k == 5:
                    blob = _first(v, 6)
                    if blob is not None:
                        protos.append(blob)
            for blob in protos:
                out.setdefault(name, {}).update(_instruction_paths(blob))
    return out


def load(xplane_file, lines_of_planes, n_devices, op_name):
    """(programs, paths): for each of the first `n_devices` TPU planes, as
    `tracing.load` takes them, the "XLA Modules" line as (program name,
    start, end) in seconds, and the path of every "XLA Ops" event in the
    line's order ("" where the capture does not say).

    lines_of_planes: what `tracing.planes(xplane_file)` returns; op_name:
    `tracing.op_name`, an event's instruction name."""
    by_module = module_paths(xplane_file)
    dev = sorted((n, lines) for n, lines in lines_of_planes
                 if n.startswith("/device:") and "TPU" in n)[:n_devices]
    programs, paths = [], []
    for _, lines in dev:
        modules = [(name, s * 1e-9, (s + d) * 1e-9)
                   for ln, evs in lines if ln == "XLA Modules"
                   for name, s, d in evs]
        module_at = running_at(modules)
        programs.append([(program_name(n), s, e) for n, s, e in modules])
        paths.append([
            by_module.get(module_at((s + d / 2) * 1e-9), {}).get(
                op_name(name), "")
            for ln, evs in lines if ln == "XLA Ops" for name, s, d in evs])
    return programs, paths


# -- the host's side of the same capture ------------------------------------------

def host_phases(lines_of_planes):
    """The program's step phases as the capture holds them: (name, start,
    end) in seconds of every host event named `serve/...` or `train/...`
    (each `paddle_tpu.trace.phase` is a TraceAnnotation), on the clock of
    the device's operations, with no offset to map them through."""
    return sorted(
        ((name, s * 1e-9, (s + d) * 1e-9)
         for plane, lines in lines_of_planes if not plane.startswith("/device:")
         for _, evs in lines for name, s, d in evs
         if name.startswith(("serve/", "train/"))), key=lambda p: p[1])


def innermost_at(spans):
    """f(t) -> the name of the shortest of `spans` (name, start, end) that
    covers t, or None: what `reduce.attribute_gaps` asks for each gap,
    worked out once for every stretch between two boundaries."""
    bounds = sorted({t for _, s, e in spans for t in (s, e)})
    order = sorted(spans, key=lambda p: p[1])
    names, running, j = [], [], 0
    for lo, hi in zip(bounds, bounds[1:]):
        mid = (lo + hi) / 2
        while j < len(order) and order[j][1] <= mid:
            running.append(order[j])
            j += 1
        running = [p for p in running if p[2] >= mid]
        names.append(min((e - s, name) for name, s, e in running)[1]
                     if running else None)

    def at(t):
        i = bisect.bisect_right(bounds, t) - 1
        return names[i] if 0 <= i < len(names) else None

    return at


def idle_by_phase(devices, phases, window):
    """[[phase, idle seconds]] largest first: the idle gaps of `window`,
    cut at the phases' boundaries, each piece under the innermost phase
    that covers it ("no_span" where none does), averaged over the devices:
    what `readers/phase_idle.py` notes as `idle_by_phase`, from the
    capture's own annotations."""
    from benchmark.readers import phase_idle

    phases = reduce.clip_events(phases, window)
    name_at = innermost_at(phases)
    total = collections.Counter()
    for d in devices:
        for s, e in phase_idle.cut(reduce.gaps(d, window), phases):
            total[name_at((s + e) / 2) or "no_span"] += e - s
    n_dev = max(1, len(devices))
    return [[k, v / n_dev] for k, v in total.most_common()]
