"""benchmark/scopes.py, its reader and its tool, on the CPU: synthetic planes
for every rule of the reduction, the metric files against the reader, a
hand-made capture file for the two protobuf walks, a cut of one real
capture of each kind of cell, and the tool on a traced rehearsal in a trace
directory of its own."""
import gzip
import json
import os

import pytest

from benchmark import reduce, scopes
from benchmark.readers import scope_time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
HERE = os.path.dirname(os.path.abspath(__file__))
NEW = {"prefill_device_share.serve": ("%", "serve_itl_p95_ms"),
       "attn_core_device_ms.serve": ("ms", "serve_tokens_per_s"),
       "experts_device_ms.serve": ("ms", "serve_tokens_per_s"),
       "optimizer_device_ms.train": ("ms", "train_tokens_per_s"),
       "backward_device_share.train": ("%", "train_tokens_per_s")}

STEP = "jit(serving.step_greedy)/"
TRAIN = "jit(train.step)/"


@pytest.mark.parametrize("path, want", [
    (STEP + "attn/core/dot_general", ("attn/core", "none")),
    (STEP + "attn/proj/jit(_where)/select_n", ("attn/proj", "none")),
    (STEP + "attn/cache/store/scatter", ("cache/store", "none")),
    (STEP + "moe/router/sort", ("moe/router", "none")),
    (STEP + "moe/experts/while/body/closed_call/dot_general",
     ("moe/experts", "none")),
    (STEP + "moe/reduce_sum", ("moe", "none")),
    (STEP + "jit(head)/add", ("unscoped", "none")),
    ("", ("unscoped", "none")),
    (TRAIN + "jvp(GPTForCausalLM)/gpt/blocks/attn/proj/qkv/dot_general",
     ("attn/proj", "fwd")),
    (TRAIN + "transpose(jvp(GPTForCausalLM))/gpt/blocks/attn/core/mul",
     ("attn/core", "bwd")),
    (TRAIN + "transpose(jvp(GPTForCausalLM))/gpt/blocks/mlp/fc1/dot_general",
     ("mlp", "bwd")),
    (TRAIN + "jvp(GPTPretrainLoss)/loss/reduce_max", ("loss", "fwd")),
    (TRAIN + "jvp(GPTForCausalLM)/gpt/blocks/ln1/sub", ("unscoped", "fwd")),
    (TRAIN + "optimizer/sqrt", ("optimizer", "none")),
    (TRAIN + "optimizer/jit(_where)/select_n", ("optimizer", "none")),
])
def test_an_operations_scope_and_direction(path, want):
    assert scopes.scope_of(path) == want


def test_program_names():
    assert scopes.program_name(
        "jit_serving.step_greedy(8218272662071183869)") \
        == "serving.step_greedy"
    assert scopes.program_name("jit_train.step") == "train.step"
    assert scopes.program_name("jit__threefry_fold_in(1)") \
        == "_threefry_fold_in"


def test_self_time_by_containment_and_a_loop_counted_once():
    # a while of 10 s holds two runs of its body's product (3 s each)
    ops = [("while.1", 0.0, 10.0), ("fusion.2", 1.0, 4.0),
           ("fusion.2", 5.0, 8.0), ("copy.3", 12.0, 13.0)]
    own = scopes.self_times(ops)
    assert own == [4.0, 3.0, 3.0, 1.0]
    assert sum(own) == pytest.approx(reduce.busy(ops, (0.0, 20.0)))
    paths = [STEP + "moe/experts/while", STEP + "moe/experts/while/body/dot",
             STEP + "moe/experts/while/body/dot", ""]
    tab = scopes.table(ops, paths, [("serving.step_greedy", 0.0, 14.0)])
    assert tab == {
        ("serving.step_greedy", "moe/experts", "none"): 10.0,
        ("serving.step_greedy", "unscoped", "none"): 1.0}
    assert scopes.unscoped_kinds(ops, paths) == [["copy", 1.0]]


def test_operations_that_run_beside_one_another_share_no_instant_twice():
    # a copy's wait runs beside two kernels and past them
    ops = [("slice-done.1", 0.0, 10.0), ("kernel.1", 2.0, 5.0),
           ("kernel.2", 4.0, 12.0), ("fusion.9", 20.0, 21.0)]
    own = scopes.self_times(ops)
    assert sum(own) == pytest.approx(reduce.busy(ops, (0.0, 30.0))) == 13.0
    assert own == [2.0, 2.0, 8.0, 1.0]


def test_forward_backward_and_two_programs_interleaved():
    fwd = TRAIN + "jvp(M)/gpt/blocks/attn/core/dot"
    bwd = TRAIN + "transpose(jvp(M))/gpt/blocks/attn/core/dot"
    opt = TRAIN + "optimizer/mul"
    ops, paths, programs = [], [], []
    for k in range(3):          # step k at 10 k, a seed program between
        t = 10.0 * k
        programs += [("train.step", t, t + 8.0),
                     ("_threefry_fold_in", t + 8.5, t + 9.0)]
        ops += [("fusion.1", t, t + 2.0), ("fusion.2", t + 2.0, t + 6.0),
                ("fusion.3", t + 6.0, t + 8.0), ("fusion.1", t + 8.5, t + 9)]
        paths += [fwd, bwd, opt, "jit(_threefry_fold_in)/shift"]
    tab = scopes.table(ops, paths, programs)
    assert tab == {("train.step", "attn/core", "fwd"): 6.0,
                   ("train.step", "attn/core", "bwd"): 12.0,
                   ("train.step", "optimizer", "none"): 6.0,
                   ("_threefry_fold_in", "unscoped", "none"): 1.5}
    assert sum(tab.values()) == pytest.approx(reduce.busy(ops, (0.0, 30.0)))
    assert scopes.select(tab, direction="bwd") == 12.0
    assert scopes.select(tab, programs=["train."], scopes=["attn"]) == 18.0
    assert scopes.select(tab, scopes=["optimizer"]) == 6.0
    assert scopes.select(tab, programs=["serving."]) == 0.0
    # a window cuts the operations it crosses
    tab = scopes.table(ops, paths, programs, window=(1.0, 7.0))
    assert tab == {("train.step", "attn/core", "fwd"): 1.0,
                   ("train.step", "attn/core", "bwd"): 4.0,
                   ("train.step", "optimizer", "none"): 1.0}


def test_an_operation_across_two_programs_goes_to_its_midpoints():
    ops = [("copy.1", 9.0, 12.0), ("copy.2", 9.5, 10.2)]
    programs = [("serving.admit", 0.0, 10.0),
                ("serving.step_greedy", 10.0, 20.0)]
    tab = scopes.table(ops, ["", ""], programs)
    assert tab == {("serving.step_greedy", "unscoped", "none"):
                   pytest.approx(2.3),
                   ("serving.admit", "unscoped", "none"): pytest.approx(0.7)}
    # and one that no program covers is kept, under no program
    assert scopes.table([("x", 30.0, 31.0)], [""], programs) \
        == {("no_program", "unscoped", "none"): 1.0}


# -- the reader ----------------------------------------------------------------

def _ctx(trace, **counters):
    from benchmark import run, tracing

    return run.Ctx(trace=trace, spans=tracing.Spans(), notes={}, chips=1,
                   counters=counters)


def _trace(with_lines=True):
    from benchmark import tracing

    step = ("serving.step_greedy", 0.0, 8.0)
    ops = [("fusion.1", 0.0, 2.0), ("while.2", 2.0, 6.0),
           ("fusion.3", 3.0, 5.0), ("fusion.4", 8.0, 9.0)]
    paths = [STEP + "attn/core/dot", STEP + "moe/experts/while",
             STEP + "moe/experts/while/body/dot",
             "jit(serving.prefill)/attn/core/dot"]
    tr = tracing.Trace([ops], [("window", 0.0, 10.0)], (0.0, 10.0))
    if with_lines:
        tr.programs = [[step, ("serving.prefill", 8.0, 9.0)]]
        tr.scopes = [paths]
    return tr


def test_the_reader_returns_nothing_without_the_two_lines():
    params = {"scopes": ["attn/core"]}
    assert scope_time.read(_ctx(_trace(False), decode_steps=2),
                           params) is None
    assert scope_time.read(_ctx(None), params) is None
    assert scope_time.read(_ctx(_trace(), decode_steps=2),
                           params) == pytest.approx(30.0)      # % of 10 s


def test_the_reader_by_program_scope_and_counter():
    ctx = _ctx(_trace(), decode_steps=2, steps={"decode": 2})
    read = scope_time.read
    assert read(ctx, {"scopes": ["moe/experts"], "programs": ["serving.step_"],
                      "per": "decode_steps"}) == pytest.approx(2000.0)
    assert read(ctx, {"scopes": ["attn/core"], "per": "decode_steps"}) \
        == pytest.approx(1500.0)
    assert read(ctx, {"programs": ["serving.prefill", "serving.admit"]}) \
        == pytest.approx(10.0)
    # nothing selected, a counter that is no number, a counter of 0
    assert read(ctx, {"scopes": ["optimizer"]}) is None
    assert read(ctx, {"direction": "bwd"}) is None
    assert read(ctx, {"scopes": ["attn/core"], "per": "steps"}) is None
    assert read(_ctx(_trace(), decode_steps=0),
                {"scopes": ["attn/core"], "per": "decode_steps"}) is None


@pytest.mark.parametrize("name", list(NEW))
def test_the_metric_files_name_what_the_reader_takes(name):
    with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
        spec = json.load(f)
    unit, moves = NEW[name]
    assert spec["reader"] == "scope_time"
    assert spec["source"] == "device_trace" and spec["better"] == "lower"
    assert (spec["unit"], spec["moves"]) == (unit, moves)
    params = {k: v for k, v in spec["params"].items()
              if not k.endswith("_why")}
    assert set(params) <= {"programs", "scopes", "direction", "per"}
    assert ("per" in params) == (unit == "ms")
    assert set(params.get("scopes", ())) <= set(scopes.VOCABULARY)
    assert params.get("direction", "bwd") in ("fwd", "bwd", "none")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        man = json.load(f)
    with open(os.path.join(ROOT, "PERF.md")) as f:
        assert spec["layer"] in f.read()        # a layer PERF.md has
    assert moves in {m["name"] for m in man["end_to_end"]}
    # files and no entry yet: PERF.md section 7 has the hand-over
    assert name not in {m["name"] for m in man["per_layer"]}


# -- the capture file ------------------------------------------------------------

def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, value):
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _instruction(name, path, ident=0, operands=()):
    packed = b"".join(_varint(o) for o in operands)
    return _field(2, _field(1, name) + _field(2, "fusion")
                  + (_field(7, _field(1, "dot") + _field(2, path))
                     if path else b"")
                  + _field(35, ident) + (_field(36, packed) if packed else b""))


def test_the_paths_are_read_from_the_captures_own_hlo(tmp_path):
    """An XSpace written by hand, field by field as the schema numbers
    them: a device plane to skip, fixed-width fields to step over, and the
    `/host:metadata` plane with two modules."""
    def module(name, instructions):
        hlo = _field(1, _field(1, name) + _field(3, _field(1, "main")
                     + b"".join(_instruction(*i) for i in instructions)))
        stat = _field(1, 1) + _field(6, hlo)
        return _field(4, _field(1, 7) + _field(2, _field(1, 7)
                      + _field(2, name) + _field(5, stat)))

    device = _field(2, "/device:TPU:0") + _field(3, _field(2, "XLA Ops")
                                                 + _field(4, _field(1, 3)))
    meta = _field(2, "/host:metadata") \
        + _varint(9 << 3 | 1) + b"\0" * 8 + _varint(9 << 3 | 5) + b"\0" * 4 \
        + module("jit_serving.step_greedy(11)",
                 # a weight fetched ahead of its use: the compiler's own
                 # copies, nameless, take the path of what they feed
                 [("copy-start.1", "", 1), ("copy-done.1", "", 2, (1,)),
                  ("fusion.1", STEP + "attn/core/dot", 3, (2, 9)),
                  ("fusion.5", STEP + "mlp/dot", 4, (2, 3)),
                  ("copy.2", "", 5, (4,))]) \
        + module("jit_train.step(12)", [("fusion.1", TRAIN + "optimizer/mul")])
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_field(1, device) + _field(1, meta) + _field(4, "host"))
    got = scopes.module_paths(str(path))
    assert got == {
        "jit_serving.step_greedy(11)": {
            "copy-start.1": STEP + "attn/core/dot",
            "copy-done.1": STEP + "attn/core/dot",
            "fusion.1": STEP + "attn/core/dot", "fusion.5": STEP + "mlp/dot",
            "copy.2": ""},
        "jit_train.step(12)": {"fusion.1": TRAIN + "optimizer/mul"}}
    # the same instruction name in two modules: the module that runs then
    lines = [("/device:TPU:0", [
        ("XLA Modules", [("jit_serving.step_greedy(11)", 0, 100),
                         ("jit_train.step(12)", 200, 100)]),
        ("XLA Ops", [("%fusion.1 = f32[] fusion()", 10, 50),
                     ("%copy.2 = f32[] copy()", 70, 10),
                     ("%fusion.1 = f32[] fusion()", 210, 50),
                     ("%fusion.7 = f32[] fusion()", 400, 5)])]),
        ("/host:CPU", [("python", [("serve/step", 0, 90),
                                   ("bench:window", 0, 500),
                                   ("train/step", 190, 120)])])]
    from benchmark import tracing

    programs, paths = scopes.load(str(path), lines, 1, tracing.op_name)
    assert programs == [[("serving.step_greedy", 0.0, pytest.approx(1e-7)),
                         ("train.step", pytest.approx(2e-7),
                          pytest.approx(3e-7))]]
    assert paths == [[STEP + "attn/core/dot", "", TRAIN + "optimizer/mul",
                      ""]]
    assert scopes.host_phases(lines) == [
        ("serve/step", 0.0, pytest.approx(9e-8)),
        ("train/step", pytest.approx(1.9e-7), pytest.approx(3.1e-7))]


def test_idle_by_phase_from_the_captures_annotations():
    ops = [("a", 0.0, 1.0), ("b", 2.0, 3.0), ("c", 6.0, 7.0)]
    phases = [("serve/step", 0.5, 5.0), ("serve/decode_wait", 1.5, 2.5),
              ("serve/step", 5.5, 8.0)]
    got = dict(scopes.idle_by_phase([ops], phases, (0.0, 8.0)))
    # 1-1.5 step, 1.5-2 wait, 3-5 step, 5-5.5 no span, 5.5-6 and 7-8 step
    assert got == {"serve/step": pytest.approx(4.0),
                   "serve/decode_wait": pytest.approx(0.5),
                   "no_span": pytest.approx(0.5)}
    assert sum(got.values()) == pytest.approx(
        8.0 - reduce.busy(ops, (0.0, 8.0)))


# -- the tool, on a capture of its own ----------------------------------------------

def test_the_tool_on_a_traced_rehearsal(tmp_path, capsys):
    """A traced serving rehearsal with the profile kept in a directory of
    its own. The CPU's capture has no device plane, so the table is empty
    and the reader returns nothing; the program's phases are in it, and the
    idle table made of them partitions the same idle time as the one
    `phase_idle` makes of the ring."""
    from benchmark import run
    from benchmark.tools import scope_table

    keep = str(tmp_path / "trace")
    rc, result = run.run_cell("rehearsal-serve-tiny", 1, 2, True,
                              keep_trace=keep)
    assert rc == 0
    line = tmp_path / "line.json"
    line.write_text(json.dumps(result))
    fixture = tmp_path / "cut.json.gz"
    assert scope_table.main([keep, "--workload", "rehearsal-serve-tiny",
                             "--line", str(line), "--fixture", str(fixture),
                             "--fixture-start", "0.2"]) == 0
    out = capsys.readouterr().out
    assert "table total 0.0000 s" in out and "serve/decode_wait" in out
    tr = scope_table.capture(keep)
    assert tr.programs == [] and tr.scopes == []
    names = {name for name, _, _ in tr.phases}
    assert {"serve/step", "serve/admit", "serve/decode_dispatch",
            "serve/decode_wait", "serve/emit"} <= names
    ring = dict(result["run"]["notes"]["idle_by_phase"])
    mine = dict(scopes.idle_by_phase(tr.devices, tr.phases, tr.window))
    assert sum(mine.values()) == pytest.approx(sum(ring.values()), rel=1e-6)
    for name, seconds in ring.items():
        if seconds > 0.01:
            assert mine[name] == pytest.approx(seconds, rel=0.1), name
    with gzip.open(fixture, "rt") as f:
        cut = json.load(f)
    assert cut["ops"] == [] and cut["phases"]
    ctx = _ctx(tr, decode_steps=5)
    assert scope_time.read(ctx, {"scopes": ["attn/core"]}) is None


# -- cuts of two real captures (TPU v5 lite; the tool's --fixture) --------------------

def _cut(name):
    with gzip.open(os.path.join(HERE, name), "rt") as f:
        cut = json.load(f)
    ops = [(n, s, e) for n, s, e, _ in cut["ops"]]
    paths = [p for *_, p in cut["ops"]]
    programs = [tuple(p) for p in cut["programs"]]
    return ops, paths, programs, tuple(cut["window"])


def test_a_cut_of_a_serving_capture():
    """250 ms of `a.x-k1.serve-backlog-8k`: three chunks and three decode
    steps, the naive attention's and the experts' loops, the decode
    kernel."""
    ops, paths, programs, window = _cut("scopes_cut_serve_latent.json.gz")
    tab = scopes.table(ops, paths, programs, window)
    busy = reduce.busy(ops, window)
    assert sum(tab.values()) == pytest.approx(busy, rel=1e-9)
    assert {p for p, _, _ in tab} >= {"serving.prefill_chunk",
                                      "serving.step_greedy"}
    assert {way for _, _, way in tab} == {"none"}
    # the chunk's attention first, as PERF.md section 5 has it
    assert max(tab, key=tab.get) == ("serving.prefill_chunk", "attn/core",
                                     "none")
    chunk = scopes.select(tab, programs=["serving.prefill_chunk"])
    core = scopes.select(tab, programs=["serving.prefill_chunk"],
                         scopes=["attn/core"])
    assert 0.4 < core / chunk < 0.55
    # the decode kernel lies under the step's attn/core
    kernel = [p for (n, _, _), p in zip(ops, paths)
              if "latent_decode_attention" in n]
    assert kernel and {scopes.scope_of(p)[0] for p in kernel} \
        == {"attn/core"}
    # a loop holds its body: counted once, by what ran inside it
    inside = reduce.clip_events(ops, window)
    loops = [i for i, (n, _, _) in enumerate(inside) if n.startswith("while")]
    own = scopes.self_times(inside)
    assert loops and all(own[i] < 0.2 * (inside[i][2] - inside[i][1])
                         for i in loops)
    assert sum(e - s for _, s, e in reduce.clip_events(ops, window)) \
        > 1.3 * busy
    left = scopes.select(tab, scopes=[scopes.UNSCOPED])
    assert left < 0.03 * busy
    assert {k for k, _ in scopes.unscoped_kinds(ops, paths, window)} \
        >= {"copy-done"}


def test_a_cut_of_a_training_capture():
    """90 ms of `gpt2-medium.train-1k`: one step and the start of the
    next."""
    ops, paths, programs, window = _cut("scopes_cut_train.json.gz")
    tab = scopes.table(ops, paths, programs, window)
    busy = reduce.busy(ops, window)
    assert sum(tab.values()) == pytest.approx(busy, rel=1e-9)
    fwd, bwd, rest = (scopes.select(tab, direction=d)
                      for d in ("fwd", "bwd", "none"))
    assert fwd + bwd + rest == pytest.approx(busy)
    assert 1.8 < bwd / fwd < 3.0
    for scope in ("attn/core", "attn/proj", "mlp", "head"):
        assert tab[("train.step", scope, "fwd")] > 0
        assert tab[("train.step", scope, "bwd")] > 0
    assert tab[("train.step", "optimizer", "none")] > 0
    # the flash kernels under attn/core, forward and backward
    flash = {(reduce.kind(n), scopes.scope_of(p))
             for (n, _, _), p in zip(ops, paths) if "flash_attention" in n}
    assert flash == {("flash_attention_fwd", ("attn/core", "fwd")),
                     ("flash_attention_dq", ("attn/core", "bwd")),
                     ("flash_attention_dkv", ("attn/core", "bwd"))}
    assert scopes.select(tab, scopes=[scopes.UNSCOPED]) < 0.03 * busy
