"""The two readers of the program's step-phase timeline, on the CPU: on
synthetic phases and a synthetic Trace (median, share, window clipping,
what is taken out, eviction and skew give nothing, the idle table sums to
the idle total), and a traced rehearsal of each cell printing the four
metrics they feed."""
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
NEW = {"engine_host_ms.serve": "serve", "admit_share.serve": "serve",
       "idle_in_engine.serve": "serve", "trainer_host_ms.train": "train"}


def _params(metric):
    with open(os.path.join(BENCH, "metrics", metric + ".json")) as f:
        return json.load(f)["params"]


def _ns(seconds):
    return int(round(seconds * 1e9))


def _step(no, start, wait=0.070, admit=0.001, prefill_wait=0.0, own=0.004):
    """One serve/step as the program records it (rows in closing order):
    `own` seconds of host work — `admit` of it in serve/admit, the rest
    split around the decode wait — plus its two kinds of wait."""
    t, rows = start + admit, []
    if prefill_wait:
        rows.append(("serve/prefill_wait", _ns(t), _ns(t + prefill_wait),
                     "serve/prefill", no, None))
        rows.append(("serve/prefill", _ns(t - admit / 2),
                     _ns(t + prefill_wait), "serve/admit", no,
                     {"slot": 0, "tokens": 9, "bucket": 64}))
        t += prefill_wait
    rows.append(("serve/admit", _ns(start), _ns(t), "serve/step", no,
                 {"admitted": int(bool(prefill_wait))}))
    rest = own - admit
    rows.append(("serve/decode_wait", _ns(t + rest / 2),
                 _ns(t + rest / 2 + wait), "serve/step", no, None))
    end = t + rest + wait
    rows.append(("serve/step", _ns(start), _ns(end), None, no,
                 {"active": 4}))
    return rows, end


@pytest.fixture
def program(monkeypatch):
    """Stand-in for paddle_tpu.trace.phases(): hands the readers `rows`,
    honouring since_ns as the program does."""
    from paddle_tpu import trace

    state = {"rows": [], "lost_ns": None}

    def phases(since_ns=None):
        lost = state["lost_ns"] is not None and (
            since_ns is None or state["lost_ns"] >= since_ns)
        return [r for r in state["rows"]
                if since_ns is None or r[2] >= since_ns], lost

    monkeypatch.setattr(trace, "phases", phases)
    return state


def _ctx(t_open, window_s, trace=None, spans=()):
    from benchmark import run, tracing

    sp = tracing.Spans()
    sp.items = list(spans)
    return run.Ctx(trace=trace, spans=sp, notes={}, chips=1,
                   counters={"t_open": t_open, "window_s": window_s})


def test_median_share_and_window_clipping(program):
    from benchmark.readers import program_phase

    t_open, rows, t = 100.0, [], 99.95
    ends = []
    for no in range(1, 9):     # the first starts before the window opens
        got, t = _step(no, t, prefill_wait=0.010 if no in (3, 6) else 0.0,
                       own=0.004 + 0.001 * (no % 2))
        rows += got
        ends.append(t)
        t += 0.0005
    program["rows"] = rows
    window_s = ends[6] - t_open + 0.001     # the eighth ends after it
    ctx = _ctx(t_open, window_s)
    host = program_phase.read(ctx, _params("engine_host_ms.serve"))
    # steps 2..7 lie whole inside: own time 4, 5, 4, 5, 4, 5 ms
    assert host == pytest.approx(4.5, abs=1e-6)
    whole = program_phase.read(ctx, {"root": "serve/step",
                                     "reduce": "median", "scale": 1000})
    assert whole == pytest.approx(75.0, abs=1e-6)     # waits left in
    share = program_phase.read(ctx, _params("admit_share.serve"))
    assert share == pytest.approx(
        100 * (6 * 0.001 + 2 * 0.010) / window_s, rel=1e-6)  # waits count
    assert set(ctx.notes["phase_ms"]) == {
        "serve/step", "serve/admit", "serve/decode_wait", "serve/prefill",
        "serve/prefill_wait"}
    assert ctx.notes["phase_ms"]["serve/decode_wait"] == \
        pytest.approx(70.0)
    assert "idle_by_phase" not in ctx.notes          # not a traced run
    with pytest.raises(ValueError, match="unknown reduce"):
        program_phase.read(ctx, {"root": "serve/step", "reduce": "mean"})


def test_nothing_to_read_gives_nothing_never_zero(program, monkeypatch):
    from paddle_tpu import trace

    from benchmark.readers import phase_idle, program_phase

    rows, _ = _step(1, 100.01)
    program["rows"] = rows
    ctx = _ctx(100.0, 1.0)
    p = _params("engine_host_ms.serve")
    assert program_phase.read(ctx, p) == pytest.approx(4.0, abs=1e-6)
    # no phase of that name
    assert program_phase.read(ctx, _params("trainer_host_ms.train")) is None
    # the ring lost a phase that ended inside the window
    program["lost_ns"] = _ns(100.005)
    assert program_phase.read(ctx, p) is None
    # ... but one that ended before the window opened is no loss
    program["lost_ns"] = _ns(99.0)
    assert program_phase.read(ctx, p) == pytest.approx(4.0, abs=1e-6)
    # no phases at all in the window
    assert program_phase.read(_ctx(200.0, 1.0), p) is None
    # a program from before the timeline (the parent commit)
    monkeypatch.delattr(trace, "phases")
    assert program_phase.read(ctx, p) is None
    assert phase_idle.read(_ctx(100.0, 1.0, trace=_trace([], [], (0, 1))),
                           _params("idle_in_engine.serve")) is None


def _trace(devices, host, window):
    from benchmark import tracing

    return tracing.Trace(devices, host, window)


def _traced(program, skew=0.0):
    """Two steps on the host clock (from 100 s) and on the trace's (from
    5 s): the device runs while the host waits, and idles while the host
    works. `skew` shifts the program's phases against the annotations."""
    offset = 5.0 - 100.0
    rows, t, host, ops = [], 100.002, [], []
    for no in (1, 2):
        start = t
        got, t = _step(no, start, wait=0.070, own=0.004)
        rows += [(n, s + _ns(skew), e + _ns(skew), *rest)
                 for n, s, e, *rest in got]
        host.append(("eng.step", start + offset - 2e-6, t + offset + 2e-6))
        wait = [r for r in got if r[0] == "serve/decode_wait"][0]
        ops.append(("fusion.1", wait[1] * 1e-9 + offset,
                    wait[2] * 1e-9 + offset - 0.001))
        host.append(("bookkeeping", t + offset + 3e-6, t + offset + 0.001))
        t += 0.001
    program["rows"] = rows
    window = (5.0, t + offset)
    host.append(("window", *window))
    ctx = _ctx(100.0005, t - 100.0005, trace=_trace([ops], host, window),
               spans=[("window", 100.0, t)])
    return ctx, ops, window


def test_idle_by_phase_sums_to_the_idle_total(program):
    from benchmark import reduce
    from benchmark.readers import phase_idle, program_phase

    ctx, ops, window = _traced(program)
    value = phase_idle.read(ctx, _params("idle_in_engine.serve"))
    idle_s = (window[1] - window[0]) - reduce.busy(ops, window)
    table = dict(ctx.notes["idle_by_phase"])
    assert sum(table.values()) == pytest.approx(idle_s, rel=1e-9)
    # idle outside the engine: before the first step, and the bookkeeping
    outside = table.pop("no_span")
    assert outside == pytest.approx(0.002 + 2 * 0.001, abs=1e-5)
    assert set(table) == {"serve/step", "serve/admit", "serve/decode_wait"}
    # 1 ms of each wait is idle (the device finished early), each
    # admit's 1 ms, and the 3 ms a step works outside both
    assert table["serve/decode_wait"] == pytest.approx(0.002, abs=1e-6)
    assert table["serve/admit"] == pytest.approx(0.002, abs=1e-6)
    assert table["serve/step"] == pytest.approx(0.006, abs=1e-6)
    assert value == pytest.approx(
        100 * (idle_s - outside) / (window[1] - window[0]), rel=1e-9)
    assert value < 100 * idle_s / (window[1] - window[0])
    assert 0.0 <= ctx.notes["phase_clock_skew_us"] < 5.0
    # a traced run's program_phase notes the same table
    again, _, _ = _traced(program)
    program_phase.read(again, _params("engine_host_ms.serve"))
    assert again.notes["idle_by_phase"] == ctx.notes["idle_by_phase"]


def test_a_skewed_clock_gives_nothing(program):
    from benchmark.readers import phase_idle

    ctx, _, _ = _traced(program, skew=150e-6)
    assert phase_idle.read(ctx, _params("idle_in_engine.serve")) is None
    assert ctx.notes["phase_clock_skew_us"] == pytest.approx(148.0, abs=1.0)
    assert "idle_by_phase" not in ctx.notes
    # inside the allowance it still reads
    ctx, _, _ = _traced(program, skew=50e-6)
    assert phase_idle.read(ctx, _params("idle_in_engine.serve")) > 0
    assert ctx.notes["phase_clock_skew_us"] == pytest.approx(48.0, abs=1.0)
    # no annotation encloses a root: the offset cannot be checked
    ctx, _, _ = _traced(program)
    ctx.trace.host = [h for h in ctx.trace.host if h[0] == "window"]
    assert phase_idle.read(ctx, _params("idle_in_engine.serve")) is None
    assert ctx.notes["phase_clock_skew_us"] is None


def test_the_new_metrics_follow_the_manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        man = json.load(f)
    entries = {m["name"]: m for m in man["per_layer"]}
    assert list(entries)[-4:] == list(NEW)       # appended, in this order
    layers = {m["layer"] for m in man["per_layer"][:-4]}
    for name, kind in NEW.items():
        m = entries[name]
        assert m["source"] == "program_span" and m["better"] == "lower"
        assert m["layer"] in layers              # a layer PERF.md has
        assert [w.rsplit(".", 1)[1].split("-")[0] for w in m["workloads"]] \
            == [kind]


@pytest.mark.parametrize("workload, names", [
    ("rehearsal-serve-tiny", ["engine_host_ms.serve", "admit_share.serve",
                              "idle_in_engine.serve"]),
    ("rehearsal-train-tiny", ["trainer_host_ms.train"])])
def test_a_traced_rehearsal_prints_the_new_metrics(workload, names):
    from benchmark import run

    rc, result = run.run_cell(workload, 2147483999, 2.0, True)
    assert rc == 0
    got = {k: v["value"] for k, v in result["metrics"].items()}
    for name in names:
        assert got.get(name) is not None and got[name] > 0, (name, got)
    notes = result["run"]["notes"]
    assert notes["phase_clock_skew_us"] < 100
    idle_s = result["device"]["window_s"] - result["device"]["busy_s"]
    assert sum(v for _, v in notes["idle_by_phase"]) == \
        pytest.approx(idle_s, rel=0.02)
    root = "serve/step" if "serve" in workload else "train/step"
    assert notes["phase_ms"][root] > 0
    if "serve" in workload:
        assert got["idle_in_engine.serve"] <= got["device_idle.serve"] + 1e-9
        assert got["engine_host_ms.serve"] <= notes["phase_ms"][root]
    else:
        assert got["trainer_host_ms.train"] <= got["dispatch_ms.train"]
