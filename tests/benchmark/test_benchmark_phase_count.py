"""The reader of the counts the program notes on its step phases
(`benchmark/readers/phase_count.py`), on the CPU: on recorded phases (counts
present, counts absent, the ring lost part of the window), its metric
`kv_read_share.serve` against the manifest's other entries, and a traced
rehearsal of the serving cell printing it."""
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
METRIC = "kv_read_share.serve"


def _spec():
    with open(os.path.join(BENCH, "metrics", METRIC + ".json")) as f:
        return json.load(f)


def _ns(seconds):
    return int(round(seconds * 1e9))


def _step(no, start, counts):
    """One serve/step as the program records it, rows in closing order: a
    decode_dispatch of 2 ms carrying `counts`, then a wait of 8 ms."""
    return [("serve/decode_dispatch", _ns(start + 0.001), _ns(start + 0.003),
             "serve/step", no, counts),
            ("serve/decode_wait", _ns(start + 0.003), _ns(start + 0.011),
             "serve/step", no, None),
            ("serve/step", _ns(start), _ns(start + 0.012), None, no,
             {"active": 4})]


@pytest.fixture
def program(monkeypatch):
    """Stand-in for paddle_tpu.trace.phases(): hands the reader `rows`,
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


def _ctx(t_open, window_s):
    from benchmark import run, tracing

    return run.Ctx(trace=None, spans=tracing.Spans(), notes={}, chips=1,
                   counters={"t_open": t_open, "window_s": window_s})


@pytest.mark.parametrize("case, want", [
    ("counts_present", 100 * (10 + 12 + 20) / (32 + 32 + 24)),
    ("straddles_the_window", 100 * (12 + 20) / (32 + 24)),
    ("counts_absent", None),          # the parent: the phase, no counts
    ("other_counts_only", None),
    ("ring_lost_part_of_the_window", None),
    ("no_phase_in_the_window", None),
    ("nothing_held", None),           # never 0 over 0
], ids=lambda v: v if isinstance(v, str) else "")
def test_phase_count_on_recorded_phases(program, case, want):
    from benchmark.readers import phase_count

    params = _spec()["params"]
    counts = [{"kv_tiles_read": r, "kv_tiles_held": h}
              for r, h in ((10, 32), (12, 32), (20, 24))]
    t_open, starts = 100.0, (100.01, 100.03, 100.05)
    if case == "straddles_the_window":
        starts = (99.995, 100.03, 100.05)   # the first opens before it
    elif case == "counts_absent":
        counts = [None] * 3
    elif case == "other_counts_only":
        counts = [{"slots": 3}] * 3
    elif case == "nothing_held":
        counts = [{"kv_tiles_read": 0, "kv_tiles_held": 0}] * 3
    program["rows"] = [row for no, (s, c) in enumerate(zip(starts, counts))
                       for row in _step(no + 1, s, c)]
    if case == "ring_lost_part_of_the_window":
        program["lost_ns"] = _ns(100.02)
    if case == "no_phase_in_the_window":
        t_open = 200.0
    got = phase_count.read(_ctx(t_open, 1.0), params)
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want, rel=1e-9)
        # a lost phase from before the window opened is no loss
        program["lost_ns"] = _ns(99.0)
        assert phase_count.read(_ctx(t_open, 1.0), params) == \
            pytest.approx(want, rel=1e-9)


def test_a_plain_sum_and_a_program_without_the_timeline(program, monkeypatch):
    from paddle_tpu import trace

    from benchmark.readers import phase_count

    program["rows"] = _step(1, 100.01, {"kv_tiles_read": 7,
                                        "kv_tiles_held": 16})
    ctx = _ctx(100.0, 1.0)
    assert phase_count.read(ctx, {"root": "serve/decode_dispatch",
                                  "count": "kv_tiles_read"}) == 7
    assert phase_count.read(ctx, {"root": "serve/decode_dispatch",
                                  "count": "kv_tiles_held",
                                  "scale": 0.5}) == 8
    assert phase_count.read(ctx, {"root": "serve/step",
                                  "count": "kv_tiles_read"}) is None
    monkeypatch.delattr(trace, "phases")
    assert phase_count.read(ctx, _spec()["params"]) is None


ENTRY = {"name": METRIC, "unit": "%", "better": "lower",
         "source": "program_counter",
         "layer": "model step (models/gpt.py, nn/)",
         "moves": "serve_tokens_per_s",
         "workloads": ["gpt2-large.serve-backlog"]}


def _manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_metric_is_ready_for_the_manifest():
    """`BENCHMARK.json` does not list the metric yet (a test the benchmark
    already has pins the list's last four entries, PERF.md section 7).
    ENTRY is the entry its file is written for: held to the manifest by
    name, wherever a later PR puts it."""
    man = _manifest()
    listed = {m["name"]: m for m in man["per_layer"]}
    entry = listed.get(METRIC, ENTRY)
    assert entry == ENTRY
    spec = _spec()
    for key in ("layer", "unit", "better", "source", "moves"):
        assert spec[key] == entry[key], key
    assert "workloads" not in spec
    assert os.path.isfile(os.path.join(BENCH, "readers",
                                       spec["reader"] + ".py"))
    others = [m for name, m in listed.items() if name != METRIC]
    assert entry["layer"] in {m["layer"] for m in others}
    assert entry["moves"] in {m["name"] for m in man["end_to_end"]}
    assert set(entry["workloads"]) <= {w["name"] for w in man["workloads"]}


def test_a_traced_rehearsal_prints_the_metric(monkeypatch):
    from benchmark import run

    man = _manifest()
    if METRIC not in {m["name"] for m in man["per_layer"]}:
        man["per_layer"].append(ENTRY)
        monkeypatch.setattr(run, "manifest", lambda: man)
    rc, result = run.run_cell("rehearsal-serve-tiny", 2147483998, 2.0, True)
    assert rc == 0
    # off the chip the einsums run: every row is read whole
    assert result["metrics"][METRIC] == {"value": 100.0, "unit": "%"}
