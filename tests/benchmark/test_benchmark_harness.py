"""The benchmark's own code, on the CPU: the manifest and its files agree,
counts.py against hand-worked numbers, the trace reductions on synthetic
events and on a small recorded trace of cell 1, the traffic generator.
No topology is described and nothing heavy is imported at import time."""
import gzip
import importlib
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def manifest():
    return _json(ROOT, "BENCHMARK.json")


def _cfg(name):
    return _json(BENCH, "configs", name + ".json")


# -- the manifest and the files ---------------------------------------------
def test_manifest_keys_and_names(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= manifest["run_seconds"] <= 51
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in manifest[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names), names
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    assert "setup_s" in [m["name"] for m in manifest["end_to_end"]]
    for m in manifest["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")


def test_every_cell_has_its_files(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    for c in configs.values():
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert _json(ROOT, c["file"])["reduced"] == c["reduced"]
    for w in manifest["workloads"]:
        wl = _json(BENCH, "workloads", w["name"] + ".json")
        assert (wl["config"], wl["traffic"], wl["chips"]) == \
            (w["config"], w["traffic"], w["chips"])
        assert w["config"] in configs and len(w["why"]) <= 200
        assert os.path.isfile(os.path.join(BENCH, "traffic",
                                           w["traffic"] + ".json"))
        assert os.path.isfile(os.path.join(BENCH, "runners",
                                           wl["runner"] + ".py"))
        assert "rehearsal" not in wl
    used = {w["config"] for w in manifest["workloads"]}
    assert used == set(configs)


def test_every_metric_has_its_file_and_reader(manifest):
    cells = {w["name"] for w in manifest["workloads"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    for m in manifest["per_layer"]:
        spec = _json(BENCH, "metrics", m["name"] + ".json")
        for key in ("layer", "unit", "better", "source", "moves"):
            assert spec[key] == m[key], (m["name"], key)
        # which cells report it is the manifest's to say (a later PR adds
        # a cell there), never a file's that may not be edited
        assert "workloads" not in spec
        assert os.path.isfile(os.path.join(BENCH, "readers",
                                           spec["reader"] + ".py"))
        assert m["moves"] in e2e
        for cell in m["workloads"]:
            assert cell in cells
            moved = e2e[m["moves"]]
            assert "workloads" not in moved or cell in moved["workloads"]
    for cell in cells:     # setup_s, another end-to-end, one per-layer
        assert any(m["name"] != "setup_s" and cell in m.get("workloads", [cell])
                   for m in manifest["end_to_end"])
        assert any(cell in m["workloads"] for m in manifest["per_layer"])


def test_limits_are_set_for_every_number(manifest):
    for w in manifest["workloads"]:
        wl = _json(BENCH, "workloads", w["name"] + ".json")
        assert wl["limits"]["compiles_in_window"] == 0
        assert wl["limits"]["failed"] == 0
        assert all(v < 1.0 for v in wl["limits"].values()), \
            "a limit of 1.0 is the placeholder, not a measured limit"


# -- counts.py against hand-worked numbers ----------------------------------
@pytest.mark.parametrize("config, millions", [("gpt2-medium", 354.8),
                                              ("gpt2-large", 774.0)])
def test_parameter_counts(config, millions):
    from benchmark import counts

    assert round(counts.n_params(_cfg(config)) / 1e6, 1) == millions


def test_train_flops_per_token_medium():
    from benchmark import counts

    cfg = _cfg("gpt2-medium")
    # 6 x (24 x 12 x 1024^2 + 50257 x 1024) + 6 x 1024 x 1024 x 24
    by_hand = 6 * (24 * 12 * 1024 ** 2 + 50257 * 1024) + 6 * 1024 * 1024 * 24
    assert counts.train_flops_per_token(cfg, 1024) == by_hand
    assert round(by_hand / 1e9, 2) == 2.27


def test_cache_and_flash_counts():
    from benchmark import counts

    assert counts.kv_bytes_per_token(_cfg("gpt2-large")) == 184320
    # one causal 1024 x 64 head: 7 products of 2 x 1024 x 1024 x 64, halved
    assert counts.flash_train_flops(1024, 64) == 7 * 1024 * 1024 * 64
    assert counts.flash_fwd_flops(1024, 64) == 2 * 1024 * 1024 * 64
    assert counts.flash_train_bytes(1024, 64) == 12 * 1024 * 64 * 2


@pytest.mark.parametrize("config", ["gpt2-medium", "gpt2-large"])
def test_no_share_of_a_peak_can_pass_100(config):
    """step_mfu and step_hbm_share by hand, at rates no chip could beat:
    the count x rate / peak is exactly 100 at the least time."""
    from benchmark import counts

    cfg, peak = _cfg(config), counts.peaks("TPU v5 lite")
    assert peak["flops_per_s"] == 197e12 and peak["hbm_bytes_per_s"] == 819e9
    per_token = counts.train_flops_per_token(cfg, 1024)
    assert per_token * (peak["flops_per_s"] / per_token) \
        == pytest.approx(peak["flops_per_s"])
    # serving: 32 rows at context 500 for one step
    flops = counts.serve_flops(cfg, 0, 0, 32, 32 * 500)
    h, L = cfg["n_embd"], cfg["n_layer"]
    assert flops == 2 * counts.matmul_params(cfg) * 32 + 4 * h * L * 16000
    # a prompt's tokens pass the blocks, and not the head
    assert counts.serve_flops(cfg, 100, 100 * 100, 0, 0) == \
        2 * L * 12 * h * h * 100 + 4 * h * L * 5000
    nbytes = counts.serve_decode_bytes(cfg, 1, 32 * 500)
    assert nbytes == counts.decode_weight_bytes(cfg) \
        + 16000 * counts.kv_bytes_per_token(cfg)
    # a decode step cannot beat its bytes: at peak bandwidth the share of
    # FLOPs it reaches is far under 100 (decode is bound by bytes)
    least_s = nbytes / peak["hbm_bytes_per_s"]
    assert 100 * flops / least_s / peak["flops_per_s"] < 25
    with pytest.raises(KeyError):
        counts.peaks("cpu")


# -- the reductions ------------------------------------------------------------
EVENTS = [("a", 0.0, 1.0), ("b", 0.5, 2.0), ("a", 3.0, 4.0), ("c", 6.0, 7.0)]


def test_busy_is_a_union_not_a_sum():
    from benchmark import reduce

    assert reduce.busy(EVENTS, (0.0, 10.0)) == pytest.approx(4.0)
    assert reduce.busy(EVENTS, (0.75, 3.5)) == pytest.approx(1.75)
    assert reduce.top_by_name(EVENTS, 2) == [["a", 2.0], ["b", 1.5]]


def test_gaps_and_who_held_the_host():
    from benchmark import reduce

    g = reduce.gaps(EVENTS, (0.0, 10.0))
    assert g == [(7.0, 10.0), (4.0, 6.0), (2.0, 3.0)]
    spans = [("eng.step", 1.9, 3.1), ("bookkeeping", 4.0, 9.0),
             ("submit", 4.5, 5.5)]
    assert reduce.attribute_gaps(g, spans) == [
        ["bookkeeping", 3.0], ["submit", 2.0], ["eng.step", 1.0]]
    assert reduce.attribute_gaps(g, spans[:1]) == [
        ["no_span", 5.0], ["eng.step", 1.0]]
    assert reduce.top_by_kind([("fusion.12", 0, 1), ("fusion.7", 1, 3),
                               ("copy", 3, 4)]) == [["fusion", 3], ["copy", 1]]
    assert reduce.percentile([1, 2, 3, 4, 5], 50) == 3
    assert reduce.percentile([], 95) is None


def _ctx(devices, host, window, **counters):
    from benchmark import counts, run, tracing

    return run.Ctx(trace=tracing.Trace(devices, host, window),
                   counters=counters, cfg=_cfg("gpt2-medium"), chips=1,
                   peak=counts.peaks("TPU v5 lite"), notes={})


def test_idle_and_roofline_readers_on_synthetic_events():
    from benchmark import counts
    from benchmark.readers import idle, kernel_roofline

    cfg = _cfg("gpt2-medium")
    least, bound = counts.flash_train_least_seconds(
        cfg, 4, 1024, counts.peaks("TPU v5 lite"))
    assert bound == "compute"
    assert least == pytest.approx(4 * 16 * 24 * 7 * 1024 * 1024 * 64 / 197e12)
    ops = [("fusion.1", 0.0, 0.3),
           ("jvp_jit__flash_attention_jit__.24", 0.3, 0.3 + 2 * least),
           ("transpose_jvp_jit__flash_attention_jit___.3", 0.5,
            0.5 + 2 * least),
           ("transpose_jvp_jit__flash_attention_jit___.4", 0.7,
            0.7 + 4 * least)]
    ctx = _ctx([ops], [], (0.0, 1.0), steps=2, batch=4, seq_len=1024)
    assert idle.read(ctx, {}) == pytest.approx(
        100 * (1 - (0.3 + 8 * least)))
    params = _json(BENCH, "metrics", "flash_attention_roofline.json")["params"]
    # two steps' least time over 8 x least of kernel time: 25 %
    assert kernel_roofline.read(ctx, params) == pytest.approx(25.0)
    assert ctx.notes["flash_attention_roofline_bound"] == "compute"
    # nothing to read -> nothing, never 0
    empty = _ctx([[("fusion", 0.0, 0.5)]], [], (0.0, 1.0), steps=2, batch=4,
                 seq_len=1024)
    assert kernel_roofline.read(empty, params) is None
    assert idle.read(_ctx([], [], (0.0, 0.0)), {}) is None


def test_recorded_trace_of_cell_1():
    """A third of a second of a real traced run of gpt2-medium.train-1k on
    the v5e (fixture): the reductions read it as they read a live trace."""
    from benchmark import reduce
    from benchmark.readers import idle

    path = os.path.join(os.path.dirname(__file__), "cell1_trace.json.gz")
    with gzip.open(path, "rt") as f:
        rec = json.load(f)
    devices = [[tuple(ev) for ev in d] for d in rec["devices"]]
    window = tuple(rec["window"])
    assert len(devices) == 1 and len(devices[0]) > 1000
    ctx = _ctx(devices, [tuple(h) for h in rec["host"]], window)
    assert 0.0 <= idle.read(ctx, {}) < 20.0
    params = _json(BENCH, "metrics", "flash_attention_roofline.json")["params"]
    flash = reduce.matching(devices[0], params["kernels"])
    share = sum(e - s for _, s, e in flash) / reduce.busy(devices[0], window)
    assert len(flash) >= 72 and 0.05 < share < 0.6
    assert {h[0] for h in rec["host"]} >= {"train_step", "data_batch"}


# -- traffic --------------------------------------------------------------------
def test_same_seed_same_requests_and_every_seed_the_same_sizes():
    import numpy as np

    from benchmark import traffic

    mix = traffic.load("serve-backlog")
    big = 2 ** 31 + 12345

    pool = mix["pool"]
    assert pool == mix["clients"]

    def take(seed, n=pool):
        it = traffic.requests(mix, 50257, seed)
        return [next(it) for _ in range(n)]

    a, b, c = take(big), take(big), take(7)
    assert all(np.array_equal(x[0], y[0]) and x[1] == y[1]
               for x, y in zip(a, b))
    assert sorted((len(p), n) for p, n in a) == \
        sorted((len(p), n) for p, n in c) == sorted(traffic.request_sizes(mix))
    # the next round is the same pool again, in a fresh order
    again = take(big, 2 * pool)[pool:]
    assert sorted((len(p), n) for p, n in again) == \
        sorted((len(p), n) for p, n in a)
    assert [len(p) for p, _ in again] != [len(p) for p, _ in a]
    assert [len(p) for p, _ in a] != [len(p) for p, _ in c]
    lens = [len(p) for p, _ in a]
    assert min(lens) >= 33 and max(lens) <= 512
    assert all(32 <= n <= 256 for _, n in a)
    assert max(int(p.max()) for p, _ in a) < 50257
    t1 = traffic.token_batches(traffic.load("train-1k"), 50257, big)
    t2 = traffic.token_batches(traffic.load("train-1k"), 50257, big)
    x, y = next(t1), next(t2)
    assert x[0].shape == (4, 1024) and np.array_equal(x[0], y[0]) \
        and np.array_equal(x[1], y[1]) and not np.array_equal(x[0], x[1])


def test_readers_and_runners_import():
    for kind in ("readers", "runners"):
        for f in os.listdir(os.path.join(BENCH, kind)):
            if f.endswith(".py") and f != "__init__.py":
                importlib.import_module(f"benchmark.{kind}.{f[:-3]}")
