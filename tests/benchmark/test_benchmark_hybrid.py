"""The `solar-open2-250b` configuration's part of the benchmark, on the CPU:
its file against the published config, hybrid_weights.py and hybrid_counts.py
against hand-worked numbers, the shares of a peak that cannot pass 100, the
three per-layer metrics that wait for a manifest entry, and the cell's
control flow at a tiny size (a rehearsal workload)."""
import json
import os
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "solar-open2-250b.serve-backlog-2k"
REHEARSAL = "rehearsal-serve-hybrid-tiny"

#: the catalog row's `config` (https://huggingface.co/upstage/
#: Solar-Open2-250B/blob/main/config.json), every key
PUBLISHED = {
    "model_type": "solar_open2", "partial_rotary_factor": 1,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                           "num_heads": 64, "num_kv_heads": None},
    "hidden_size": 4096, "num_hidden_layers": 48, "num_attention_heads": 64,
    "head_dim": 128, "num_key_value_heads": 8, "vocab_size": 196608,
    "intermediate_size": 10240, "moe_intermediate_size": 1280,
    "rms_norm_eps": 1e-05, "rope_theta": 10000, "tie_word_embeddings": False,
    "max_position_embeddings": 1048576, "first_k_dense_replace": 0,
    "use_rope": False, "gqa_interval": 3,
    "gqa_layers": [0, 4, 8, 12, 16, 20, 24, 28, 32, 36, 40, 44],
    "use_gqa_gate": True, "kda_use_full_proj": False,
    "kda_allow_neg_eigval": True, "n_routed_experts": 320,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "num_experts_per_tok": 8}

#: the entries the three metric files are written for. BENCHMARK.json cannot
#: list them yet (tests/benchmark/test_benchmark_phases.py pins the list's
#: last four, and a PR may only append: PERF.md section 7)
LAYER = "model step (models/solar_open2.py, distributed/moe.py, ops/kda.py)"
ENTRIES = [
    {"name": "step_mfu.serve_hybrid", "unit": "%", "better": "higher",
     "source": "host_clock", "layer": LAYER, "moves": "serve_tokens_per_s",
     "workloads": [CELL]},
    {"name": "step_hbm_share.serve_hybrid", "unit": "%", "better": "higher",
     "source": "host_clock", "layer": LAYER, "moves": "serve_itl_p95_ms",
     "workloads": [CELL]},
    {"name": "moe_rows_padded.serve", "unit": "%", "better": "lower",
     "source": "program_counter", "layer": LAYER,
     "moves": "serve_tokens_per_s", "workloads": [CELL]}]


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    return _json(BENCH, "configs", "solar-open2-250b.json")


# -- the configuration's file ----------------------------------------------------
def test_the_file_holds_the_published_keys_but_for_the_three_reduced(cfg):
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    for key, value in PUBLISHED.items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    dep = cfg["deployment"]
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (4, 40, 24576)
    assert dep["chips_sharing_a_layer"] == 8
    assert dep["held_experts"] == [0, 40] and dep["layers_here"] == [0, 1, 2, 3]
    assert dep["vocab_rows_here"] == [0, 24576]
    assert (dep["num_hidden_layers_published"],
            dep["n_routed_experts_published"],
            dep["vocab_size_published"]) == (48, 320, 196608)
    # the guide's floors: a whole period, 8 experts, an eighth of the rows
    assert cfg["num_hidden_layers"] >= 4 and cfg["n_routed_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= dep["vocab_size_published"]
    assert cfg["state_dtype"] == "float32"
    for key in ("no_bias", "gqa_gate", "kda_parameterisation", "kda_low_rank",
                "kda_state", "hidden_act", "router", "init_std",
                "select_bias_std", "select_bias_balance", "decay_why"):
        assert key in cfg["assumed"], key
    man = _json(ROOT, "BENCHMARK.json")
    entry = {c["name"]: c for c in man["configs"]}["solar-open2-250b"]
    assert entry["source"] == cfg["source"] and entry["reduced"] == cfg["reduced"]
    cell = {w["name"]: w for w in man["workloads"]}[CELL]
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    reports = {m["name"] for group in ("end_to_end", "per_layer")
               for m in man[group] if CELL in m.get("workloads", [CELL])}
    assert reports == {"serve_tokens_per_s", "serve_itl_p95_ms", "setup_s",
                       "device_idle.serve", "engine_occupancy.serve"}


def test_the_traffic_is_the_issues(cfg):
    from benchmark import traffic

    mix = traffic.load("serve-backlog-2k")
    assert (mix["kind"], mix["clients"], mix["pool"]) == ("closed_loop", 256,
                                                          256)
    sizes = traffic.request_sizes(mix)
    prompts, news = np.array(sizes).T
    assert prompts.min() >= 64 and prompts.max() <= 2048
    assert news.min() >= 128 and news.max() <= 2048
    assert 330 <= np.median(prompts) <= 440 and 450 <= np.median(news) <= 580
    wl = _json(BENCH, "workloads", CELL + ".json")
    assert (prompts + news).max() < wl["max_seq_len"] == 4096
    assert wl["max_batch"] == 128 and wl["runner"] == "serve_hybrid"


# -- weights and counts against hand-worked numbers -------------------------------
def test_parameter_counts_by_hand(cfg):
    from benchmark import hybrid_counts as hc
    from benchmark import hybrid_weights as hw

    d = 4096
    # q, gate, o 4096 x 8192; k, v 4096 x 1024; shared expert 3 x 4096 x 1280;
    # router 4096 x 320
    gqa = 3 * d * 8192 + 2 * d * 1024 + 3 * d * 1280 + d * 320
    # q, k, v, o 4096 x 8192; decay and gate 4096 x 128 + 128 x 8192 each;
    # beta 4096 x 64
    kda = 4 * d * 8192 + 2 * (d * 128 + 128 * 8192) + d * 64 \
        + 3 * d * 1280 + d * 320
    assert hc.gqa_layer_params(cfg) == gqa == 126_091_264
    assert hc.kda_layer_params(cfg) == kda == 154_664_960
    assert hc.expert_params(cfg) == 3 * d * 1280 == 15_728_640
    assert hc.head_params(cfg) == d * 24576
    assert hc.n_params(cfg) == hw.n_params(cfg)
    assert round(hw.n_params(cfg) / 1e6) == 3308       # the issue's 3,308 M
    assert hc.decode_weight_bytes(cfg) == 2 * (gqa + 3 * kda + d * 24576)
    # the recurrence: 7 x 128 x 128 a head, 64 heads; 3 convolutions of 4
    # taps over 8,192 channels
    assert hc.kda_state_flops_per_token(cfg) == 7 * 64 * 128 * 128 \
        + 2 * 4 * 3 * 8192


def test_same_seed_same_weights_and_a_name_the_table_lacks_is_refused():
    from benchmark import hybrid_weights as hw

    tiny = _json(BENCH, "configs", "solar-open2-tiny-rehearsal.json")
    a = hw.flat(tiny, 2147485000, round_to="bfloat16")
    b = hw.flat(tiny, 2147485000, round_to="bfloat16")
    c = hw.flat(tiny, 2147485001, round_to="bfloat16")
    name = "layers.1.kda.q.weight"
    assert a[name].dtype == np.dtype("bfloat16") or str(a[name].dtype) == \
        "bfloat16"
    np.testing.assert_array_equal(np.asarray(a[name], np.float32),
                                  np.asarray(b[name], np.float32))
    assert (np.asarray(a[name], np.float32)
            != np.asarray(c[name], np.float32)).any()
    # decays that matter: a step's log-decay between -0.001 and -1.6
    A = np.exp(np.asarray(a["layers.1.kda.A_log"], np.float32))
    assert 0.99 <= A.min() and A.max() <= 16.1
    init = hw.initializer(tiny, 5)
    assert init(name, a[name].shape, "matrix", "float32").shape == \
        a[name].shape
    with pytest.raises(KeyError):
        init("layers.1.kda.bias", (4,), "zero", "float32")
    with pytest.raises(KeyError):
        init(name, (3, 3), "matrix", "float32")
    assert name not in init.missing() and "norm.weight" in init.missing()


def _loads(tiny, seed, ids):
    """Each layer's load by expert (1 = an even share) over `ids`, through
    the reference's layers with the weights `flat` hands out."""
    import jax
    import jax.numpy as jnp

    from benchmark import hybrid_weights as hw
    from benchmark.reference import solar_open2 as reference

    P = hw.flat(tiny, seed, round_to="bfloat16")
    D = reference.dims_of(tiny)
    x = P["embed.weight"][ids].astype(jnp.float32)
    out = []
    for l in range(D.layers):
        x, h, scores = hw._to_router(P, x, D, l)
        bias = P[f"layers.{l}.moe.router.bias"].astype(jnp.float32)
        _, chosen = jax.lax.top_k(scores + bias, D.top_k)
        out.append(np.bincount(np.asarray(chosen).ravel(),
                               minlength=D.experts)
                   * D.experts / chosen.size)
        x = hw._past_experts(P, x, h, D, l)
    return np.array(out), P


@pytest.mark.parametrize("seed", [3, 2147485000])
def test_a_balanced_bias_evens_the_load_on_ids_it_never_saw(seed):
    """The selection bias as drawn leaves a random router to favour some
    experts by the seed; balanced on 8 x 64 ids from the seed it gives
    every expert about its share of OTHER ids, in every layer, and the
    program's constructor and the reference are handed the same bias."""
    import copy

    import jax

    from benchmark import hybrid_weights as hw

    tiny = _json(BENCH, "configs", "solar-open2-tiny-rehearsal.json")
    tiny["assumed"]["select_bias_balance"] = {"sequences": 8, "length": 64,
                                              "rounds": 200}
    plain = copy.deepcopy(tiny)
    del plain["assumed"]["select_bias_balance"]
    ids = jax.random.randint(jax.random.PRNGKey(99), (8, 64), 0,
                             tiny["vocab_size"])
    before, drawn = _loads(plain, seed, ids)
    after, P = _loads(tiny, seed, ids)
    assert before.std(axis=1).min() > 0.3, before.std(axis=1)
    # 512 ids give an expert 128 assignments: 0.09 of noise in the count
    assert after.std(axis=1).max() < 0.25, after.std(axis=1)
    assert (after.std(axis=1) < 0.5 * before.std(axis=1)).all()
    assert after.min() > 0.4 and after.max() < 1.7
    name = "layers.2.moe.router.bias"
    assert str(P[name].dtype) == "bfloat16"
    assert (np.asarray(P[name], np.float32)
            != np.asarray(drawn[name], np.float32)).any()
    # nothing else moves, and the constructor is handed the same numbers
    np.testing.assert_array_equal(
        np.asarray(P["layers.2.moe.router.weight"], np.float32),
        np.asarray(drawn["layers.2.moe.router.weight"], np.float32))
    init = hw.initializer(tiny, seed, round_to="bfloat16")
    np.testing.assert_array_equal(
        np.asarray(init(name, P[name].shape, "zero", "float32")),
        np.asarray(P[name], np.float32))


def test_balance_alone_on_scores_with_a_planted_offset():
    """Scores with a constant offset an expert, as a common part of every
    hidden state gives them: as drawn the favoured experts take several
    shares and others none; `_balance` ends within a tenth of even."""
    import jax
    import jax.numpy as jnp

    from benchmark import hybrid_weights as hw

    rng = np.random.default_rng(0)
    n, E, k = 4096, 32, 4
    z = rng.normal(0, 1.0, (n, E)) + rng.normal(0, 0.7, (E,))
    scores = jax.nn.sigmoid(jnp.asarray(z, jnp.float32))

    def load(b):
        _, chosen = jax.lax.top_k(scores + b, k)
        return np.bincount(np.asarray(chosen).ravel(), minlength=E) \
            * E / (n * k)

    zero = jnp.zeros((E,), jnp.float32)
    assert load(zero).max() > 2.5 and load(zero).min() < 0.3
    b = hw._balance(scores, zero, k, 300, "bfloat16")
    assert str(b.dtype) == "float32"
    np.testing.assert_array_equal(
        np.asarray(b), np.asarray(b.astype(jnp.bfloat16), np.float32))
    assert np.abs(load(b) - 1).max() < 0.1, load(b)


@pytest.mark.parametrize("order", ["size_seed", None])
def test_the_order_of_the_pool_is_the_mixs_where_the_mix_says_so(order):
    """With `"order": "size_seed"` two seeds send the same sizes in the same
    order and other ids; without it the generator orders by the seed."""
    import itertools

    from benchmark import traffic
    from benchmark.runners import serve_hybrid

    mix = dict(traffic.load("serve-backlog-2k"))
    assert mix["order"] == "size_seed"
    if order is None:
        del mix["order"]

    def first(seed, n=300):
        ctx = types.SimpleNamespace(seed=seed, mix=mix,
                                    cfg={"vocab_size": 24576})
        return list(itertools.islice(
            serve_hybrid.Runner(ctx)._requests(), n))

    a, b, again = first(2147485000), first(7000000019), first(2147485000)
    sizes = [[(len(p), new) for p, new in reqs] for reqs in (a, b)]
    assert sorted(sizes[0][:256]) == sorted(traffic.request_sizes(mix))
    assert (sizes[0] == sizes[1]) == (order == "size_seed")
    assert not any(len(p) == len(q) and (p == q).all()
                   for (p, _), (q, _) in zip(a, b))
    for (p, _), (q, _) in zip(a, again):
        np.testing.assert_array_equal(p, q)
    assert all(p.dtype == np.int32 and 0 <= p.min() and p.max() < 24576
               for p, _ in a)


def _ctx(cfg, counters):
    from benchmark import counts

    return types.SimpleNamespace(cfg=cfg, counters=counters, chips=1,
                                 peak=counts.peaks("TPU v5 lite"))


def test_no_share_of_a_peak_can_pass_100(cfg):
    """One decode step of 128 rows at context 700, every held expert of every
    layer touched, worked by hand: at the least time its bytes allow the
    share of the bandwidth is exactly 100 and the share of the FLOPs far
    under it; a window that claims more time reads less."""
    from benchmark import hybrid_counts as hc
    from benchmark.readers import hybrid_peak_share as reader

    steps, rows, ctx_len = 1, 128, 700
    held = {"kv": 128 * 4096 * 2 * 8 * 128 * 2,
            "recurrent": 3 * 128 * 64 * 128 * 128 * 4,
            "conv": 3 * 128 * 3 * 24576 * 2}
    assert held["recurrent"] == 1_610_612_736          # the issue's 1.61 GB
    col = 2 * 8 * 128 * 2                              # K and V of a column
    moved = {"kv": (rows * ctx_len + 2 * rows) * col,
             "recurrent": 2 * held["recurrent"], "conv": 2 * held["conv"]}
    nbytes = hc.serve_decode_bytes(cfg, steps, 160, moved)
    assert nbytes == hc.decode_weight_bytes(cfg) + 160 * 15_728_640 * 2 \
        + sum(moved.values())
    assert 9.5e9 < nbytes < 10.5e9                     # the issue's 10.1 GB
    least_s = nbytes / 819e9
    k = {"window_s": least_s, "decode_steps": steps, "new_tokens": rows,
         "prompt_tokens": 0, "prompt_sq": 0, "ctx_tokens": rows * ctx_len,
         "moe_assignments": rows * 8 * 4, "moe_assignments_held": rows * 4,
         "moe_rows_computed": 160 * 8, "moe_experts_touched": 160,
         "state_bytes_moved": moved}
    hbm = reader.read(_ctx(cfg, k), {"resource": "hbm"})
    assert hbm == pytest.approx(100.0)
    flops = reader.read(_ctx(cfg, k), {"resource": "flops"})
    by_hand = 2 * rows * (126_091_264 + 3 * 154_664_960) \
        + 2 * rows * 4 * 15_728_640 + 2 * rows * 4096 * 24576 \
        + 4 * 64 * 128 * rows * ctx_len \
        + rows * 3 * hc.kda_state_flops_per_token(cfg)
    assert flops == pytest.approx(100 * by_hand / least_s / 197e12)
    assert flops < 10                                  # decode is bound by bytes
    # prompts alone at the chip's peak FLOP/s: exactly 100, never more
    pk = dict(k, new_tokens=0, ctx_tokens=0, prompt_tokens=512,
              prompt_sq=512 * 512, moe_assignments_held=0)
    need = hc.serve_flops(cfg, 512, 512 * 512, 0, 0, 0)
    pk["window_s"] = need / 197e12
    assert reader.read(_ctx(cfg, pk), {"resource": "flops"}) == \
        pytest.approx(100.0)
    # another family's counters: nothing to read, never 0
    assert reader.read(_ctx(cfg, {"window_s": 1.0, "decode_steps": 3}),
                       {"resource": "hbm"}) is None
    # rows padded: 160 tiles of 8 carrying 512 assignments
    from benchmark.readers import engine_counter

    spec = _json(BENCH, "metrics", "moe_rows_padded.serve.json")
    k["moe_rows_padded"] = k["moe_rows_computed"] - k["moe_assignments_held"]
    assert engine_counter.read(_ctx(cfg, k), spec["params"]) == \
        pytest.approx(100 * (1280 - 512) / 1280)


@pytest.mark.parametrize("entry", ENTRIES, ids=lambda e: e["name"])
def test_the_metrics_are_ready_for_the_manifest(entry):
    man = _json(ROOT, "BENCHMARK.json")
    listed = {m["name"]: m for m in man["per_layer"]}
    assert listed.get(entry["name"], entry) == entry
    spec = _json(BENCH, "metrics", entry["name"] + ".json")
    for key in ("layer", "unit", "better", "source", "moves"):
        assert spec[key] == entry[key], key
    assert "workloads" not in spec
    assert os.path.isfile(os.path.join(BENCH, "readers",
                                       spec["reader"] + ".py"))
    e2e = {m["name"]: m for m in man["end_to_end"]}
    assert CELL in e2e[entry["moves"]]["workloads"]
    assert set(entry["workloads"]) <= {w["name"] for w in man["workloads"]}
    from benchmark.runners import serve_hybrid

    assert entry["name"] in serve_hybrid.READY


# -- the cell's control flow at a tiny size ---------------------------------------
@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    from benchmark import run

    # a trace directory of its own: another worker's traced rehearsal
    # clears the default one
    rc, result = run.run_cell(REHEARSAL, 2147483997, 2.0, True,
                              keep_trace=str(tmp_path_factory.mktemp("tr")))
    assert rc == 0
    return result


def test_a_traced_rehearsal_reports_the_cells_metrics(traced):
    assert traced["correct"] and traced["failed"] == 0
    got = {k: v["value"] for k, v in traced["metrics"].items()}
    assert set(got) == {"device_idle.serve", "engine_occupancy.serve"}
    assert 0 < got["engine_occupancy.serve"] <= 100
    notes = traced["run"]["notes"]
    ready = notes["per_layer_without_an_entry"]
    assert set(ready) == {e["name"] for e in ENTRIES}
    assert all(0 < v < 100 for v in ready.values()), ready
    work = notes["work"]
    # the share holds experts 4..11 of 16: about half of the assignments
    assert 0.3 < work["held_share_of_assignments"] < 0.7
    assert set(work["state_bytes_a_step"]) == {"kv", "recurrent", "conv"}
    assert notes["phase_ms"]["serve/step"] > 0
    assert traced["checked"]["token_gap"]["value"] < 0.5


def test_a_served_token_altered_is_not_correct(monkeypatch):
    from benchmark import run
    from paddle_tpu.inference.serving import ServingEngine

    real = ServingEngine._dispatch_decode
    calls = {"n": 0}

    def altered(self, active):
        toks, kind = real(self, active)
        calls["n"] += 1
        if calls["n"] % 3 == 0:
            toks = (toks + 7) % 256
        return toks, kind

    monkeypatch.setattr(ServingEngine, "_dispatch_decode", altered)
    rc, result = run.run_cell(REHEARSAL, 2147483996, 2.0, False)
    assert rc == 0 and not result["correct"]
    assert result["checked"]["token_gap"]["value"] > 0.5
    assert set(result["metrics"]) == {"serve_tokens_per_s",
                                      "serve_itl_p95_ms", "setup_s"}


def test_the_parent_cannot_run_the_cell(monkeypatch):
    """A program without the family fails the runner's import at once: the
    driver's try of the new cell on the parent commit ends cleanly."""
    import builtins
    import importlib
    import sys

    real = builtins.__import__

    def no_family(name, *a, **kw):
        if name.endswith("solar_open2") and "paddle_tpu" in name:
            raise ModuleNotFoundError(name)
        return real(name, *a, **kw)

    for mod in [m for m in sys.modules if m.endswith("models.solar_open2")]:
        monkeypatch.delitem(sys.modules, mod)
    monkeypatch.setattr(builtins, "__import__", no_family)
    from benchmark.runners import serve_hybrid

    runner = serve_hybrid.Runner(types.SimpleNamespace(
        workload={}, cfg={}, mix={}, seed=1))
    with pytest.raises(ModuleNotFoundError):
        runner.setup()
    importlib.invalidate_caches()
