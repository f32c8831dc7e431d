"""The `a.x-k1` configuration's part of the benchmark, on the CPU: its file
against the published config, latent_weights.py and latent_counts.py against
hand-worked numbers, the router balanced inside its own matrix, the shares of
a peak that cannot pass 100, the per-layer metrics that wait for a manifest
entry, the planted faults `token_gap` has to catch, and the cell's control
flow at a tiny size (a rehearsal workload)."""
import json
import os
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "a.x-k1.serve-backlog-8k"
REHEARSAL = "rehearsal-serve-latent-tiny"

#: the catalog row's `config` (https://huggingface.co/skt/A.X-K1/blob/main/
#: config.json), every key
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 7168, "intermediate_size": 18432,
    "kv_lora_rank": 512, "max_position_embeddings": 131072,
    "model_type": "axk1", "moe_intermediate_size": 2048, "moe_layer_freq": 1,
    "n_group": 8, "n_routed_experts": 192, "n_shared_experts": 1,
    "norm_topk_prob": True, "num_attention_heads": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 61,
    "num_key_value_heads": 64, "q_lora_rank": 1536, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 32,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid", "seq_aux": True,
    "tie_word_embeddings": False, "topk_group": 4, "topk_method": "none",
    "v_head_dim": 128, "vocab_size": 163840}

#: the entries the four new metric files are written for. BENCHMARK.json
#: cannot list them yet (tests/benchmark/test_benchmark_phases.py pins the
#: list's last four, and a PR may only append: PERF.md section 7)
LAYER = "model step (models/axk1.py, ops/rope.py, distributed/moe.py)"
ENTRIES = [
    {"name": "step_mfu.serve_latent", "unit": "%", "better": "higher",
     "source": "host_clock", "layer": LAYER, "moves": "serve_tokens_per_s",
     "workloads": [CELL]},
    {"name": "step_hbm_share.serve_latent", "unit": "%", "better": "higher",
     "source": "host_clock", "layer": LAYER, "moves": "serve_itl_p95_ms",
     "workloads": [CELL]},
    {"name": "prefill_share.serve", "unit": "%", "better": "lower",
     "source": "program_span",
     "layer": "serving engine (inference/serving.py)",
     "moves": "serve_itl_p95_ms", "workloads": [CELL]},
    {"name": "latent_decode_attention_roofline", "unit": "%",
     "better": "higher", "source": "device_trace",
     "layer": "kernels (ops/latent_decode_attention.py)",
     "moves": "serve_tokens_per_s", "workloads": [CELL]}]


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    return _json(BENCH, "configs", "a.x-k1.json")


@pytest.fixture(scope="module")
def tiny():
    return _json(BENCH, "configs", "a.x-k1-tiny-rehearsal.json")


# -- the configuration's file ----------------------------------------------------
def test_the_file_holds_the_published_keys_but_for_the_three_reduced(cfg):
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    for key, value in PUBLISHED.items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    # every width as published (the issue's list)
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["q_lora_rank"], cfg["kv_lora_rank"],
            cfg["moe_intermediate_size"], cfg["intermediate_size"],
            cfg["num_experts_per_tok"], cfg["routed_scaling_factor"]) == \
        (7168, 64, 128, 64, 128, 1536, 512, 2048, 18432, 8, 2.5)
    dep = cfg["deployment"]
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (5, 12, 20480)
    assert dep["chips_sharing_a_layer"] == 16
    assert dep["held_experts"] == [0, 12]
    assert dep["layers_here"] == [0, 1, 2, 3, 4]
    assert dep["vocab_rows_here"] == [0, 20480]
    assert (dep["num_hidden_layers_published"],
            dep["n_routed_experts_published"],
            dep["vocab_size_published"]) == (61, 192, 163840)
    # the guide's floors: the dense layer and four after it, 8 experts, an
    # eighth of the rows
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert cfg["n_routed_experts"] >= 8
    assert cfg["n_routed_experts"] * dep["chips_sharing_a_layer"] == 192
    assert cfg["vocab_size"] * 8 >= dep["vocab_size_published"]
    for key in ("no_bias", "layer_equations", "topk_method", "seq_aux",
                "rotary_pairing", "rope_scaling", "cache", "init_std",
                "head_init_std", "router_balance", "router_balance_why"):
        assert key in cfg["assumed"], key
    assert "n_group" in cfg["assumed"]["topk_method"]
    man = _json(ROOT, "BENCHMARK.json")
    entry = {c["name"]: c for c in man["configs"]}["a.x-k1"]
    assert entry["source"] == cfg["source"] and entry["reduced"] == cfg["reduced"]
    assert entry["file"] == "benchmark/configs/a.x-k1.json"
    cell = {w["name"]: w for w in man["workloads"]}[CELL]
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    reports = {m["name"] for group in ("end_to_end", "per_layer")
               for m in man[group] if CELL in m.get("workloads", [CELL])}
    assert reports == {"serve_tokens_per_s", "serve_itl_p95_ms", "setup_s",
                       "device_idle.serve", "engine_occupancy.serve"}
    # additions only: what was there is where it was
    assert [c["name"] for c in man["configs"]][-1] == "a.x-k1"
    assert [w["name"] for w in man["workloads"]][-1] == CELL


def test_the_traffic_and_the_engine_are_the_issues(cfg):
    from benchmark import traffic

    mix = traffic.load("serve-backlog-8k")
    assert (mix["kind"], mix["clients"], mix["pool"], mix["size_seed"],
            mix["order"], mix["lead_seconds"], mix["check_requests"]) == \
        ("closed_loop", 256, 256, 36, "size_seed", 12, 8)
    prompts, news = np.array(traffic.request_sizes(mix)).T
    assert prompts.min() >= 256 and prompts.max() <= 6144
    assert news.min() >= 128 and news.max() <= 2048
    assert 1850 <= np.median(prompts) <= 2250 and 450 <= np.median(news) <= 580
    wl = _json(BENCH, "workloads", CELL + ".json")
    assert (prompts + news).max() <= wl["max_seq_len"] == 8192
    assert (wl["max_batch"], wl["prefill_chunk"], wl["dtype"], wl["runner"],
            wl["trace_seconds"]) == (128, 1024, "bfloat16", "serve_latent", 4)
    assert wl["limits"]["compiles_in_window"] == 0 == wl["limits"]["failed"]
    assert 0 < wl["limits"]["token_gap"] < 1.0
    # the issue's engine parameters and no other: what the runner hands
    # ServingEngine is max_batch, dtype and prefill_chunk
    assert set(wl) - {"why", "limits_why"} == {
        "config", "traffic", "runner", "chips", "max_batch", "max_seq_len",
        "prefill_chunk", "dtype", "trace_seconds", "limits"}
    # every prompt stays on the chunked path
    assert -(-prompts.max() // 1024) * 1024 <= 8192


# -- weights and counts against hand-worked numbers -------------------------------
def test_parameter_counts_by_hand(cfg):
    from benchmark import latent_counts as lc
    from benchmark import latent_weights as lw

    d = 7168
    # q_a 7168 x 1536, q_b 1536 x 12288, kv_a 7168 x 576, kv_b 512 x 16384,
    # o 8192 x 7168
    attn = d * 1536 + 1536 * 12288 + d * 576 + 512 * 16384 + 8192 * d
    assert lc.attn_layer_params(cfg) == attn == 101_122_048     # 101.12 M
    assert lc.dense_mlp_params(cfg) == 3 * d * 18432 == 396_361_728
    assert lc.expert_params(cfg) == 3 * d * 2048 == 44_040_192
    assert lc.expert_layer_params(cfg) == 44_040_192 + d * 192
    assert lc.head_params(cfg) == d * 20480 == 146_800_640
    outside = 5 * attn + 396_361_728 + 4 * (44_040_192 + d * 192)
    assert lc.outside_experts_params(cfg) == outside
    assert lc.n_params(cfg) == lw.n_params(cfg)
    assert round(lw.n_params(cfg) / 1e6) == 3491           # the issue's 3,491.2 M
    # what a decode step reads whatever the routing: the issue's 2.46 GB
    assert lc.decode_weight_bytes(cfg) == 2 * (outside + 146_800_640)
    assert round(lc.decode_weight_bytes(cfg) / 1e7) == 246
    assert lc.latent_bytes_per_token(cfg) == 5 * 576 * 2 == 5760
    # one query-key pair a head a layer: naive 2 (192 + 128), absorbed
    # 2 (576 + 512)
    assert lc.naive_attention_flops(cfg, 2) == 5 * 64 * 2 * 320
    assert lc.absorbed_attention_flops(cfg, 1) == 5 * 64 * 2 * 1088


def test_same_seed_same_weights_and_a_name_the_table_lacks_is_refused(tiny):
    from benchmark import latent_weights as lw

    a = lw.flat(tiny, 2147485000, round_to="bfloat16")
    b = lw.flat(tiny, 2147485000, round_to="bfloat16")
    c = lw.flat(tiny, 2147485001, round_to="bfloat16")
    for name in ("layers.1.attn.kv_b.weight", "layers.2.moe.router.weight"):
        assert str(a[name].dtype) == "bfloat16"
        np.testing.assert_array_equal(np.asarray(a[name], np.float32),
                                      np.asarray(b[name], np.float32))
        assert (np.asarray(a[name], np.float32)
                != np.asarray(c[name], np.float32)).any()
    assert not any(n.endswith("router.bias") for n in a)
    name = "layers.1.attn.kv_b.weight"
    init = lw.initializer(tiny, 5)
    assert init(name, a[name].shape, "matrix", "float32").shape == \
        a[name].shape
    with pytest.raises(KeyError):
        init("layers.1.moe.router.bias", (16,), "zero", "float32")
    with pytest.raises(KeyError):
        init(name, (3, 3), "matrix", "float32")
    assert name not in init.missing() and "norm.weight" in init.missing()


def _loads(cfg, seed, ids):
    """Each expert layer's load by expert (1 = an even share) over `ids`,
    through the reference's layers with the weights `flat` hands out."""
    import jax
    import jax.numpy as jnp

    from benchmark import latent_weights as lw
    from benchmark.reference import axk1 as reference

    P = lw.flat(cfg, seed, round_to="bfloat16")
    D = reference.dims_of(cfg)
    x = P["embed.weight"][ids].astype(jnp.float32)
    out = []
    for l in range(D.layers):
        x, h = lw._past_attention(P, x, D, l)
        if l >= D.dense_layers:
            z = h @ P[f"layers.{l}.moe.router.weight"].astype(jnp.float32)
            _, chosen = jax.lax.top_k(z, D.top_k)
            out.append(np.bincount(np.asarray(chosen).ravel(),
                                   minlength=D.experts)
                       * D.experts / chosen.size)
        x = lw._past_ffn(P, x, h, D, l)
    return np.array(out), P


@pytest.mark.parametrize("seed", [3, 2147485000])
def test_a_balanced_router_evens_its_batch_and_helps_on_ids_it_never_saw(
        tiny, seed):
    """The router as drawn favours some experts by the seed. Balanced inside
    its own matrix on 64 x 64 ids from the seed it gives every expert its
    share of THAT batch, in every expert layer; on OTHER ids the part of the
    imbalance that all sequences share goes and what a sequence's own
    context adds stays (a fifth off the spread at least, at this size).
    Nothing but the router's matrix moves and the program's constructor is
    handed the same numbers."""
    import copy

    import jax

    from benchmark import latent_weights as lw
    from benchmark import weights

    tiny = copy.deepcopy(tiny)
    tiny["assumed"]["router_balance"] = {"sequences": 64, "length": 64,
                                         "rounds": 200}
    plain = copy.deepcopy(tiny)
    del plain["assumed"]["router_balance"]
    own = jax.random.randint(
        jax.random.fold_in(weights.seed_key(seed), len(lw.leaves(tiny))),
        (64, 64), 0, tiny["vocab_size"])
    other = jax.random.randint(jax.random.PRNGKey(99), (8, 64), 0,
                               tiny["vocab_size"])
    before, drawn = _loads(plain, seed, other)
    after, P = _loads(tiny, seed, other)
    assert before.shape == (2, 16)
    assert before.std(axis=1).min() > 0.25, before.std(axis=1)
    assert (after.std(axis=1) < 0.8 * before.std(axis=1)).all(), \
        (before.std(axis=1), after.std(axis=1))
    # its own batch: 4,096 ids give an expert 1,024 assignments, 0.03 of
    # noise in the count
    assert _loads(plain, seed, own)[0].std(axis=1).min() > 0.2
    assert _loads(tiny, seed, own)[0].std(axis=1).max() < 0.05
    name = "layers.2.moe.router.weight"
    assert str(P[name].dtype) == "bfloat16"
    moved = {n for n in P if (np.asarray(P[n], np.float32)
                              != np.asarray(drawn[n], np.float32)).any()}
    assert moved == {"layers.1.moe.router.weight", name}
    init = lw.initializer(tiny, seed, round_to="bfloat16")
    np.testing.assert_array_equal(
        np.asarray(init(name, P[name].shape, "matrix", "float32")),
        np.asarray(P[name], np.float32))


def test_balance_alone_on_inputs_with_a_planted_common_part():
    """Router inputs that share a large common vector, as hidden states do:
    as drawn the experts whose columns lie along it take several shares and
    others none; `balanced_router` ends within a tenth of even, and what it
    added to the matrix is of rank one, along that vector."""
    import jax
    import jax.numpy as jnp

    from benchmark import latent_weights as lw

    rng = np.random.default_rng(0)
    n, d, E, k = 4096, 64, 32, 4
    common = rng.normal(0, 1.0, (d,))
    h = jnp.asarray(rng.normal(0, 1.0, (n, d)) + 1.5 * common, jnp.float32)
    w = jnp.asarray(rng.normal(0, 0.1, (d, E)), jnp.float32)

    def load(m):
        _, chosen = jax.lax.top_k(h @ m, k)
        return np.bincount(np.asarray(chosen).ravel(), minlength=E) \
            * E / (n * k)

    assert load(w).max() > 2.5 and load(w).min() < 0.3
    out, delta = lw.balanced_router(h, w, k, 300, None)
    assert np.abs(load(out) - 1).max() < 0.1, load(out)
    added = np.asarray(out - w)
    sv = np.linalg.svd(added, compute_uv=False)
    assert sv[1] < 1e-4 * sv[0]
    u = np.asarray(h).mean(0)
    u /= np.linalg.norm(u)
    np.testing.assert_allclose(added, np.outer(u, u @ added), atol=1e-6)
    assert np.abs(np.asarray(delta)).max() > 0.05


def _ctx(cfg, counters):
    from benchmark import counts

    return types.SimpleNamespace(cfg=cfg, counters=counters, chips=1,
                                 peak=counts.peaks("TPU v5 lite"))


def test_no_share_of_a_peak_can_pass_100(cfg):
    """Two cases by hand. (1) One decode step of 128 rows at context 2,900,
    every held expert of every layer touched: at the least time its bytes
    allow the share of the bandwidth is exactly 100 and the share of the
    FLOPs under it. (2) One prompt of 1,024 tokens at the chip's peak
    FLOP/s: exactly 100, never more. A window that claims more time reads
    less; a program without the expert counts reads nothing. The reader is
    no family's own: handed the other expert family's counts module it reads
    what that family's reader reads."""
    from benchmark import latent_counts as lc
    from benchmark.readers import family_peak_share as reader

    steps, rows, ctx_len = 1, 128, 2900
    col = 5 * 576 * 2                      # one token's latents, all layers
    moved = {"kv": (rows * ctx_len + 2 * rows) * col}
    nbytes = lc.serve_decode_bytes(cfg, steps, 48, moved)
    assert nbytes == lc.decode_weight_bytes(cfg) + 48 * 44_040_192 * 2 \
        + moved["kv"]
    # the issue's 2.46 + 4.23 + 2.1 GB
    assert 8.7e9 < nbytes < 8.9e9 and 2.1e9 < moved["kv"] < 2.2e9
    least_s = nbytes / 819e9
    k = {"window_s": least_s, "decode_steps": steps, "new_tokens": rows,
         "prompt_tokens": 0, "prompt_sq": 0, "ctx_tokens": rows * ctx_len,
         "moe_assignments": rows * 8 * 4, "moe_assignments_held": 64 * 4,
         "moe_rows_computed": 48 * 16, "moe_experts_touched": 48,
         "state_bytes_moved": moved}
    hbm = _json(BENCH, "metrics", "step_hbm_share.serve_latent.json")["params"]
    mfu = _json(BENCH, "metrics", "step_mfu.serve_latent.json")["params"]
    assert (hbm, mfu) == ({"counts": "latent_counts", "resource": "hbm"},
                          {"counts": "latent_counts", "resource": "flops"})
    assert reader.read(_ctx(cfg, k), hbm) == pytest.approx(100.0)
    flops = reader.read(_ctx(cfg, k), mfu)
    by_hand = 2 * rows * lc.outside_experts_params(cfg) \
        + 2 * 64 * 4 * 44_040_192 + 2 * rows * 7168 * 20480 \
        + 5 * 64 * 2 * (576 + 512) * rows * ctx_len
    assert flops == pytest.approx(100 * by_hand / least_s / 197e12)
    # the issue's 0.26 TFLOP of absorbed attention a step
    assert 0.25e12 < 5 * 64 * 2 * 1088 * rows * ctx_len < 0.27e12
    assert 20 < flops < 60                  # a step mixes both bounds
    assert reader.read(_ctx(cfg, dict(k, window_s=2 * least_s)), hbm) == \
        pytest.approx(50.0)
    # prompts alone, the naive form, at the chip's peak
    need = lc.serve_flops(cfg, 1024, 1024 * 1024, 0, 0, 0)
    by_hand = 2 * 1024 * lc.outside_experts_params(cfg) \
        + 5 * 64 * 2 * (192 + 128) * 1024 * 1024 / 2
    assert need == by_hand
    # the issue's 2.4 TFLOP a chunk before its attention and its experts
    assert 2.2e12 < 2 * 1024 * lc.outside_experts_params(cfg) < 2.3e12
    pk = dict(k, new_tokens=0, ctx_tokens=0, prompt_tokens=1024,
              prompt_sq=1024 * 1024, moe_assignments_held=0,
              window_s=need / 197e12)
    assert reader.read(_ctx(cfg, pk), mfu) == pytest.approx(100.0)
    # nothing to read, never 0: a program without the expert counts
    assert reader.read(_ctx(cfg, {"window_s": 1.0, "decode_steps": 3}),
                       hbm) is None
    # the other expert family's counts through the same reader (its module
    # names no expert layers: every layer has them)
    from benchmark.readers import hybrid_peak_share

    solar = _json(BENCH, "configs", "solar-open2-250b.json")
    ks = dict(k, prompt_tokens=1024, prompt_sq=1024 * 1024,
              state_bytes_moved={"kv": 1e8, "recurrent": 3e9, "conv": 1e8})
    for resource in ("hbm", "flops"):
        assert reader.read(
            _ctx(solar, ks), {"counts": "hybrid_counts",
                              "resource": resource}) == pytest.approx(
            hybrid_peak_share.read(_ctx(solar, ks), {"resource": resource}))


def test_the_kernels_roofline_on_synthetic_events(cfg):
    """Two decode steps of 128 rows at position 2,899 (2,900 columns to
    read), the kernel's events taking four times the least time those
    columns allow: 25 %. The bound is memory: 121 operations a byte."""
    from benchmark import counts, latent_counts as lc, run, tracing
    from benchmark.readers import kv_read_roofline as reader

    peak = counts.peaks("TPU v5 lite")
    rows, T, steps, at = 128, 8192, 2, 2899
    stored = 5 * 640 * 2                   # a column as the cache holds it
    columns = steps * rows * (at + 1)
    least, bound = lc.absorbed_read_least_seconds(cfg, columns * 5, peak)
    assert bound == "memory"
    assert least == pytest.approx(columns * 5 * 576 * 2 / 819e9)
    flops = columns * 5 * 2 * 64 * (576 + 512)
    assert flops / (columns * 5 * 576 * 2) == pytest.approx(120.9, abs=0.1)
    ops = [("fusion.1", 0.0, 0.1)] + [
        (f"_latent_decode_attention.{i}", 0.1 + 0.01 * i,
         0.1 + 0.01 * i + 4 * least / 10) for i in range(10)]
    k = {"decode_steps": steps, "max_batch": rows,
         "state_bytes_moved": {"kv": steps * rows * (at + 2) * stored},
         "state_bytes_held": {"kv": rows * T * stored}}
    ctx = run.Ctx(trace=tracing.Trace([ops], [], (0.0, 1.0)), counters=k,
                  cfg=cfg, chips=1, peak=peak, notes={},
                  workload={"max_seq_len": T})
    spec = _json(BENCH, "metrics", "latent_decode_attention_roofline.json")
    assert (spec["params"]["counts"], spec["params"]["least"]) == (
        "latent_counts", "absorbed_read_least_seconds")
    assert reader.read(ctx, spec["params"]) == pytest.approx(25.0)
    assert ctx.notes["latent_decode_attention_roofline_bound"] == "memory"
    # nothing to read -> nothing, never 0: no such event; a program whose
    # engine counts no state bytes
    ctx.trace = tracing.Trace([ops[:1]], [], (0.0, 1.0))
    assert reader.read(ctx, spec["params"]) is None
    ctx.trace = tracing.Trace([ops], [], (0.0, 1.0))
    ctx.counters = {"decode_steps": steps, "max_batch": rows}
    assert reader.read(ctx, spec["params"]) is None


def test_phase_sum_adds_the_phases_of_the_window(monkeypatch):
    from benchmark.readers import phase_idle, phase_sum

    rows = [("serve/step", 0.0, 1.0, None, 1, {}),
            ("serve/prefill_chunk", 0.1, 0.3, "serve/admit", 1, {}),
            ("serve/prefill_chunk", 0.4, 0.5, "serve/admit", 1, {}),
            ("serve/prefill_wait", 0.8, 0.9, "serve/step", 1, {})]
    spec = _json(BENCH, "metrics", "prefill_share.serve.json")
    ctx = types.SimpleNamespace(counters={"window_s": 2.0})
    monkeypatch.setattr(phase_idle, "window_phases", lambda c: rows)
    assert phase_sum.read(ctx, spec["params"]) == pytest.approx(
        100 * (0.2 + 0.1 + 0.1) / 2.0)
    # a cell that never prefills in chunks: nothing, never 0
    monkeypatch.setattr(phase_idle, "window_phases", lambda c: rows[:1])
    assert phase_sum.read(ctx, spec["params"]) is None
    monkeypatch.setattr(phase_idle, "window_phases", lambda c: None)
    assert phase_sum.read(ctx, spec["params"]) is None


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
    from benchmark.runners import serve_latent

    assert entry["name"] in serve_latent.READY
    assert {"moe_rows_padded.serve", "kv_read_share.serve"} <= \
        set(serve_latent.READY)


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
    assert traced["attempted"] >= 1
    got = {k: v["value"] for k, v in traced["metrics"].items()}
    assert set(got) == {"device_idle.serve", "engine_occupancy.serve"}
    assert 0 < got["engine_occupancy.serve"] <= 100
    assert traced["breakdown"]["device_ops"]
    notes = traced["run"]["notes"]
    ready = notes["per_layer_without_an_entry"]
    # off the chip the absorbed step takes the einsums: no kernel to time
    assert set(ready) == {e["name"] for e in ENTRIES} - {
        "latent_decode_attention_roofline"} | {
        "moe_rows_padded.serve", "kv_read_share.serve"}
    assert all(0 < v <= 100 for v in ready.values()), ready
    # the absorbed step's einsums read every column of every row
    assert ready["kv_read_share.serve"] == 100.0
    work = notes["work"]
    # the share holds experts 4..11 of 16: about half of the assignments
    assert 0.3 < work["held_share_of_assignments"] < 0.7
    assert set(work["state_bytes_a_step"]) == {"kv"}
    assert work["prefill_chunks"] > 0 and work["decode_steps"] > 0
    assert notes["phase_ms"]["serve/prefill_chunk"] > 0
    assert notes["setup"]["router_balance_s"] > 0
    assert traced["checked"]["token_gap"]["value"] < 0.5


#: what separates at test size: the float32 program reads under the first,
#: every planted fault and the fp8 control over the second
FAULT_FLOOR, FAULT_LIMIT = 1e-3, 0.3


@pytest.fixture(scope="module")
def served():
    """The rehearsal cell's runner after a window, its engine freed: what
    `check` would compare. With two changes, made here and not in the
    rehearsal's files: matrices drawn at a std of 0.35, not 0.05 (attention
    sharp enough that the softmax scale and the rotary term decide tokens:
    at 0.05 every row attends about evenly and `no_mscale` changes no token
    at all), and a float32 program (at a hidden size of 64 bfloat16's own
    noise then reads 0.2 to 0.9, measured, as much as the smallest fault;
    at the cell's width of 7,168 it is the chip's readings that separate:
    PERF.md section 2)."""
    from benchmark import run

    ctx = run.open_cell(REHEARSAL, 2147483996, require_chip=False)
    ctx.cfg["assumed"]["init_std"] = 0.35
    ctx.workload["dtype"] = "float32"
    ctx.mix["check_requests"] = 8
    runner = run.make_runner(ctx)
    runner.setup()
    ctx.counters = runner.window(2.0)
    runner.release()
    return runner


def _worst(per_request):
    return float(max(g.max() for g in per_request))


def test_the_float32_program_reads_nothing(served):
    assert sum(len(g) for g in served.gaps()) >= 40
    assert _worst(served.gaps()) < FAULT_FLOOR


@pytest.mark.parametrize("fault", ["no_key_rotary", "no_kv_norm",
                                   "no_mscale", "routed_scale_1", "fp8"])
def test_token_gap_catches_a_planted_fault(served, fault):
    """Each part of the mathematics left out of the reference put in the
    program's place (the rotary term off the shared key, the latent's norm
    skipped, YaRN's mscale squared dropped from the softmax scale, the routed
    experts' scale left at 1), and the fp8 control: the widest gap of the
    tokens it would serve reads 0.5 to 5 (measured), over FAULT_LIMIT."""
    gaps = served.gaps(precision="fp8") if fault == "fp8" \
        else served.gaps(faults=(fault,))
    assert _worst(gaps) > FAULT_LIMIT, (fault, _worst(gaps))


def test_the_two_numbers_that_are_compared():
    from benchmark.runners import serve_latent

    got = serve_latent.Runner.numbers([np.array([0.0, 0.0, 0.2]),
                                       np.array([0.4, 0.0])])
    assert got == {"token_gap": pytest.approx(0.4),
                   "token_gap_mean": pytest.approx(0.12),
                   "tokens_compared": 5}
    none = serve_latent.Runner.numbers([])
    assert none["token_gap"] == none["token_gap_mean"] == float("inf")
    for name in (CELL, REHEARSAL):      # both numbers are held to a limit
        limits = _json(BENCH, "workloads", name + ".json")["limits"]
        assert set(limits) == {"token_gap", "token_gap_mean",
                               "compiles_in_window", "failed"}
        assert 0 < limits["token_gap_mean"] < limits["token_gap"] < 1.0


def test_a_window_that_finishes_nothing_samples_what_is_in_flight():
    from benchmark.runners import serve_latent

    runner = serve_latent.Runner(types.SimpleNamespace(
        seed=3, mix={"check_requests": 2}))
    assert runner.sample() == []
    flying = [(np.arange(n), list(range(n // 2))) for n in (20, 90, 40)]
    runner.in_flight = flying
    got = runner.sample()
    assert len(got) == 2 and got[0] is flying[1]         # the longest first
    done = [(np.arange(5), [1, 2])]
    runner.finished = done
    assert runner.sample() == done and runner.finished is done


@pytest.mark.parametrize("finished, want", [(0, 2), (3, 3)])
def test_a_window_that_finishes_nothing_still_attempted_something(
        monkeypatch, finished, want):
    """The result line's `attempted` is at least 1 or the line is no result:
    a window in which no reply ended (the cell's traced 4 s) counts the
    replies it served 8 tokens of or more, the ones it samples; a window in
    which some ended counts those alone, as the other serving cells do."""
    from benchmark.runners import serve_hybrid, serve_latent

    def req(n, done=False):
        return types.SimpleNamespace(prompt_ids=np.arange(12), finished=done,
                                     output_ids=list(range(n)))

    monkeypatch.setattr(
        serve_hybrid.Runner, "window", lambda self, seconds: {
            "attempted": finished, "failed": 0, "decode_steps": 9,
            "steps": {"prefill_chunk": 7}})
    runner = serve_latent.Runner(types.SimpleNamespace(seed=3, mix={}))
    runner.eng = types.SimpleNamespace(
        stats=lambda: {"steps": {"prefill_chunk": 4}})
    runner.clients = [[req(8), 8, None], [req(30), 30, None],
                      [req(7), 7, None], [req(40, done=True), 40, None]]
    k = runner.window(1.0)
    assert (k["attempted"], k["requests_finished"], k["failed"]) == \
        (want, finished, 0)
    assert (k["prefill_chunks"], k["decode_steps"]) == (3, 6)
    assert [len(t) for _, t in runner.in_flight] == [8, 30]


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
        if name.endswith("axk1") and "paddle_tpu" in name:
            raise ModuleNotFoundError(name)
        return real(name, *a, **kw)

    for mod in [m for m in sys.modules if m.endswith("models.axk1")]:
        monkeypatch.delitem(sys.modules, mod)
    monkeypatch.setattr(builtins, "__import__", no_family)
    from benchmark.runners import serve_latent

    runner = serve_latent.Runner(types.SimpleNamespace(
        workload={"dtype": "bfloat16", "max_seq_len": 64},
        cfg=_json(BENCH, "configs", "a.x-k1-tiny-rehearsal.json"), mix={},
        seed=1))
    with pytest.raises(ModuleNotFoundError):
        runner.setup()
    importlib.invalidate_caches()
