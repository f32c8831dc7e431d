"""The solar_open2 family on the CPU at a small size (hidden 64, heads of 16,
16 experts top-4, 2 periods of 3 KDA layers to 1 GQA layer, vocabulary 256;
float32 program): the model, its two forms of KDA, the dropless expert layer
and the serving engine's state kinds, each against the benchmark's plain
reference (benchmark/reference/solar_open2.py: the recurrence token by token,
a dense loop over the held experts, no cache).

Tolerances: program and reference are both float32 here and differ in the
order of their sums alone (chunked against recurrent KDA, sorted tiles
against a dense loop over experts, a cache against a full forward). Logits are
of order 2; 2e-4 absolute is some fifty times what those orders cost here
(measured 5e-6 to 2e-5) and three orders under what one bfloat16 rounding of
an activation would show (1e-2).
"""
import functools
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TOL = 2e-4
BUCKETS = (16, 32, 64, 128)

CFG = {
    "model_type": "solar_open2",
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 16,
                           "num_heads": 4, "num_kv_heads": None},
    "hidden_size": 64, "num_hidden_layers": 8, "num_attention_heads": 4,
    "head_dim": 16, "num_key_value_heads": 2, "vocab_size": 256,
    "moe_intermediate_size": 32, "rms_norm_eps": 1e-5, "gqa_layers": [0, 4],
    "n_routed_experts": 16, "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "num_experts_per_tok": 4,
    "assumed": {"kda_low_rank": 16, "init_std": 0.05,
                "select_bias_std": 0.05},
}
SEED = 7


@functools.lru_cache(maxsize=None)
def _model(max_seq_len=256, cfg=None):
    from benchmark import hybrid_weights

    cfg = cfg or CFG
    from benchmark.runners.serve_hybrid import program_config
    from paddle_tpu.models import SolarOpen2ForCausalLM

    return SolarOpen2ForCausalLM(
        program_config(cfg, max_seq_len),
        initializer=hybrid_weights.initializer(cfg, SEED))


@pytest.fixture(scope="module")
def ref():
    """(P, D, logits(ids) -> [s, V]) of the plain reference."""
    import jax.numpy as jnp

    from benchmark import hybrid_weights
    from benchmark.reference import solar_open2 as reference

    P = hybrid_weights.flat(CFG, SEED)
    D = reference.dims_of(CFG)

    def logits(ids):
        return np.asarray(reference.sequence_logits(
            P, jnp.asarray(np.asarray(ids, np.int32)), D))

    return P, D, logits


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, (n,)).astype(np.int32)


# -- the model ----------------------------------------------------------------
def test_layer_surface_and_pattern():
    from benchmark import hybrid_weights

    m = _model()
    names = dict(m.named_parameters())
    assert len(names) == len(hybrid_weights.leaves(CFG)) == 183
    assert set(m.state_dict()) == set(names)
    assert m.cfg.gqa_layers == (0, 4) and m.cfg.kda_layers == (1, 2, 3, 5, 6, 7)
    assert "layers.0.attn.q.weight" in names and "layers.1.kda.A_log" in names
    assert "layers.1.attn.q.weight" not in names
    assert sum(int(np.prod(p.shape)) for p in names.values()) == \
        hybrid_weights.n_params(CFG)


@pytest.mark.parametrize("length", [63, 64, 300])
def test_whole_forward_matches_the_reference(ref, length):
    """Chunked KDA (chunks of 64, lengths on and off a chunk's edge), the
    softmax attention in query blocks (300 > 256) and the sorted expert
    tiles against the recurrence, a full softmax and a dense expert loop."""
    m = _model()
    ids = np.stack([_ids(length, 1), _ids(length, 2)])
    got = np.asarray(m(ids)._data)
    for b in range(2):
        np.testing.assert_allclose(got[b], ref[2](ids[b]), atol=TOL, rtol=0)


@pytest.mark.parametrize("t", [1, 64, 130])
def test_chunked_kda_is_the_recurrence(t):
    """ops/kda.py's two forms on random inputs with strong and weak decays,
    beta up to 2 (negative eigenvalues), from a state that is not zero."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import kda

    B, H, dk = 2, 3, 16
    ks = jax.random.split(jax.random.PRNGKey(t), 6)
    l2 = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa
    q = l2(jax.random.normal(ks[0], (B, t, H, dk)))
    k = l2(jax.random.normal(ks[1], (B, t, H, dk)))
    v = jax.random.normal(ks[2], (B, t, H, dk))
    g = -jnp.exp(jax.random.uniform(ks[3], (B, t, H, dk), minval=-7.,
                                    maxval=1.5))        # -0.001 .. -4.5
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (B, t, H)))
    S0 = jax.random.normal(ks[5], (B, H, dk, dk))
    o_c, S_c = kda.chunked(S0, q, k, v, g, beta, 0.25)
    S, outs = S0, []
    for i in range(t):
        o, S = kda.recurrent_step(S, q[:, i], k[:, i], v[:, i], g[:, i],
                                  beta[:, i], 0.25)
        outs.append(o)
    np.testing.assert_allclose(np.asarray(o_c), np.stack(outs, 1), atol=2e-5)
    np.testing.assert_allclose(np.asarray(S_c), np.asarray(S), atol=2e-5)


def test_positions_past_valid_len_leave_the_state_alone():
    """A padded whole-sequence call returns the fixed-size state as it stood
    at valid_len: the same as the unpadded call's, leaf by leaf."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models.solar_open2 import _decode_fns

    m = _model()
    p = {n: t._data for n, t in m.named_parameters()}
    fwd, _, cache_init = _decode_fns(m.cfg)
    padded_call = jax.jit(lambda a, vl: fwd(
        p, a, 0, *cache_init(1, 128, jnp.float32), valid_len=vl))
    for n in (2, 37, 64):
        ids = _ids(n, n)
        padded = np.zeros((1, 64), np.int32)
        padded[0, :n] = ids
        _, _, want = jax.jit(lambda a: fwd(
            p, a, 0, *cache_init(1, 128, jnp.float32)))(jnp.asarray(ids[None]))
        _, _, got = padded_call(jnp.asarray(padded), jnp.int32(n))
        for w, g in zip(jax.tree_util.tree_leaves(want),
                        jax.tree_util.tree_leaves(got)):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       atol=2e-5)


# -- the dropless expert layer ---------------------------------------------------
def _layer_inputs(T=24, d=64, f=32, E=16, seed=3):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(T, d)).astype(np.float32)
    router = (0.3 * rng.normal(size=(d, E))).astype(np.float32)
    bias = (0.05 * rng.normal(size=(E,))).astype(np.float32)
    w = [(0.1 * rng.normal(size=s)).astype(np.float32)
         for s in ((E, d, f), (E, d, f), (E, f, d))]
    shared = [(0.1 * rng.normal(size=s)).astype(np.float32)
              for s in ((d, f), (d, f), (f, d))]
    return x, router, bias, w, shared


def test_every_token_to_one_expert_drops_none():
    """A router that sends every token to expert 5 first: the capacity
    form would drop most of them, this one computes them all."""
    import jax.numpy as jnp

    from paddle_tpu.distributed import moe

    x, _, _, w, _ = _layer_inputs(T=40)
    experts = np.tile(np.asarray([[5, 9, 2, 11]], np.int32), (40, 1))
    weights = np.full((40, 4), 0.25, np.float32)
    y, counts = moe.moe_dropless(jnp.asarray(x), jnp.asarray(experts),
                                 jnp.asarray(weights), *map(jnp.asarray, w),
                                 num_experts=16, tile=8)
    want = np.zeros_like(x)
    for e in (5, 9, 2, 11):
        h = x @ w[0][e]
        want += 0.25 * ((h / (1 + np.exp(-h))) * (x @ w[1][e])) @ w[2][e]
    np.testing.assert_allclose(np.asarray(y), want, atol=1e-5)
    assert int(counts["assignments_held"]) == 160
    assert int(counts["rows_computed"]) == 160      # 4 experts x 5 tiles of 8
    assert int(counts["experts_touched"]) == 4
    # held elsewhere: nothing to compute, not a row
    y, counts = moe.moe_dropless(
        jnp.asarray(x), jnp.asarray(experts), jnp.asarray(weights),
        *(jnp.asarray(a[12:]) for a in w), held=(12, 4), num_experts=16)
    assert not np.asarray(y).any() and int(counts["rows_computed"]) == 0


def test_the_shares_add_up(ref):
    """What the 8 shares of a layer give (2 experts each, the shared expert
    counted once) is what the uncut reference's layer gives."""
    import jax.numpy as jnp

    from benchmark.reference import solar_open2 as reference
    from paddle_tpu.distributed import moe

    x, router, bias, w, shared = _layer_inputs()
    parts = []
    for i in range(8):
        y, counts = moe.moe_dropless_layer(
            jnp.asarray(x), jnp.asarray(router), jnp.asarray(bias),
            *(jnp.asarray(a[2 * i:2 * i + 2]) for a in w), 4,
            held=(2 * i, 2))
        parts.append(np.asarray(y))
        assert int(counts["assignments"]) == 24 * 4
    shared_part = np.asarray(moe.gated_mlp(jnp.asarray(x),
                                           *map(jnp.asarray, shared)))
    P = {"m.router.weight": router, "m.router.bias": bias,
         "m.experts.gate": w[0], "m.experts.up": w[1], "m.experts.down": w[2],
         "m.shared.gate.weight": shared[0], "m.shared.up.weight": shared[1],
         "m.shared.down.weight": shared[2]}
    D = ref[1]._replace(held=(0, 16))
    want = np.asarray(reference.moe_layer(P, "m.", jnp.asarray(x), D))
    np.testing.assert_allclose(sum(parts) + shared_part, want, atol=TOL)
    # and one share is the reference's same share
    D2 = ref[1]._replace(held=(6, 2), shared=0)
    P2 = dict(P, **{"m.experts." + n: a[6:8]
                    for n, a in zip(("gate", "up", "down"), w)})
    np.testing.assert_allclose(
        parts[3], np.asarray(reference.moe_layer(P2, "m.", jnp.asarray(x),
                                                 D2)), atol=TOL)


def test_the_layer_api():
    from paddle_tpu import nn

    layer = nn.DroplessMoELayer(32, 16, num_experts=8, k=2, held=(2, 4),
                                shared_d_ff=16)
    names = {n for n, _ in layer.named_parameters()}
    assert {"router_weight", "select_bias", "w_gate", "shared_down"} <= names
    assert list(layer.w_gate.shape) == [4, 32, 16]
    y = layer(np.random.default_rng(0).normal(size=(3, 5, 32)).astype(
        np.float32))
    assert list(y.shape) == [3, 5, 32]
    assert int(layer.counts["assignments"]) == 30
    with pytest.raises(ValueError, match="held"):
        nn.DroplessMoELayer(32, 16, num_experts=8, held=(6, 4))


# -- the engine ------------------------------------------------------------------
def _gap(logits, prompt, tokens):
    rows = logits[len(prompt) - 1: len(prompt) - 1 + len(tokens)]
    return float((rows.max(-1) - rows[np.arange(len(tokens)), tokens]).max())


def test_prefill_at_every_bucket_then_steps_with_other_slots_live(ref):
    """Prompts of 5..100 tokens, padded to buckets 16..128, on 4 slots: the
    prefill's logits and 40 steps' tokens, with the other slots live and at
    other positions, against the reference's full forward."""
    import jax.numpy as jnp

    from paddle_tpu.inference.serving import ServingEngine

    eng = ServingEngine(_model(), max_batch=4, prompt_buckets=BUCKETS)
    assert eng._lookahead and eng._fixed_state
    for n, bucket in ((5, 16), (16, 16), (17, 32), (33, 64), (100, 128)):
        ids = _ids(n, n)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :n] = ids
        _, _, logits = eng._prefill(eng._params, jnp.asarray(padded),
                                    np.int32(n))
        np.testing.assert_allclose(np.asarray(logits), ref[2](ids)[-1],
                                   atol=TOL, rtol=0)
    reqs = []
    for n, new in ((5, 40), (16, 40), (17, 12), (33, 40), (64, 20),
                   (100, 40), (7, 9), (3, 30)):
        ids = _ids(n, 100 + n)
        reqs.append((ids, new, eng.submit(ids, max_new_tokens=new)))
    while eng.has_work():
        eng.step()
    for ids, new, rid in reqs:
        req = eng.get_request(rid)
        toks = list(req.output_ids)
        assert len(toks) == new and req.finish_reason == "length"
        full = ref[2](np.concatenate([ids, toks]))
        # the served token is the reference's best, or within TOL of it
        assert _gap(full, ids, toks) <= TOL
    st = eng.stats()
    assert st["lookahead"]["rounds_overlapped"] >= st["lookahead"]["rounds"] - 2
    assert st["moe_assignments"] == st["moe_assignments_held"] > 0
    assert st["moe_rows_computed"] >= st["moe_assignments_held"]
    # the dispatch gathers the rows walked or, where the tiles are small,
    # every padded row; the combine adds the rows walked back
    assert st["moe_rows_moved"] >= 2 * st["moe_rows_computed"] > 0
    assert set(st["state_bytes"]["held"]) == {"kv", "recurrent", "conv"}
    # 6 KDA layers x 4 slots x 4 heads x 16 x 16 float32
    assert st["state_bytes"]["held"]["recurrent"] == 6 * 4 * 4 * 16 * 16 * 4
    assert st["state_bytes"]["moved"]["recurrent"] == \
        2 * st["state_bytes"]["held"]["recurrent"] * st["lookahead"]["rounds"]


def test_decode_logits_with_other_rows_live(ref):
    """The step's logits themselves (the engine hands out tokens only):
    three rows prefilled at other lengths into one cache by the engine's
    own admit programs, then 40 steps of all rows at their own positions,
    teacher-forced; every row's logits against the reference's."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.models.solar_open2 import _decode_fns

    m = _model()
    eng = ServingEngine(m, max_batch=3, prompt_buckets=BUCKETS)
    fwd, logits_of, _ = _decode_fns(m.cfg)
    p = eng._params
    seqs = [_ids(n + 40, 200 + n) for n in (9, 30, 61)]
    want = [ref[2](s) for s in seqs]
    kc, vc = eng._kc, eng._vc
    for r, (s, n) in enumerate(zip(seqs, (9, 30, 61))):
        padded = np.zeros((1, 64), np.int32)
        padded[0, :n] = s[:n]
        kc1, vc1, lg = eng._prefill(p, jnp.asarray(padded), np.int32(n))
        np.testing.assert_allclose(np.asarray(lg), want[r][n - 1], atol=TOL)
        kc = eng._admit(kc, kc1, r)
        vc = eng._admit_second(vc, vc1, r)

    @jax.jit
    def step(kc, vc, toks, pos):
        x, kc, vc = fwd(p, toks[:, None], pos, kc, vc)
        return logits_of(p, x[:, 0]), kc, vc

    pos = np.asarray([9, 30, 61], np.int32)
    for i in range(40):
        toks = np.asarray([s[q] for s, q in zip(seqs, pos)], np.int32)
        lg, kc, vc = step(kc, vc, jnp.asarray(toks), jnp.asarray(pos))
        for r in range(3):
            np.testing.assert_allclose(np.asarray(lg[r]), want[r][pos[r]],
                                       atol=TOL, rtol=0)
        pos = pos + 1


def test_a_slot_reused_by_a_shorter_request_is_a_fresh_slot(ref):
    """One slot: a long request, then a short one in the same slot. The
    short one's tokens are what a fresh engine gives it (the admission
    replaced the slot's fixed-size state whole), and the reference's."""
    from paddle_tpu.inference.serving import ServingEngine

    m = _model()
    long_ids, short_ids = _ids(90, 1), _ids(6, 2)
    eng = ServingEngine(m, max_batch=1, prompt_buckets=BUCKETS)
    eng.submit(long_ids, max_new_tokens=30)
    rid = eng.submit(short_ids, max_new_tokens=25)
    eng.run_until_complete()
    reused = list(eng.get_request(rid).output_ids)
    fresh_eng = ServingEngine(m, max_batch=1, prompt_buckets=BUCKETS)
    rid = fresh_eng.submit(short_ids, max_new_tokens=25)
    fresh_eng.run_until_complete()
    assert reused == list(fresh_eng.get_request(rid).output_ids)
    assert _gap(ref[2](np.concatenate([short_ids, reused])), short_ids,
                reused) <= TOL


def test_chunked_prefill_and_a_handed_off_row(ref):
    """The engine's other admissions go by the same description: a prompt
    consumed in chunks of 16 (fixed-size state carried from chunk to chunk,
    the last chunk's padding left out), a suffix prefilled from a
    registered prefix's rows, and a row prefilled by a PrefillWorker and
    handed to the engine."""
    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.serving.disagg import PrefillWorker

    m = _model()
    ids = _ids(45, 9)
    plain = ServingEngine(m, max_batch=2, prompt_buckets=BUCKETS)
    rid = plain.submit(ids, max_new_tokens=12)
    plain.run_until_complete()
    want = list(plain.get_request(rid).output_ids)
    assert _gap(ref[2](np.concatenate([ids, want])), ids, want) <= TOL

    chunked = ServingEngine(m, max_batch=2, prompt_buckets=BUCKETS,
                            prefill_chunk=16)
    rid = chunked.submit(ids, max_new_tokens=12)
    chunked.run_until_complete()
    assert list(chunked.get_request(rid).output_ids) == want

    # a registered prefix: its state rows are copied whole and the suffix
    # is prefilled from them (the chunked engine's programs again)
    pid = chunked.register_prefix(ids[:21])
    rid = chunked.submit(ids[21:], max_new_tokens=12, prefix_id=pid)
    chunked.run_until_complete()
    assert list(chunked.get_request(rid).output_ids) == want
    assert chunked.stats()["prefix_cache"]["hit"] == 1

    worker = PrefillWorker(m, prompt_buckets=BUCKETS)
    row, logits = worker.prefill(ids)
    decode = ServingEngine(m, max_batch=2, prompt_buckets=BUCKETS)
    rid = decode.admit_prefilled(ids, row, logits, max_new_tokens=12)
    decode.run_until_complete()
    assert list(decode.get_request(rid).output_ids) == want
    bad = (row[0], dict(row[1], conv=row[1]["conv"][:-1]))
    with pytest.raises(ValueError, match="hand-off row"):
        decode.admit_prefilled(ids, bad, logits, max_new_tokens=2)


def test_the_description_and_the_engines_that_refuse_the_family():
    import jax

    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.serving import decode_model as dm

    m = _model()
    adapter = dm.resolve(m)
    assert adapter.name == "solar_open2"
    spec = adapter.cache_spec(m.cfg)
    kinds = [leaf["kind"] for leaf in spec["leaves"]]
    assert kinds.count("kv") == 2 and kinds.count("recurrent") == 6 \
        and kinds.count("conv") == 6
    fns = adapter.decode_fns(m.cfg, None)
    pair = jax.eval_shape(lambda: fns[2](3, 64, "float32"))
    leaves = dm.state_leaves(spec)
    assert dm.slot_axes(leaves, 0, pair[0]) == {"k": 1, "v": 1}
    assert dm.slot_axes(leaves, 1, pair[1])["recurrent"] == (0,) * 6
    assert pair[0]["k"].shape == (2, 3, 64, 2, 16)
    assert pair[1]["recurrent"][0].shape == (3, 4, 16, 16)
    assert pair[1]["conv"][0].shape == (3, 3, 3 * 4 * 16)
    # a spec without leaves is a K/V pair, slots on axis 1
    assert [l["slot_axis"] for l in dm.state_leaves({"kind": "kv_pair"})] \
        == [1, 1]
    small = __import__("paddle_tpu").models.GPTForCausalLM(
        __import__("paddle_tpu").models.GPTConfig(
            vocab_size=256, hidden_size=32, num_layers=1, num_heads=2,
            max_seq_len=256, dropout=0.0))
    for kw, word in ((dict(draft_model=small), "draft_model"),
                     (dict(cache_dtype="int8"), "cache_dtype"),
                     (dict(max_adapters=2), "max_adapters")):
        with pytest.raises(ValueError, match="solar_open2.*" + word):
            ServingEngine(m, max_batch=2, prompt_buckets=BUCKETS, **kw)
    import paddle_tpu as paddle

    paddle.set_flags({"paged_kv": True})
    try:
        with pytest.raises(ValueError, match="solar_open2.*paged_kv"):
            ServingEngine(m, max_batch=2, prompt_buckets=BUCKETS)
    finally:
        paddle.set_flags({"paged_kv": False})


def test_kv_store_refuses_heads_of_128():
    """From hd 128 on the chip keeps a cache leaf hd-minor, and the T-minor
    view ops/kv_store.py stores through would copy the whole cache in and
    out (PERF.md, PR 31): `fits` draws the line `decode_attention.fits`
    draws."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import decode_attention, kv_store

    def leaf(hd):
        return (jax.ShapeDtypeStruct((1, 8, 8, 1024, hd), jnp.bfloat16),
                jax.ShapeDtypeStruct((8, 8, 1, hd), jnp.bfloat16))

    assert kv_store.fits(*leaf(64))
    assert not kv_store.fits(*leaf(128))
    assert not kv_store.fits(*leaf(256))
    assert decode_attention.fits(*leaf(64))
    assert not decode_attention.fits(*leaf(128))
