"""KV-cache autoregressive decoding (GPTForCausalLM.generate): the fused
prefill+scan program must reproduce the cache-free reference decode (full
re-forward through the model's own layer stack each step) token for token."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import GPTConfig, GPTForCausalLM


def _model():
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
                    max_seq_len=64, dropout=0.0)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


def _reference_greedy(model, ids, n_new):
    """Cache-free decode: full forward over the growing sequence each step."""
    cur = np.asarray(ids)
    for _ in range(n_new):
        logits = model(paddle.to_tensor(cur.astype(np.int32)))
        nxt = np.argmax(np.asarray(logits._data)[:, -1], -1)
        cur = np.concatenate([cur, nxt[:, None]], axis=1)
    return cur


class TestGenerate:
    def test_greedy_matches_cache_free_reference(self):
        model = _model()
        rng = np.random.RandomState(0)
        ids = rng.randint(0, 128, (2, 7)).astype(np.int32)
        want = _reference_greedy(model, ids, 9)
        got = np.asarray(
            model.generate(paddle.to_tensor(ids), max_new_tokens=9,
                           temperature=0.0)._data)
        np.testing.assert_array_equal(got, want)

    def test_single_new_token(self):
        model = _model()
        ids = np.arange(5, dtype=np.int32)[None]
        want = _reference_greedy(model, ids, 1)
        got = np.asarray(model.generate(paddle.to_tensor(ids),
                                        max_new_tokens=1,
                                        temperature=0.0)._data)
        np.testing.assert_array_equal(got, want)

    def test_sampling_seeded_deterministic_and_varies(self):
        model = _model()
        ids = paddle.to_tensor(np.ones((1, 4), np.int32))
        a = np.asarray(model.generate(ids, max_new_tokens=8, temperature=1.0,
                                      top_k=20, seed=7)._data)
        b = np.asarray(model.generate(ids, max_new_tokens=8, temperature=1.0,
                                      top_k=20, seed=7)._data)
        c = np.asarray(model.generate(ids, max_new_tokens=8, temperature=1.0,
                                      top_k=20, seed=8)._data)
        np.testing.assert_array_equal(a, b)
        assert not np.array_equal(a, c)  # different seed, different sample
        assert (a[:, :4] == 1).all()     # prompt preserved

    def test_eos_freezes_tail(self):
        model = _model()
        ids = paddle.to_tensor(np.ones((1, 3), np.int32))
        out = np.asarray(model.generate(ids, max_new_tokens=12,
                                        temperature=0.0,
                                        eos_token_id=int(
                                            _first_greedy_token(model)))._data)
        new = out[0, 3:]
        # the first emitted token IS the eos here, so the whole tail is eos
        assert (new == new[0]).all()

    def test_rejects_overlong_and_parallel_configs(self):
        model = _model()
        ids = paddle.to_tensor(np.ones((1, 60), np.int32))
        with pytest.raises(ValueError, match="max_seq_len"):
            model.generate(ids, max_new_tokens=10)
        cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=1,
                        num_heads=2, max_seq_len=32, dropout=0.0,
                        num_experts=2, moe_every=1)
        moe = GPTForCausalLM(cfg)
        with pytest.raises(ValueError, match="dense"):
            moe.generate(paddle.to_tensor(np.ones((1, 4), np.int32)),
                         max_new_tokens=2)

    def test_weight_update_no_stale_cache(self):
        """Params pass as arguments, so training between generate calls must
        change the output without a retrace."""
        model = _model()
        ids = paddle.to_tensor(
            np.random.RandomState(1).randint(0, 128, (1, 6)).astype(np.int32))
        before = np.asarray(model.generate(ids, max_new_tokens=6,
                                           temperature=0.0)._data)
        for p in model.parameters():  # crude "training": perturb weights
            p.set_value(np.asarray(p._data) * 1.5 + 0.01)
        after = np.asarray(model.generate(ids, max_new_tokens=6,
                                          temperature=0.0)._data)
        want = _reference_greedy(model, np.asarray(ids._data), 6)
        np.testing.assert_array_equal(after, want)
        assert not np.array_equal(before, after)


def _first_greedy_token(model):
    ids = paddle.to_tensor(np.ones((1, 3), np.int32))
    logits = model(ids)
    return np.argmax(np.asarray(logits._data)[0, -1])


def test_untied_head_after_pipeline_split():
    """Review r3: pipeline_split installs a bias-free lm_head; generate must
    take the untied branch without a KeyError and match the model forward."""
    model = _model()
    model.pipeline_split(2)  # installs model.lm_head (bias_attr=False)
    assert getattr(model, "lm_head", None) is not None
    ids = np.random.RandomState(3).randint(0, 128, (1, 5)).astype(np.int32)
    want = _reference_greedy(model, ids, 4)
    got = np.asarray(model.generate(paddle.to_tensor(ids), max_new_tokens=4,
                                    temperature=0.0)._data)
    np.testing.assert_array_equal(got, want)


def test_generate_validates_and_greedy_keeps_rng_state():
    model = _model()
    ids = paddle.to_tensor(np.ones((1, 4), np.int32))
    with pytest.raises(ValueError, match="max_new_tokens"):
        model.generate(ids, max_new_tokens=0)
    from paddle_tpu.core.generator import default_generator

    paddle.seed(123)
    model.generate(ids, max_new_tokens=2, temperature=0.0)
    offset_after = default_generator()._offset
    assert offset_after == 0  # greedy consumed no global randomness


class TestBeamSearch:
    def test_full_width_beam_matches_exhaustive_oracle(self):
        """With n_new=2 and num_beams=V the beam keeps ALL length-1 prefixes,
        so the search is truly exhaustive over the V^2 paths and must equal
        the brute-force argmax (oracle: one batched teacher-forced
        forward)."""
        import itertools

        paddle.seed(0)
        V, n_new = 10, 2
        cfg = GPTConfig(vocab_size=V, hidden_size=32, num_layers=2,
                        num_heads=2, max_seq_len=16, dropout=0.0)
        model = GPTForCausalLM(cfg)
        model.eval()
        ids = np.array([[3, 1, 4]], np.int32)
        s0 = ids.shape[1]

        paths = np.array(list(itertools.product(range(V), repeat=n_new)),
                         np.int32)                       # [V^n, n_new]
        batch = np.concatenate(
            [np.repeat(ids, len(paths), axis=0), paths], axis=1)
        logits = np.asarray(model(paddle.to_tensor(batch))._data)
        z = logits[:, s0 - 1:s0 - 1 + n_new]             # predicts each step
        lse = np.log(np.exp(z - z.max(-1, keepdims=True)).sum(-1)) \
            + z.max(-1)[..., 0:].reshape(z.shape[:-1])
        logp = np.take_along_axis(
            z, paths[..., None], -1)[..., 0] - lse       # [V^n, n_new]
        totals = logp.sum(-1)
        best = int(np.argmax(totals))

        seqs, scores = model.generate(paddle.to_tensor(ids),
                                      max_new_tokens=n_new, num_beams=V)
        got = tuple(np.asarray(seqs._data)[0, s0:])
        assert got == tuple(paths[best]), (got, paths[best])
        np.testing.assert_allclose(float(np.asarray(scores._data)[0]),
                                   totals[best], rtol=1e-4)

    def test_beam_shapes_and_finite_scores(self):
        model = _model()
        ids = paddle.to_tensor(
            np.random.RandomState(5).randint(0, 128, (2, 6)).astype(np.int32))
        seqs, scores = model.generate(ids, max_new_tokens=5, num_beams=4)
        assert np.asarray(seqs._data).shape == (2, 11)
        assert np.asarray(scores._data).shape == (2,)
        assert np.isfinite(np.asarray(scores._data)).all()

    def test_beam_single_new_token(self):
        model = _model()
        ids = paddle.to_tensor(np.ones((1, 4), np.int32))
        seqs, _ = model.generate(ids, max_new_tokens=1, num_beams=3)
        want = _reference_greedy(model, np.asarray(ids._data), 1)
        np.testing.assert_array_equal(np.asarray(seqs._data), want)

    def test_beam_eos_freezes(self):
        model = _model()
        eos = int(_first_greedy_token(model))
        ids = paddle.to_tensor(np.ones((1, 3), np.int32))
        seqs, _ = model.generate(ids, max_new_tokens=8, num_beams=3,
                                 eos_token_id=eos)
        new = np.asarray(seqs._data)[0, 3:]
        hits = np.where(new == eos)[0]
        if hits.size:  # after the first eos, only eos follows
            assert (new[hits[0]:] == eos).all()


def test_beam_length_penalty_prefers_short_finished_beam():
    """GNMT normalization: with a huge length_penalty, a beam that finished
    early (shorter generated length) must win the final pick when scores are
    comparable; with penalty 0 ranking is by raw joint log-prob."""
    model = _model()
    eos = int(_first_greedy_token(model))
    ids = paddle.to_tensor(np.ones((1, 3), np.int32))
    s_short, sc_short = model.generate(ids, max_new_tokens=6, num_beams=4,
                                       eos_token_id=eos, length_penalty=8.0)
    s_raw, sc_raw = model.generate(ids, max_new_tokens=6, num_beams=4,
                                   eos_token_id=eos, length_penalty=0.0)
    # both runs are valid decodes; the knob must at least be able to change
    # the selected beam/score when early-eos beams exist
    a = np.asarray(s_short._data)
    b = np.asarray(s_raw._data)
    assert a.shape == b.shape == (1, 9)
    assert np.isfinite(np.asarray(sc_short._data)).all()
    assert np.isfinite(np.asarray(sc_raw._data)).all()


def test_beam_rejects_overwide():
    model = _model()
    ids = paddle.to_tensor(np.ones((1, 3), np.int32))
    with pytest.raises(ValueError, match="vocab_size"):
        model.generate(ids, max_new_tokens=2, num_beams=500)


def test_bf16_decode_close_to_f32():
    """Serving precision: dtype='bfloat16' halves the KV cache; greedy
    tokens must agree with f32 decode for most steps on a tiny model (bf16
    rounding can legitimately flip near-tie argmaxes, so exact equality is
    not required — but wholesale divergence means broken plumbing)."""
    model = _model()
    ids = paddle.to_tensor(
        np.random.RandomState(2).randint(0, 128, (2, 6)).astype(np.int32))
    f32 = np.asarray(model.generate(ids, max_new_tokens=8,
                                    temperature=0.0)._data)
    bf16 = np.asarray(model.generate(ids, max_new_tokens=8, temperature=0.0,
                                     dtype="bfloat16")._data)
    assert bf16.shape == f32.shape
    # compare GENERATED tokens only (the echoed prompt always matches);
    # bf16 rounding may flip near-tie argmaxes, wholesale divergence may not
    agree = (bf16[:, 6:] == f32[:, 6:]).mean()
    assert agree > 0.5, (agree, bf16, f32)
    import pytest
    with pytest.raises(ValueError, match="floating"):
        model.generate(ids, max_new_tokens=2, dtype="int32")


def test_beam_accepts_dtype_and_f32_is_default_path():
    model = _model()
    ids = paddle.to_tensor(np.ones((1, 4), np.int32))
    seqs, scores = model.generate(ids, max_new_tokens=3, num_beams=3,
                                  dtype="bfloat16")
    assert np.asarray(seqs._data).shape == (1, 7)
    assert np.isfinite(np.asarray(scores._data)).all()
    # explicit float32 must not duplicate the compiled program
    n_before = len(model._generate_compiled)
    model.generate(ids, max_new_tokens=3, temperature=0.0)
    n_mid = len(model._generate_compiled)
    model.generate(ids, max_new_tokens=3, temperature=0.0, dtype="float32")
    assert len(model._generate_compiled) == n_mid


class TestRaggedBatchDecode:
    def test_left_padded_rows_match_individual_decodes(self):
        """Batched ragged serving: each LEFT-padded row's greedy continuation
        must EXACTLY match decoding that prompt alone (positions, masks and
        cache columns all line up)."""
        model = _model()
        rng = np.random.RandomState(4)
        p1 = rng.randint(1, 128, 4).astype(np.int32)   # len 4
        p2 = rng.randint(1, 128, 7).astype(np.int32)   # len 7
        s0 = 7
        batch = np.zeros((2, s0), np.int32)
        batch[0, s0 - 4:] = p1
        batch[1] = p2
        mask = np.zeros((2, s0), np.int32)
        mask[0, s0 - 4:] = 1
        mask[1] = 1

        out = np.asarray(model.generate(
            paddle.to_tensor(batch), max_new_tokens=6, temperature=0.0,
            attention_mask=paddle.to_tensor(mask))._data)

        solo1 = np.asarray(model.generate(
            paddle.to_tensor(p1[None]), max_new_tokens=6,
            temperature=0.0)._data)
        solo2 = np.asarray(model.generate(
            paddle.to_tensor(p2[None]), max_new_tokens=6,
            temperature=0.0)._data)
        np.testing.assert_array_equal(out[0, s0:], solo1[0, 4:])
        np.testing.assert_array_equal(out[1, s0:], solo2[0, 7:])

    def test_mask_validation(self):
        model = _model()
        ids = paddle.to_tensor(np.ones((2, 5), np.int32))
        right_pad = paddle.to_tensor(
            np.array([[1, 1, 1, 0, 0]] * 2, np.int32))
        with pytest.raises(ValueError, match="LEFT-padded"):
            model.generate(ids, max_new_tokens=2, temperature=0.0,
                           attention_mask=right_pad)
        all_pad = paddle.to_tensor(np.zeros((2, 5), np.int32))
        with pytest.raises(ValueError, match="all-pad"):
            model.generate(ids, max_new_tokens=2, temperature=0.0,
                           attention_mask=all_pad)



def test_non_binary_mask_rejected():
    model = _model()
    ids = paddle.to_tensor(np.ones((1, 4), np.int32))
    bad = paddle.to_tensor(np.array([[0, 1, 2, 2]], np.int32))
    with pytest.raises(ValueError, match="binary"):
        model.generate(ids, max_new_tokens=2, temperature=0.0,
                       attention_mask=bad)


def test_ragged_beam_matches_solo_beam():
    """Beam search over a left-padded ragged batch: each row's best beam
    must match beam-decoding that prompt alone."""
    model = _model()
    rng = np.random.RandomState(6)
    p1 = rng.randint(1, 128, 3).astype(np.int32)
    p2 = rng.randint(1, 128, 6).astype(np.int32)
    s0 = 6
    batch = np.zeros((2, s0), np.int32)
    mask = np.zeros((2, s0), np.int32)
    batch[0, s0 - 3:] = p1; mask[0, s0 - 3:] = 1
    batch[1] = p2; mask[1] = 1

    seqs, scores = model.generate(paddle.to_tensor(batch), max_new_tokens=5,
                                  num_beams=3,
                                  attention_mask=paddle.to_tensor(mask))
    out = np.asarray(seqs._data)
    s1, sc1 = model.generate(paddle.to_tensor(p1[None]), max_new_tokens=5,
                             num_beams=3)
    s2, sc2 = model.generate(paddle.to_tensor(p2[None]), max_new_tokens=5,
                             num_beams=3)
    np.testing.assert_array_equal(out[0, s0:], np.asarray(s1._data)[0, 3:])
    np.testing.assert_array_equal(out[1, s0:], np.asarray(s2._data)[0, 6:])
    np.testing.assert_allclose(np.asarray(scores._data),
                               [float(np.asarray(sc1._data)[0]),
                                float(np.asarray(sc2._data)[0])], rtol=1e-5)


def test_top_p_sampling():
    """Nucleus sampling: top_p -> 0 degenerates to greedy (only the argmax
    survives the nucleus); seeded runs are deterministic."""
    model = _model()
    ids = paddle.to_tensor(np.ones((2, 4), np.int32))
    greedy = np.asarray(model.generate(ids, max_new_tokens=6,
                                       temperature=0.0)._data)
    tiny_p = np.asarray(model.generate(ids, max_new_tokens=6,
                                       temperature=1.0, top_p=1e-6,
                                       seed=0)._data)
    np.testing.assert_array_equal(tiny_p, greedy)
    a = np.asarray(model.generate(ids, max_new_tokens=6, temperature=1.0,
                                  top_p=0.9, seed=3)._data)
    b = np.asarray(model.generate(ids, max_new_tokens=6, temperature=1.0,
                                  top_p=0.9, seed=3)._data)
    np.testing.assert_array_equal(a, b)
    assert np.isfinite(a).all()


def test_beam_rejects_sampling_knobs():
    model = _model()
    ids = paddle.to_tensor(np.ones((1, 4), np.int32))
    with pytest.raises(ValueError, match="sampling knobs"):
        model.generate(ids, max_new_tokens=2, num_beams=2, top_p=0.9)
    with pytest.raises(ValueError, match="sampling knobs"):
        model.generate(ids, max_new_tokens=2, num_beams=2, top_k=5)


class TestInt8KVCache:
    """cache_dtype='int8': per-row absmax-quantized KV cache — half the bf16
    cache's HBM traffic in the HBM-bound decode loop."""

    def test_greedy_matches_f32_cache(self):
        model = _model()
        ids = paddle.to_tensor(
            np.random.RandomState(2).randint(0, 128, (2, 6)).astype(np.int32))
        f32 = np.asarray(model.generate(ids, max_new_tokens=8,
                                        temperature=0.0)._data)
        i8 = np.asarray(model.generate(ids, max_new_tokens=8, temperature=0.0,
                                       cache_dtype="int8")._data)
        assert i8.shape == f32.shape
        # int8 rounding can flip near-tie argmaxes; wholesale divergence
        # means broken quantization plumbing (same bar as the bf16 test)
        agree = (i8[:, 6:] == f32[:, 6:]).mean()
        assert agree > 0.5, (agree, i8, f32)

    def test_beam_search_with_int8_cache(self):
        """Beam search reorders the (values, scales) pair by parent beam —
        both components must travel together through repeat/gather/scan."""
        model = _model()
        ids = paddle.to_tensor(
            np.random.RandomState(3).randint(0, 128, (2, 5)).astype(np.int32))
        s_f, sc_f = model.generate(ids, max_new_tokens=6, num_beams=3)
        s_i, sc_i = model.generate(ids, max_new_tokens=6, num_beams=3,
                                   cache_dtype="int8")
        assert np.asarray(s_i._data).shape == np.asarray(s_f._data).shape
        assert np.isfinite(np.asarray(sc_i._data)).all()
        gen_f = np.asarray(s_f._data)[:, 5:]  # generated tokens only
        gen_i = np.asarray(s_i._data)[:, 5:]
        agree = (gen_i == gen_f).mean()
        assert agree > 0.5

    def test_composes_with_bf16_params_and_ragged_batch(self):
        model = _model()
        rng = np.random.RandomState(4)
        ids = np.full((2, 6), 7, np.int32)
        ids[1, :3] = 0  # left-padded row
        amask = np.ones((2, 6), np.int32)
        amask[1, :3] = 0
        ids_t = paddle.to_tensor(ids)
        out = model.generate(ids_t, max_new_tokens=4, temperature=0.0,
                             dtype="bfloat16", cache_dtype="int8",
                             attention_mask=paddle.to_tensor(amask))
        arr = np.asarray(out._data)
        assert arr.shape == (2, 10)
        assert np.isfinite(arr.astype(np.float64)).all()

    def test_rejects_unknown_cache_dtype(self):
        import pytest

        model = _model()
        ids = paddle.to_tensor(np.ones((1, 4), np.int32))
        with pytest.raises(ValueError, match="cache_dtype"):
            model.generate(ids, max_new_tokens=2, cache_dtype="int4")

    def test_compiled_decode_temp_memory_shrinks(self):
        """XLA-level evidence the int8 cache is real: the compiled decode
        program's peak temp allocation must shrink vs the f32 cache (the
        quantized cache has to survive XLA's buffer assignment, not just
        the python-level dtype)."""
        import jax
        import pytest

        model = _model()
        ids = paddle.to_tensor(np.ones((2, 8), np.int32))
        model.generate(ids, max_new_tokens=32, temperature=0.0)
        model.generate(ids, max_new_tokens=32, temperature=0.0,
                       cache_dtype="int8")
        params = {n: p._data for n, p in model.named_parameters()}
        key = jax.random.key(0)  # typed key, matching production generate()
        sizes = {}
        for k, fn in model._generate_compiled.items():
            mem = fn.lower(params, ids._data, key,
                           None).compile().memory_analysis()
            t = getattr(mem, "temp_size_in_bytes", None)
            if t is None:
                pytest.skip("backend reports no memory analysis")
            sizes["int8" if "int8" in k else "f32"] = t
        assert sizes["int8"] < 0.75 * sizes["f32"], sizes


class TestFp8KVCache:
    """cache_dtype='fp8' (r5): float8_e4m3fn KV cache at int8's byte
    footprint — scaled casts keep a mantissa instead of integer
    rounding; the same (values, scales) plumbing as int8."""

    def test_greedy_tracks_f32_cache_closely(self):
        model = _model()
        ids = paddle.to_tensor(
            np.random.RandomState(5).randint(0, 128, (2, 6)).astype(np.int32))
        f32 = np.asarray(model.generate(ids, max_new_tokens=8,
                                        temperature=0.0)._data)
        f8 = np.asarray(model.generate(ids, max_new_tokens=8,
                                       temperature=0.0,
                                       cache_dtype="fp8")._data)
        assert f8.shape == f32.shape
        agree = (f8[:, 6:] == f32[:, 6:]).mean()
        assert agree > 0.5, (agree, f8, f32)

    def test_serving_engine_fp8_exact_parity_vs_generate_fp8(self):
        from paddle_tpu.inference.serving import ServingEngine

        model = _model()
        eng = ServingEngine(model, max_batch=2, cache_dtype="fp8")
        rng = np.random.RandomState(6)
        prompts = [rng.randint(0, 128, (n,)).astype(np.int32)
                   for n in (5, 9)]
        rids = [eng.submit(p, max_new_tokens=6) for p in prompts]
        res = eng.run_until_complete()
        for rid, p in zip(rids, prompts):
            ref = np.asarray(model.generate(
                paddle.to_tensor(p[None]), max_new_tokens=6,
                temperature=0.0, cache_dtype="fp8")._data)[0, len(p):]
            np.testing.assert_array_equal(res[rid].tokens, ref)

    def test_cache_codec_dtypes_and_range(self):
        # the cache really stores the quantized dtype (int8 / e4m3fn), and
        # the fp8 codec's qmax=448 sits inside e4m3fn's representable range
        import jax.numpy as jnp

        from paddle_tpu.models.gpt import _decode_fns

        model = _model()
        cfg = model.cfg
        for cd in ("int8", "fp8"):
            _, _, cache_init = _decode_fns(cfg, False, False,
                                           cache_dtype=cd)
            kc, vc = cache_init(1, 8, jnp.float32)
            assert (kc[0].dtype == (jnp.int8 if cd == "int8"
                                    else jnp.float8_e4m3fn))
        x = jnp.asarray(447.0, jnp.float32).astype(jnp.float8_e4m3fn)
        assert float(x.astype(jnp.float32)) > 400.0

    def test_central_validation_covers_speculative(self):
        # the _QUANT table is the single interpreter of cache_dtype: a
        # typo through ANY entry point (here the speculative path, which
        # has no validation of its own) must raise, never silently serve
        # a full-precision cache
        model = _model()
        ids = paddle.to_tensor(np.ones((1, 4), np.int32))
        with pytest.raises(ValueError, match="cache_dtype"):
            model.generate_speculative(model, ids, max_new_tokens=2,
                                       cache_dtype="f8")

    def test_engine_rejects_unknown_cache_dtype(self):
        from paddle_tpu.inference.serving import ServingEngine

        model = _model()
        with pytest.raises(ValueError, match="cache_dtype"):
            ServingEngine(model, cache_dtype="int4")


class TestSpeculativeDecoding:
    """generate_speculative: draft proposes k, target verifies in one
    forward; output must equal the target's own greedy decode."""

    def _pair(self):
        paddle.seed(0)
        cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                        num_heads=4, max_seq_len=128, dropout=0.0)
        target = GPTForCausalLM(cfg)
        target.eval()
        paddle.seed(7)
        dcfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=1,
                         num_heads=2, max_seq_len=128, dropout=0.0)
        draft = GPTForCausalLM(dcfg)
        draft.eval()
        ids = paddle.to_tensor(
            np.random.RandomState(2).randint(0, 128, (1, 6)).astype(np.int32))
        return target, draft, ids

    def test_matches_plain_greedy(self):
        target, draft, ids = self._pair()
        plain = np.asarray(target.generate(ids, max_new_tokens=20,
                                           temperature=0.0)._data)
        spec, rounds = target.generate_speculative(draft, ids,
                                                   max_new_tokens=20, k=4)
        np.testing.assert_array_equal(np.asarray(spec._data), plain)
        assert 1 <= rounds <= 20

    def test_perfect_draft_needs_fewer_rounds(self):
        """Draft == target: every proposal accepted, so the data-dependent
        while_loop exits in the ideal ceil(20/(k+1)) = 4 rounds (a small
        slack tolerates numeric near-ties on the random test model; rounds
        near 20 would mean acceptance — or the draft KV cache — broke)."""
        target, _, ids = self._pair()
        plain = np.asarray(target.generate(ids, max_new_tokens=20,
                                           temperature=0.0)._data)
        spec, rounds = target.generate_speculative(target, ids,
                                                   max_new_tokens=20, k=4)
        np.testing.assert_array_equal(np.asarray(spec._data), plain)
        assert rounds <= 5, rounds

    def test_validation(self):
        import pytest

        target, draft, ids = self._pair()
        with pytest.raises(ValueError, match="batch"):
            target.generate_speculative(
                draft, paddle.to_tensor(np.ones((2, 6), np.int32)),
                max_new_tokens=4)
        with pytest.raises(ValueError, match="k must"):
            target.generate_speculative(draft, ids, max_new_tokens=4, k=0)
        paddle.seed(1)
        other = GPTForCausalLM(GPTConfig(vocab_size=64, hidden_size=32,
                                         num_layers=1, num_heads=2,
                                         max_seq_len=128, dropout=0.0))
        other.eval()
        with pytest.raises(ValueError, match="vocab"):
            target.generate_speculative(other, ids, max_new_tokens=4)

    def test_composes_with_bf16_and_int8_cache(self):
        target, draft, ids = self._pair()
        spec, rounds = target.generate_speculative(
            draft, ids, max_new_tokens=12, k=3, dtype="bfloat16",
            cache_dtype="int8")
        arr = np.asarray(spec._data)
        assert arr.shape == (1, 18)
        assert ((0 <= arr) & (arr < 128)).all()


class TestTensorParallelDecode:
    """generate(tp_mesh=...): Megatron-style head/MLP-sharded serving of a
    DENSE model — local-head KV caches, two psums per layer; tokens must
    match the single-replica decode exactly."""

    def _mesh(self, n=4):
        import jax

        from paddle_tpu.distributed.mesh import build_mesh

        return build_mesh((n,), ("mp",), devices=jax.devices()[:n])

    def test_greedy_matches_dense(self):
        model = _model()
        ids = paddle.to_tensor(
            np.random.RandomState(2).randint(0, 128, (2, 6)).astype(np.int32))
        dense = np.asarray(model.generate(ids, max_new_tokens=8,
                                          temperature=0.0)._data)
        tp = np.asarray(model.generate(ids, max_new_tokens=8,
                                       temperature=0.0,
                                       tp_mesh=self._mesh())._data)
        np.testing.assert_array_equal(tp, dense)

    def test_ragged_and_int8_compose(self):
        model = _model()
        ids = np.full((2, 6), 7, np.int32)
        ids[1, :3] = 0
        amask = np.ones((2, 6), np.int32)
        amask[1, :3] = 0
        ids_t = paddle.to_tensor(ids)
        mk = paddle.to_tensor(amask)
        dense = np.asarray(model.generate(ids_t, max_new_tokens=6,
                                          temperature=0.0,
                                          attention_mask=mk)._data)
        tp = np.asarray(model.generate(ids_t, max_new_tokens=6,
                                       temperature=0.0, attention_mask=mk,
                                       tp_mesh=self._mesh())._data)
        np.testing.assert_array_equal(tp, dense)
        # int8 codec correctness under tp, in f32 so psum reassociation
        # cannot flip near-tie argmaxes (bf16 composition is exercised for
        # shape/compile by the drive below)
        i8_dense = np.asarray(model.generate(ids_t, max_new_tokens=6,
                                             temperature=0.0,
                                             cache_dtype="int8")._data)
        i8_tp = np.asarray(model.generate(ids_t, max_new_tokens=6,
                                          temperature=0.0,
                                          cache_dtype="int8",
                                          tp_mesh=self._mesh())._data)
        np.testing.assert_array_equal(i8_tp, i8_dense)
        bf = np.asarray(model.generate(ids_t, max_new_tokens=6,
                                       temperature=0.0, dtype="bfloat16",
                                       cache_dtype="int8",
                                       tp_mesh=self._mesh())._data)
        assert bf.shape == dense.shape

    def test_sampling_replicated_across_ranks(self):
        """Sampled decode under tp runs the categorical draw replicated on
        every rank with the same key — output must equal the dense sample
        with the same seed."""
        model = _model()
        ids = paddle.to_tensor(
            np.random.RandomState(3).randint(0, 128, (2, 5)).astype(np.int32))
        dense = np.asarray(model.generate(ids, max_new_tokens=6,
                                          temperature=0.8, top_k=20,
                                          seed=11)._data)
        tp = np.asarray(model.generate(ids, max_new_tokens=6,
                                       temperature=0.8, top_k=20, seed=11,
                                       tp_mesh=self._mesh())._data)
        np.testing.assert_array_equal(tp, dense)

    def test_validation(self):
        import pytest

        model = _model()
        ids = paddle.to_tensor(np.ones((1, 4), np.int32))
        with pytest.raises(ValueError, match="divisible"):
            model.generate(ids, max_new_tokens=2, tp_mesh=self._mesh(8))
        with pytest.raises(ValueError, match="divisible"):  # beam path too
            model.generate(ids, max_new_tokens=2, num_beams=2,
                           tp_mesh=self._mesh(8))
        with pytest.raises(ValueError, match="mp"):
            from paddle_tpu.distributed.mesh import build_mesh
            import jax
            bad = build_mesh((4,), ("dp",), devices=jax.devices()[:4])
            model.generate(ids, max_new_tokens=2, tp_mesh=bad)

    def test_beam_search_matches_dense(self):
        model = _model()
        ids = paddle.to_tensor(
            np.random.RandomState(3).randint(0, 128, (2, 5)).astype(np.int32))
        s_d, sc_d = model.generate(ids, max_new_tokens=6, num_beams=3)
        s_t, sc_t = model.generate(ids, max_new_tokens=6, num_beams=3,
                                   tp_mesh=self._mesh())
        np.testing.assert_array_equal(np.asarray(s_t._data),
                                      np.asarray(s_d._data))
        np.testing.assert_allclose(np.asarray(sc_t._data),
                                   np.asarray(sc_d._data), atol=1e-4)

    def test_speculative_under_tp(self):
        """Speculative decode with the TARGET sharded over mp (draft
        replicated) still reproduces the plain greedy output exactly."""
        paddle.seed(0)
        cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                        num_heads=4, max_seq_len=128, dropout=0.0)
        target = GPTForCausalLM(cfg)
        target.eval()
        paddle.seed(7)
        draft = GPTForCausalLM(GPTConfig(vocab_size=128, hidden_size=32,
                                         num_layers=1, num_heads=2,
                                         max_seq_len=128, dropout=0.0))
        draft.eval()
        ids = paddle.to_tensor(
            np.random.RandomState(2).randint(0, 128, (1, 6)).astype(np.int32))
        plain = np.asarray(target.generate(ids, max_new_tokens=16,
                                           temperature=0.0)._data)
        spec, rounds = target.generate_speculative(
            draft, ids, max_new_tokens=16, k=4, tp_mesh=self._mesh())
        np.testing.assert_array_equal(np.asarray(spec._data), plain)
        assert 1 <= rounds <= 16


def test_speculative_eos_early_stop_matches_dense():
    """eos inside the accepted slice stops the speculative loop early and
    the output (eos-filled tail) matches dense generate with the same eos."""
    model = _model()
    ids = paddle.to_tensor(
        np.random.RandomState(2).randint(0, 128, (1, 6)).astype(np.int32))
    plain = np.asarray(model.generate(ids, max_new_tokens=20,
                                      temperature=0.0)._data)
    eos_tok = int(plain[0, 6 + 4])  # the 5th generated token as 'eos'
    dense = np.asarray(model.generate(ids, max_new_tokens=20,
                                      temperature=0.0,
                                      eos_token_id=eos_tok)._data)
    spec, rounds = model.generate_speculative(model, ids, max_new_tokens=20,
                                              k=4, eos_token_id=eos_tok)
    np.testing.assert_array_equal(np.asarray(spec._data), dense)
    # perfect draft without eos needs ceil(20/5)=4 rounds; the early eos
    # must cut that down
    assert rounds < 4, rounds


def test_attention_window_decode_matches_cache_free():
    """GPTConfig(attention_window=W): the KV-cache decode masks the same
    band the training forward uses, so greedy generate equals the
    cache-free windowed forward — and differs from full attention."""
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                    num_heads=4, max_seq_len=64, dropout=0.0,
                    attention_window=8)
    m = GPTForCausalLM(cfg)
    m.eval()
    ids = paddle.to_tensor(
        np.random.RandomState(2).randint(0, 128, (2, 20)).astype(np.int32))
    cur = np.asarray(ids._data)
    for _ in range(10):
        logits = np.asarray(m(paddle.to_tensor(cur))._data)
        nxt = logits[:, -1].argmax(-1).astype(np.int32)[:, None]
        cur = np.concatenate([cur, nxt], axis=1)
    gen = np.asarray(m.generate(ids, max_new_tokens=10,
                                temperature=0.0)._data)
    np.testing.assert_array_equal(gen, cur)

    paddle.seed(0)
    full = GPTForCausalLM(GPTConfig(vocab_size=128, hidden_size=64,
                                    num_layers=2, num_heads=4,
                                    max_seq_len=64, dropout=0.0))
    full.eval()
    full.set_state_dict(m.state_dict())
    gen_full = np.asarray(full.generate(ids, max_new_tokens=10,
                                        temperature=0.0)._data)
    assert not (gen_full == gen).all()  # the window is actually active

    import pytest
    with pytest.raises(ValueError, match="attention_window"):
        GPTConfig(attention_window=0)


class TestGroupedQueryAttention:
    """GQA (num_kv_heads < num_heads): compact K/V heads shared per query
    group — the KV cache shrinks by heads/kv_heads while the math equals an
    MHA model whose kv weights are replicated per group."""

    def _gqa(self):
        paddle.seed(0)
        cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                        num_heads=4, max_seq_len=64, dropout=0.0,
                        num_kv_heads=2)
        m = GPTForCausalLM(cfg)
        m.eval()
        return cfg, m

    def test_equals_mha_with_replicated_kv(self):
        """Replicating each kv head across its group inside an MHA model
        must reproduce the GQA forward exactly."""
        cfg, m = self._gqa()
        H, K = 4, 2
        hd = cfg.hidden_size // H
        paddle.seed(1)
        mha = GPTForCausalLM(GPTConfig(vocab_size=128, hidden_size=64,
                                       num_layers=2, num_heads=4,
                                       max_seq_len=64, dropout=0.0))
        mha.eval()
        sd = m.state_dict()
        out_sd = {}
        for n, v in mha.state_dict().items():
            src = np.asarray(sd[n].numpy()) if n in sd else None
            if n.endswith("attn.qkv.weight"):
                gq = np.asarray(sd[n].numpy())  # [h, (H+2K)*hd]
                q_w = gq[:, :H * hd]
                k_w = gq[:, H * hd:(H + K) * hd].reshape(-1, K, hd)
                v_w = gq[:, (H + K) * hd:].reshape(-1, K, hd)
                rep = lambda w: np.repeat(w, H // K, axis=1).reshape(
                    -1, H * hd)
                out_sd[n] = np.concatenate([q_w, rep(k_w), rep(v_w)], axis=1)
            elif n.endswith("attn.qkv.bias"):
                gb = np.asarray(sd[n].numpy())
                q_b = gb[:H * hd]
                k_b = gb[H * hd:(H + K) * hd].reshape(K, hd)
                v_b = gb[(H + K) * hd:].reshape(K, hd)
                rep = lambda w: np.repeat(w, H // K, axis=0).reshape(-1)
                out_sd[n] = np.concatenate([q_b, rep(k_b), rep(v_b)])
            else:
                out_sd[n] = src
        mha.set_state_dict(out_sd)
        ids = paddle.to_tensor(
            np.random.RandomState(2).randint(0, 128, (2, 16)).astype(np.int32))
        np.testing.assert_allclose(np.asarray(mha(ids)._data),
                                   np.asarray(m(ids)._data),
                                   atol=1e-5, rtol=1e-5)

    def test_decode_matches_cache_free(self):
        cfg, m = self._gqa()
        ids = paddle.to_tensor(
            np.random.RandomState(2).randint(0, 128, (2, 12)).astype(np.int32))
        cur = np.asarray(ids._data)
        for _ in range(8):
            logits = np.asarray(m(paddle.to_tensor(cur))._data)
            nxt = logits[:, -1].argmax(-1).astype(np.int32)[:, None]
            cur = np.concatenate([cur, nxt], axis=1)
        gen = np.asarray(m.generate(ids, max_new_tokens=8,
                                    temperature=0.0)._data)
        np.testing.assert_array_equal(gen, cur)
        # int8 cache composes with the compact kv heads
        i8 = np.asarray(m.generate(ids, max_new_tokens=8, temperature=0.0,
                                   cache_dtype="int8")._data)
        agree = (i8[:, 12:] == gen[:, 12:]).mean()
        assert agree > 0.5

    def test_cache_holds_compact_kv_heads(self):
        from paddle_tpu.models.gpt import _decode_fns

        cfg, _ = self._gqa()
        import jax.numpy as jnp

        _, _, cache_init = _decode_fns(cfg, False, False)
        (kc), _ = cache_init(1, 32, jnp.float32)
        assert kc.shape[2] == 2  # kv heads, not the 4 query heads

    def test_trains(self):
        cfg, m = self._gqa()
        m.train()
        opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                     parameters=m.parameters())
        rng = np.random.RandomState(0)
        ids = paddle.to_tensor(rng.randint(0, 128, (2, 16)).astype(np.int32))
        losses = []
        for _ in range(4):
            loss = m.loss(ids, ids)
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(np.asarray(loss._data)))
        assert np.isfinite(losses).all() and losses[-1] < losses[0]

    def test_validation(self):
        import pytest

        with pytest.raises(ValueError, match="num_kv_heads"):
            GPTConfig(num_heads=4, num_kv_heads=3)
        with pytest.raises(ValueError, match="num_kv_heads"):
            GPTConfig(num_heads=4, num_kv_heads=0)
        with pytest.raises(ValueError, match="GQA"):
            GPTConfig(num_heads=4, num_kv_heads=2, tensor_parallel=True,
                      dropout=0.0)


def test_combined_serving_knobs_window_gqa_int8():
    """The serving knobs compose: sliding-window + GQA + int8 KV cache in
    one model — decode must still match the cache-free forward exactly
    (f32) and run finite with the quantized cache."""
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                    num_heads=4, max_seq_len=64, dropout=0.0,
                    num_kv_heads=2, attention_window=8)
    m = GPTForCausalLM(cfg)
    m.eval()
    ids = paddle.to_tensor(
        np.random.RandomState(5).randint(0, 128, (2, 12)).astype(np.int32))
    cur = _reference_greedy(m, np.asarray(ids._data), 8)
    gen = np.asarray(m.generate(ids, max_new_tokens=8,
                                temperature=0.0)._data)
    np.testing.assert_array_equal(gen, cur)
    i8 = np.asarray(m.generate(ids, max_new_tokens=8, temperature=0.0,
                               cache_dtype="int8")._data)
    assert i8.shape == gen.shape
    agree = (i8[:, 12:] == gen[:, 12:]).mean()
    assert agree > 0.5


def _update_slice_a_row(cache_i, val, pos_vec):
    """The per-row store `_row_update` replaced, kept as these tests' own
    reference: a vmap of dynamic_update_slice over the rows."""
    import jax

    return jax.vmap(lambda row, v, p_: jax.lax.dynamic_update_slice(
        row, v, (0, p_, 0)))(cache_i, val, pos_vec)


def _bits(tree):
    import jax
    import jax.numpy as jnp

    return [np.asarray(jax.lax.bitcast_convert_type(
        leaf, {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[
            leaf.dtype.itemsize])) for leaf in jax.tree_util.tree_leaves(tree)]


class TestPerRowStore:
    """fwd with per-row positions (the serving engine's decode and verify
    steps) stores, bit for bit, what the vmap of dynamic_update_slice
    stored: by the select every platform takes, and by the in-place kernel
    a TPU takes (ops/kv_store.py), here in interpret mode."""

    T = 128

    def _model(self, kv_heads):
        paddle.seed(0)
        m = GPTForCausalLM(GPTConfig(
            vocab_size=128, hidden_size=128, num_layers=2, num_heads=4,
            num_kv_heads=kv_heads, max_seq_len=self.T, dropout=0.0))
        m.eval()
        return m

    @staticmethod
    def _take_the_kernel(monkeypatch):
        """Returns the list the kernel's calls are noted in."""
        from paddle_tpu.ops import kv_store

        calls, real = [], kv_store.store_columns

        def noted(leaf, *a, **kw):
            calls.append(leaf.dtype)
            return real(leaf, *a, **kw)

        # off a TPU the kernel interprets: only the platform test is skipped
        monkeypatch.setattr(kv_store, "in_place", kv_store.fits)
        monkeypatch.setattr(kv_store, "store_columns", noted)
        return calls

    @pytest.mark.parametrize("store,t", [("select", 1), ("select", 3),
                                         ("kernel", 1)])
    @pytest.mark.parametrize("kv_heads", [4, 2], ids=["mha", "gqa"])
    @pytest.mark.parametrize("cache_dtype", [None, "int8", "fp8"],
                             ids=["bf16", "int8", "fp8"])
    def test_fwd_stores_what_update_slice_stored(self, monkeypatch,
                                                 cache_dtype, kv_heads,
                                                 store, t):
        import jax
        import jax.numpy as jnp

        from paddle_tpu.models import gpt
        from paddle_tpu.ops import kv_store

        model = self._model(kv_heads)
        _, _, params = gpt._decode_params(model, "the model")
        params = {n: v.astype(jnp.bfloat16) for n, v in params.items()}
        fwd, _, cache_init = gpt._decode_fns(model.cfg, False, False,
                                             cache_dtype=cache_dtype)

        def junk(leaf, n):
            # a full cache, so a store in the wrong place shows
            k = jax.random.PRNGKey(n)
            if leaf.dtype == jnp.int8:
                return jax.random.randint(k, leaf.shape, -127, 128,
                                          jnp.int8)
            return jax.random.uniform(k, leaf.shape, jnp.float32, 0.5,
                                      1.5).astype(leaf.dtype)

        leaves, tree = jax.tree_util.tree_flatten(
            cache_init(4, self.T, jnp.bfloat16))
        kc, vc = jax.tree_util.tree_unflatten(
            tree, [junk(leaf, n) for n, leaf in enumerate(leaves)])
        toks = jnp.asarray(np.random.RandomState(3).randint(
            0, 128, (4, t)).astype(np.int32))
        pos = jnp.asarray([0, self.T - t, 5, 77], jnp.int32)

        calls = self._take_the_kernel(monkeypatch) \
            if store == "kernel" else None
        x, *got = fwd(params, toks, pos, kc, vc)
        assert calls is None or len(calls) == 4      # K, V of two layers
        monkeypatch.setattr(gpt, "_row_update", _update_slice_a_row)
        monkeypatch.setattr(kv_store, "in_place", lambda leaf, val: False)
        x_ref, *want = fwd(params, toks, pos, kc, vc)

        for g, w in zip(_bits(got), _bits(want)):
            np.testing.assert_array_equal(g, w)
        for g, before in zip(_bits(got), _bits((kc, vc))):
            assert (g != before).any()       # and something was stored
        np.testing.assert_array_equal(_bits(x)[0], _bits(x_ref)[0])

    @pytest.mark.parametrize("store,t", [("select", 1), ("select", 3),
                                         ("kernel", 1)])
    def test_a_start_out_of_range_is_clamped_as_update_slice_clamps(
            self, store, t):
        """An idle row's stale position must land where it always did,
        never outside the cache."""
        import jax
        import jax.numpy as jnp

        from paddle_tpu.models import gpt
        from paddle_tpu.ops import kv_store

        layer = jax.random.normal(jax.random.PRNGKey(0),
                                  (2, 4, 3, self.T, 16), jnp.float32)
        val = jax.random.normal(jax.random.PRNGKey(1), (4, 3, t, 16),
                                jnp.float32)
        pos = jnp.asarray([self.T - t + 1, self.T + 5, self.T - 1, 0],
                          jnp.int32)
        want = layer.at[1].set(_update_slice_a_row(layer[1], val, pos))
        if store == "kernel":
            assert kv_store.fits(layer, val)
            got = kv_store.store_columns(layer, val, 1, pos, interpret=True)
        else:
            got = layer.at[1].set(gpt._row_update(layer[1], val, pos))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("store", ["select", "kernel"])
    @pytest.mark.parametrize("cache_dtype", [None, "int8", "fp8"],
                             ids=["f32", "int8", "fp8"])
    def test_engine_emits_what_generate_emits(self, monkeypatch,
                                              cache_dtype, store):
        from paddle_tpu.inference.serving import ServingEngine

        calls = self._take_the_kernel(monkeypatch) \
            if store == "kernel" else None
        model = self._model(4)
        eng = ServingEngine(model, max_batch=3, cache_dtype=cache_dtype)
        rng = np.random.RandomState(11)
        prompts = [rng.randint(0, 128, (n,)).astype(np.int32)
                   for n in (5, 33, 9, 70, 17)]
        rids = [eng.submit(p, max_new_tokens=10) for p in prompts]
        res = eng.run_until_complete()
        for rid, p in zip(rids, prompts):
            want = np.asarray(model.generate(
                paddle.to_tensor(p[None]), max_new_tokens=10,
                temperature=0.0, cache_dtype=cache_dtype)._data)[0, len(p):]
            np.testing.assert_array_equal(res[rid].tokens, want)
        assert calls is None or calls


def _store_then_read(kleaf, vleaf, q, k_new, v_new, i, pos):
    """`decode_attention_store` as the two kernels it took the place of."""
    from paddle_tpu.ops import decode_attention as da
    from paddle_tpu.ops import kv_store

    kleaf = kv_store.store_columns(kleaf, k_new, i, pos)
    vleaf = kv_store.store_columns(vleaf, v_new, i, pos)
    return da.decode_attention(kleaf, vleaf, q, i, pos), kleaf, vleaf


class TestStoreInTheRead:
    """Where a decode step's attention is the live-tile kernel, the kernel
    stores the step's keys and values itself
    (ops/decode_attention.py decode_attention_store): the cache it returns
    is `kv_store.store_columns`' bit for bit, its result `decode_attention`'s
    after that store, and `block` calls no store of its own. Interpret mode."""

    @pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
    @pytest.mark.parametrize("hd", [32, 64])
    @pytest.mark.parametrize("pos", [
        [0, 0, 0, 0], [127, 127, 127, 127], [128, 128, 128, 128],
        [383, 383, 383, 383], [4383, 384, 1 << 30, 383],
        [0, 127, 128, 383], [300, 5, 200, 129]],
        ids=["first", "tile_end", "tile_start", "last", "stale", "edges",
             "rows_at_different_tiles"])
    def test_the_store_then_the_read_bit_for_bit(self, pos, hd, dtype):
        import jax
        import jax.numpy as jnp

        from paddle_tpu.ops import decode_attention as da
        from paddle_tpu.ops import kv_store

        t_max, kvh = 384, 4
        ks = jax.random.split(jax.random.PRNGKey(hd), 5)
        dt = jnp.dtype(dtype)
        kc, vc = (jax.random.normal(k, (3, 4, kvh, t_max, hd),
                                    jnp.float32).astype(dt) for k in ks[:2])
        q, k_new, v_new = (jax.random.normal(k, (4, kvh, 1, hd),
                                             jnp.float32).astype(dt)
                           for k in ks[2:])
        pos = jnp.asarray(pos, jnp.int32)
        assert da.fits(kc, q)
        out, k_got, v_got = da.decode_attention_store(
            kc, vc, q, k_new, v_new, 1, pos, interpret=True)
        k_want = kv_store.store_columns(kc, k_new, 1, pos, interpret=True)
        v_want = kv_store.store_columns(vc, v_new, 1, pos, interpret=True)
        for got, want, before in ((k_got, k_want, kc), (v_got, v_want, vc)):
            np.testing.assert_array_equal(_bits(got)[0], _bits(want)[0])
            assert (_bits(got)[0] != _bits(before)[0]).any()
        # ... which is the column a per-row dynamic_update_slice stores
        np.testing.assert_array_equal(
            _bits(k_got[1])[0],
            _bits(_update_slice_a_row(kc[1], k_new, pos))[0])
        want = da.decode_attention(k_want, v_want, q, 1, pos, interpret=True)
        assert out.shape == q.shape and out.dtype == q.dtype
        np.testing.assert_array_equal(_bits(out)[0], _bits(want)[0])

    @staticmethod
    def _as_on_a_chip(monkeypatch):
        """The platform tests of the read and of the store skipped, as on a
        TPU (off one the kernels interpret); returns the calls noted, by
        name: the kernel that stores, the store's own, the select."""
        from paddle_tpu.models import gpt
        from paddle_tpu.ops import decode_attention as da
        from paddle_tpu.ops import kv_store

        calls = {"decode_attention_store": 0, "store_columns": 0,
                 "_row_update": 0}

        def noting(mod, name):
            real = getattr(mod, name)

            def noted(*a, **kw):
                calls[name] += 1
                return real(*a, **kw)

            monkeypatch.setattr(mod, name, noted)

        monkeypatch.setattr(da, "live_only", da.fits)
        monkeypatch.setattr(kv_store, "in_place", kv_store.fits)
        noting(da, "decode_attention_store")
        noting(kv_store, "store_columns")
        noting(gpt, "_row_update")
        return calls

    @pytest.mark.parametrize("case,stores", [
        ("decode_step", {"decode_attention_store": 2}),
        # values by the store's kernel, scales by the select
        ("int8", {"store_columns": 4, "_row_update": 4}),
        ("gqa", {"store_columns": 4}),
        ("several_columns", {"_row_update": 4}),
        ("window", {"store_columns": 4})])
    def test_block_stores_once_by_the_kernel_or_as_before(
            self, monkeypatch, case, stores):
        """K and V of two layers: by the attention's kernel and no other
        call where `_live_tile` holds, by `_store` everywhere else."""
        import jax.numpy as jnp

        from paddle_tpu.models import gpt

        paddle.seed(0)
        cfg = {"gqa": {"num_kv_heads": 2},
               "window": {"attention_window": 64}}.get(case, {})
        model = GPTForCausalLM(GPTConfig(
            vocab_size=128, hidden_size=128, num_layers=2, num_heads=4,
            max_seq_len=256, dropout=0.0, **cfg))
        model.eval()
        _, _, params = gpt._decode_params(model, "the model")
        params = {n: v.astype(jnp.bfloat16) for n, v in params.items()}
        fwd, _, cache_init = gpt._decode_fns(
            model.cfg, False, False,
            cache_dtype="int8" if case == "int8" else None)
        kc, vc = cache_init(4, 256, jnp.bfloat16)
        toks = jnp.asarray(np.random.RandomState(3).randint(
            0, 128, (4, 3 if case == "several_columns" else 1)), jnp.int32)
        pos = jnp.asarray([0, 127, 128, 199], jnp.int32)

        calls = self._as_on_a_chip(monkeypatch)
        x, *got = fwd(params, toks, pos, kc, vc)
        assert {n: c for n, c in calls.items() if c} == stores
        # the same cache, by whichever store, and something stored
        monkeypatch.undo()
        if case == "decode_step":       # the same read, after its own store
            from paddle_tpu.ops import decode_attention as da

            self._as_on_a_chip(monkeypatch)
            monkeypatch.setattr(da, "decode_attention_store",
                                _store_then_read)
        x_ref, *want = fwd(params, toks, pos, kc, vc)
        np.testing.assert_array_equal(_bits(x)[0], _bits(x_ref)[0])
        for g, w, before in zip(_bits(got), _bits(want), _bits((kc, vc))):
            np.testing.assert_array_equal(g, w)
            assert (g != before).any()

    def test_engine_emits_what_the_store_then_the_read_emit(self,
                                                            monkeypatch):
        """An engine whose steps store through the attention's kernel
        emits the tokens of one whose steps call the store, then the
        read."""
        from paddle_tpu.inference.serving import ServingEngine
        from paddle_tpu.ops import decode_attention as da

        def tokens(fused):
            calls = self._as_on_a_chip(monkeypatch)
            if not fused:
                monkeypatch.setattr(da, "decode_attention_store",
                                    _store_then_read)
            paddle.seed(0)
            model = GPTForCausalLM(GPTConfig(
                vocab_size=128, hidden_size=128, num_layers=2, num_heads=4,
                max_seq_len=256, dropout=0.0))
            model.eval()
            eng = ServingEngine(model, max_batch=3, dtype="bfloat16")
            rng = np.random.RandomState(11)
            prompts = [rng.randint(0, 128, (n,)).astype(np.int32)
                       for n in (5, 120, 9, 150, 127)]
            rids = [eng.submit(p, max_new_tokens=12) for p in prompts]
            res = eng.run_until_complete()
            monkeypatch.undo()
            return [res[r].tokens for r in rids], calls

        got, calls = tokens(fused=True)
        assert calls["decode_attention_store"] and not calls["store_columns"]
        want, calls = tokens(fused=False)
        assert calls["store_columns"] and not calls["decode_attention_store"]
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)
