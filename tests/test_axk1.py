"""The axk1 family on the CPU at a small size (hidden 64, 4 heads of 16 + 8
rotary / 16 value channels, query rank 24, latent rank 32, one dense layer of
width 160 and two expert layers of 16 experts top-4, vocabulary 256; float32
program): YaRN's frequencies, the two forms of the latent attention, the
router without a bias, the 16 shares of an expert layer, and the serving
engine's latent cache, each against the benchmark's plain reference
(benchmark/reference/axk1.py: the naive form, no cache, a dense loop over the
held experts) or against hand-worked numbers.

Tolerances: program and reference are both float32 here and differ in the
order of their sums alone (a running softmax over key blocks against one
softmax, the absorbed against the naive form, sorted tiles against a dense
loop over experts, a cache against a full forward). Logits are of order 1;
2e-4 absolute is some fifty times what those orders cost here (measured 2e-6
to 6e-6) and two orders under what one bfloat16 rounding of an activation
would show (1e-2).
"""
import functools
import math
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TOL = 2e-4
YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 32, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
CFG = {
    "model_type": "axk1", "hidden_size": 64, "intermediate_size": 160,
    "moe_intermediate_size": 32, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "num_attention_heads": 4, "q_lora_rank": 24,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "n_routed_experts": 16, "n_shared_experts": 1,
    "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "num_experts_per_tok": 4, "scoring_func": "sigmoid",
    "topk_method": "none", "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": dict(YARN, original_max_position_embeddings=64),
    "vocab_size": 256,
    "assumed": {"init_std": 0.08, "head_init_std": 0.1},
}
SEED = 11


@functools.lru_cache(maxsize=None)
def _model(max_seq_len=256):
    from benchmark import latent_weights
    from benchmark.runners.serve_latent import program_config
    from paddle_tpu.models import AXK1ForCausalLM

    return AXK1ForCausalLM(program_config(CFG, max_seq_len),
                           initializer=latent_weights.initializer(CFG, SEED))


@pytest.fixture(scope="module")
def ref():
    """(P, D, logits(ids) -> [s, V]) of the plain reference."""
    import jax.numpy as jnp

    from benchmark import latent_weights
    from benchmark.reference import axk1 as reference

    P = latent_weights.flat(CFG, SEED)
    D = reference.dims_of(CFG)

    def logits(ids):
        return np.asarray(reference.sequence_logits(
            P, jnp.asarray(np.asarray(ids, np.int32)), D))

    return P, D, logits


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, (n,)).astype(np.int32)


def _fns(m):
    from paddle_tpu.models.axk1 import _decode_fns

    return _decode_fns(m.cfg), {n: t._data for n, t in m.named_parameters()}


# -- the positional term ---------------------------------------------------------
def test_yarn_frequencies_and_scale_by_hand():
    """A.X-K1's published rope_scaling over 64 rotary channels, each number
    worked by hand from the formulas (ISSUE 36)."""
    from benchmark.reference import axk1 as reference
    from paddle_tpu.ops import rope

    # 64 ln(4096 / (2 pi 32)) / (2 ln 10000) = 10.47; with 1 turn 22.51
    assert rope.correction_range(32, 1, 64, 10000.0, 4096) == (10, 23)
    f = rope.yarn_inv_freq(64, 10000.0, YARN)
    plain = 10000.0 ** (-2 * np.arange(32) / 64)
    assert f.shape == (32,)
    np.testing.assert_allclose(f[:11], plain[:11], rtol=1e-12)   # kept
    np.testing.assert_allclose(f[23:], plain[23:] / 32, rtol=1e-12)
    # pair 16, between: mask = 1 - 6/13
    mask = 1 - (16 - 10) / 13
    assert f[16] == pytest.approx(plain[16] / 32 * (1 - mask)
                                  + plain[16] * mask, rel=1e-12)
    assert f[16] == pytest.approx(0.01 * (mask + (1 - mask) / 32), rel=1e-9)
    assert np.all(np.diff(f) < 0)
    assert rope.mscale(32, 1) == pytest.approx(0.1 * math.log(32) + 1)
    assert rope.mscale(32, 1) == pytest.approx(1.34657, abs=1e-5)
    assert rope.cos_sin_scale(YARN) == 1.0
    assert rope.softmax_scale(192, YARN) == pytest.approx(0.130861, abs=1e-6)
    assert rope.softmax_scale(192, None) == pytest.approx(192 ** -0.5)
    assert rope.mscale(1.0, 1) == 1.0
    np.testing.assert_allclose(rope.yarn_inv_freq(64, 10000.0, None), plain)
    with pytest.raises(ValueError, match="yarn"):
        rope.yarn_inv_freq(64, 10000.0, {"type": "linear", "factor": 2})
    # the reference has its own copy of the equations: the same numbers
    full = dict(CFG, qk_rope_head_dim=64, qk_nope_head_dim=128,
                rope_scaling=YARN)
    D = reference.dims_of(full)
    np.testing.assert_allclose(np.asarray(reference.inv_freq(D)), f,
                               rtol=2e-6)
    assert reference.softmax_scale(D) == pytest.approx(0.130861, abs=1e-6)


def test_rotation_keeps_norms_and_depends_on_the_distance_alone():
    import jax.numpy as jnp

    from paddle_tpu.ops import rope

    f = rope.yarn_inv_freq(8, 10000.0, None)
    rng = np.random.default_rng(0)
    q, k = rng.normal(size=(2, 8)).astype(np.float32)

    def dot(i, j):
        return float(jnp.dot(rope.rotate(q[None], np.array([i]), f)[0],
                             rope.rotate(k[None], np.array([j]), f)[0]))

    assert dot(5, 3) == pytest.approx(dot(105, 103), abs=1e-4)
    assert dot(5, 3) != pytest.approx(dot(5, 4), abs=1e-3)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(rope.rotate(q[None], np.array([77]), f))),
        np.linalg.norm(q), rtol=1e-6)
    # position 0 turns nothing; channel i pairs with channel i + dim / 2
    np.testing.assert_allclose(
        np.asarray(rope.rotate(q[None], np.array([0]), f))[0], q, rtol=1e-6)
    one = np.zeros((1, 8), np.float32)
    one[0, 0] = 1.0
    turned = np.asarray(rope.rotate(one, np.array([1]), f))[0]
    assert turned[0] == pytest.approx(math.cos(1.0), abs=1e-6)
    assert turned[4] == pytest.approx(math.sin(1.0), abs=1e-6)
    assert np.abs(np.delete(turned, [0, 4])).max() == 0


# -- the model -------------------------------------------------------------------
def test_layer_surface_and_the_leading_dense_layer():
    m = _model()
    names = [n for n, _ in m.named_parameters()]
    assert "layers.0.mlp.gate.weight" in names
    assert "layers.0.moe.router.weight" not in names
    assert "layers.1.moe.router.weight" in names
    assert not any(n.endswith("router.bias") for n in names)
    assert m.layers._sub_layers["1"].attn.kv_b.weight.shape == [32, 4 * 32]
    assert m.cfg.latent_width == 40 and m.cfg.cache_width == 128
    from paddle_tpu.models import AXK1Config

    full = AXK1Config()
    assert (full.latent_width, full.cache_width) == (576, 640)
    with pytest.raises(ValueError, match="topk_method"):
        AXK1Config(topk_method="noaux_tc")
    with pytest.raises(ValueError, match="held_experts"):
        AXK1Config(held_experts=(190, 12))


@pytest.mark.parametrize("length", [1, 63, 64, 300])
def test_whole_forward_matches_the_reference(ref, length):
    """300 tokens: the naive form's running softmax walks two key blocks of
    256 (with `_K_BLOCK` patched down) and pads the last."""
    from paddle_tpu.models import axk1

    _, _, logits = ref
    ids = _ids(length, seed=length)
    old = axk1._K_BLOCK
    axk1._K_BLOCK = 256 if length > 256 else old
    try:
        got = np.asarray(_model()(ids[None])._data)[0]
    finally:
        axk1._K_BLOCK = old
    want = logits(ids)
    assert got.shape == (length, 256)
    assert 0.3 < want.std() < 3
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


def test_absorbed_against_naive():
    """One layer's attention for the token at column 40 of two rows, through
    the absorbed form (t = 1, per-row positions) and through the naive form
    (the same token as a one-token chunk at offset 40): the same numbers up
    to the order of the sums. And W_UK really is absorbed: no key or value
    of a head is formed (the jaxpr of the step has no [.., T, H, dn + dv]
    value)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import axk1

    m = _model()
    (fwd, _, cache_init), p = _fns(m)
    ids = np.stack([_ids(41, 1), _ids(41, 2)])
    kv, rest = cache_init(2, 64, jnp.float32)
    _, kv, rest = fwd(p, jnp.asarray(ids[:, :40]), 0, kv, rest)
    tok = jnp.asarray(ids[:, 40:])
    x_abs, kv_abs, _ = fwd(p, tok, jnp.full((2,), 40, jnp.int32), kv, rest)
    x_naive, kv_naive, _ = fwd(p, tok, jnp.int32(40), kv, rest)
    np.testing.assert_allclose(np.asarray(x_abs), np.asarray(x_naive),
                               atol=TOL, rtol=0)
    np.testing.assert_allclose(np.asarray(kv_abs["latent"]),
                               np.asarray(kv_naive["latent"]), atol=TOL)
    # the cache row: c_kv | k_rope | zeros to the lane width
    row = np.asarray(kv_abs["latent"])[0, 0, 40]
    assert np.abs(row[:40]).min() > 0 and np.abs(row[40:]).max() == 0
    jaxpr = str(jax.make_jaxpr(
        lambda q, r, lat, w, at: axk1._attend_absorbed(q, r, lat, 0, w, at,
                                                       m.cfg))(
        jnp.zeros((2, 4, 16)), jnp.zeros((2, 4, 8)),
        jnp.zeros((1, 2, 64, 128)), jnp.zeros((32, 4 * 32)),
        jnp.zeros((2,), jnp.int32)))
    assert "f32[2,64,4,32]" not in jaxpr and "f32[2,64,128]" in jaxpr


def test_the_latent_kernel_is_the_einsums_over_live_tiles(monkeypatch):
    """ops/latent_decode_attention.py in interpret mode against the masked
    einsums over all T columns: rows that end on a tile's first and last
    column, a row of one column, junk past a row's position changing
    nothing; then the model's own step with the kernel taken (as on a chip)
    and refused: the same hidden states, and the adapter's `kv_read_tile`
    follows the choice."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import axk1
    from paddle_tpu.ops import latent_decode_attention as lda
    from paddle_tpu.serving import decode_model as dm

    monkeypatch.setattr(lda, "TILE", 128)
    rng = np.random.default_rng(0)
    L, B, T, W, H = 2, 5, 512, 128, 16
    lat = jnp.asarray(rng.normal(size=(L, B, T, W)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, H, W)), jnp.float32)
    pos = jnp.asarray([0, 127, 128, 300, 511], jnp.int32)
    assert lda.fits(lat, q)
    got = lda.latent_decode_attention(lat, q, 1, pos, 0.1, interpret=True)
    s = jnp.einsum("bhc,bsc->bhs", q, lat[1]) * 0.1
    live = jnp.arange(T)[None, None] <= pos[:, None, None]
    want = jnp.einsum("bhs,bsc->bhc",
                      jax.nn.softmax(jnp.where(live, s, -jnp.inf), -1),
                      lat[1])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    junk = jnp.where(live[:, 0, :, None], lat[1], 1e9)
    again = lda.latent_decode_attention(lat.at[1].set(junk), q, 1, pos, 0.1,
                                        interpret=True)
    np.testing.assert_array_equal(np.asarray(again), np.asarray(got))
    # what it refuses: a width not in whole lanes, T not in whole tiles,
    # heads not in whole sublane tiles, mixed dtypes
    assert not lda.fits(lat[..., :100], q[..., :100])
    assert not lda.fits(lat[:, :, :500], q)
    assert not lda.fits(lat, q[:, :5])
    assert not lda.fits(lat.astype(jnp.bfloat16), q)

    # the model's step: H = 4 heads is half a sublane tile, so a model of 8
    m = _model()
    cfg8 = axk1.AXK1Config(**{**{k: getattr(m.cfg, k) for k in (
        "vocab_size", "hidden_size", "intermediate_size", "q_lora_rank",
        "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "n_routed_experts", "num_experts_per_tok", "rope_scaling")},
        "moe_intermediate_size": 32, "num_hidden_layers": 2,
        "num_attention_heads": 8, "max_seq_len": 256})
    m8 = axk1.AXK1ForCausalLM(cfg8)
    (fwd, _, cache_init), p = _fns(m8)
    kv, rest = cache_init(3, 256, jnp.float32)
    warm = jnp.asarray(np.stack([_ids(200, s) for s in (1, 2, 3)]))
    _, kv, rest = fwd(p, warm, 0, kv, rest)
    toks = jnp.asarray([[3], [9], [27]], jnp.int32)
    at = jnp.asarray([127, 128, 199], jnp.int32)
    adapter = dm.resolve(m8)
    side = jax.eval_shape(lambda: cache_init(3, 256, jnp.float32))[0]
    assert adapter.kv_read_tile(cfg8, side, jnp.float32) is None
    x_ref, *_ = fwd(p, toks, at, kv, rest)
    calls = []
    real = lda.latent_decode_attention
    monkeypatch.setattr(lda, "live_only", lda.fits)
    monkeypatch.setattr(lda, "latent_decode_attention",
                        lambda lat, *a, **kw: calls.append(lat.shape)
                        or real(lat, *a, **kw))
    x, *_ = fwd(p, toks, at, kv, rest)
    assert calls == [(2, 3, 256, 128)] * 2                  # both layers
    np.testing.assert_allclose(np.asarray(x), np.asarray(x_ref), atol=TOL,
                               rtol=0)
    assert adapter.kv_read_tile(cfg8, side, jnp.float32) == 128
    fwd(p, warm[:, :64], jnp.int32(0), kv, rest)            # a chunk: naive
    assert len(calls) == 2


def _small_prefill_kernel(monkeypatch):
    """ops/latent_prefill_attention.py at the test model's size: lanes of
    8, key tiles of 16 columns, strips of 8 queries, groups of 2 heads."""
    from paddle_tpu.ops import latent_prefill_attention as lpa

    for name, value in (("LANE", 8), ("TILE", 16), ("STRIP", 8),
                        ("GROUP", 2)):
        monkeypatch.setattr(lpa, name, value)
    return lpa


_PREFILL_CASES = {
    # name: (t, S, offset, rows) of the kernel against the einsum loop
    "offset 0, a whole prompt": (32, 32, 0, 1),
    "offset inside a tile": (16, 64, 5, 1),
    "offset on a tile's first column": (16, 64, 16, 1),
    "offset on a tile's last column": (16, 64, 15, 1),
    "several tiles live": (32, 96, 40, 1),
    "live columns to the row's end": (16, 64, 48, 1),
    "two rows of a batch": (16, 64, 21, 2),
}
_PREFILL_REFUSED = {
    # name: what `fits` is handed instead of the good call
    "channels not in whole lanes": lambda qn, qr, lat, w: (
        qn[..., :12], qr, lat, w[:, :4 * 28]),
    "queries not in whole strips": lambda qn, qr, lat, w: (
        qn[:, :12], qr[:, :12], lat, w),
    "more queries than fast memory holds": lambda qn, qr, lat, w: (
        *(_rows(a, 1032) for a in (qn, qr)), lat, w),
    "columns not in whole tiles": lambda qn, qr, lat, w: (
        qn, qr, lat[:, :, :40], w),
    "heads not in whole groups": lambda qn, qr, lat, w: (
        qn[:, :, :3], qr[:, :, :3], lat, w[:, :3 * 32]),
    "the rotary part past the cache's width": lambda qn, qr, lat, w: (
        qn, qr, lat[..., :32], w),
    "mixed dtypes": lambda qn, qr, lat, w: (
        qn, qr, lat.astype("bfloat16"), w),
}


def _rows(a, rows):
    """a [B, t, ...] repeated along t to `rows` rows (whole strips)."""
    import jax.numpy as jnp

    return jnp.resize(a, (a.shape[0], rows) + a.shape[2:])


@pytest.mark.parametrize("case", list(_PREFILL_CASES) + [
    "junk past the live columns changes nothing",
    "a query that sees one column", "the model's chunk and whole prompt"]
    + [f"refused: {k}" for k in _PREFILL_REFUSED])
def test_the_prefill_kernel_is_the_einsum_loop(monkeypatch, case):
    """ops/latent_prefill_attention.py in interpret mode against
    `_attend_naive`'s einsum loop (float32 both: the order of the sums
    alone): a whole prompt, offsets inside a tile and on its first and last
    column, several tiles live, junk past the live columns, a query that
    sees one column; the model's chunk and whole prompt with the kernel
    taken (as on a chip) and refused: the same hidden states, one call a
    layer; and one case for each thing `fits` refuses."""
    import jax.numpy as jnp

    from paddle_tpu.models import axk1

    lpa = _small_prefill_kernel(monkeypatch)
    rng = np.random.default_rng(abs(hash(case)) % 2**31)
    m = _model()
    cfg = m.cfg
    H, r = cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    W = cfg.cache_width

    def call(t, S, B=1):
        qn = jnp.asarray(rng.normal(size=(B, t, H, dn)), jnp.float32)
        qr = jnp.asarray(rng.normal(size=(B, t, H, dr)), jnp.float32)
        lat = rng.normal(size=(2, B, S, W)).astype(np.float32)
        lat[..., r + dr:] = 0                   # two layers: the second read
        w = jnp.asarray(0.3 * rng.normal(size=(r, H * (dn + dv))),
                        jnp.float32)
        return qn, qr, jnp.asarray(lat), w

    def both(args, offset):
        qn, qr, lat, w = args
        got = lpa.latent_prefill_attention(qn, qr, lat, 1, w, offset,
                                           cfg.softmax_scale, interpret=True)
        monkeypatch.setattr(axk1, "_K_BLOCK", 32)   # blocks of another size
        want = axk1._attend_naive(qn, qr, lat, 1, w,
                                  jnp.int32(offset) if offset else 0, cfg)
        return np.asarray(got), np.asarray(want)

    if case in _PREFILL_CASES:
        t, S, offset, B = _PREFILL_CASES[case]
        args = call(t, S, B)
        assert lpa.fits(*args)
        got, want = both(args, offset)
        assert got.shape == (B, t, H, dv)
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)
    elif case.startswith("junk"):
        qn, qr, lat, w = call(16, 64)
        got = lpa.latent_prefill_attention(qn, qr, lat, 1, w, 20,
                                           cfg.softmax_scale, interpret=True)
        lat = lat.at[1, :, 36:].set(1e9)      # past column 20 + 16 - 1
        lat = lat.at[0].set(1e9)              # and the other layer
        again = lpa.latent_prefill_attention(qn, qr, lat, 1, w, 20,
                                             cfg.softmax_scale,
                                             interpret=True)
        np.testing.assert_array_equal(np.asarray(again), np.asarray(got))
    elif case.startswith("a query that sees"):
        qn, qr, lat, w = call(16, 32)
        got = lpa.latent_prefill_attention(qn, qr, lat, 0, w, 0,
                                           cfg.softmax_scale, interpret=True)
        v0 = (lat[0, 0, 0, :r] @ w).reshape(H, dn + dv)[:, dn:]
        np.testing.assert_allclose(np.asarray(got)[0, 0], np.asarray(v0),
                                   atol=1e-5, rtol=0)
    elif case.startswith("the model"):
        (fwd, _, cache_init), p = _fns(m)
        ids = _ids(48, seed=5)
        kv, rest = cache_init(1, 256, jnp.float32)
        _, kv, rest = fwd(p, jnp.asarray(ids[None, :32]), 0, kv, rest)
        chunk = jnp.asarray(ids[None, 32:48])
        want_chunk, want_kv, _ = fwd(p, chunk, jnp.int32(32), kv, rest)
        want_whole, *_ = fwd(p, jnp.asarray(ids[None, :32]), 0,
                             *cache_init(1, 256, jnp.float32))
        calls = []
        real = lpa.latent_prefill_attention
        monkeypatch.setattr(lpa, "live_only", lpa.fits)
        monkeypatch.setattr(lpa, "latent_prefill_attention",
                            lambda *a, **kw: calls.append(a[2].shape)
                            or real(*a, **kw))
        got_chunk, got_kv, _ = fwd(p, chunk, jnp.int32(32), kv, rest)
        L = cfg.num_layers
        assert calls == [(L, 1, 256, W)] * L                # one a layer
        np.testing.assert_allclose(np.asarray(got_chunk),
                                   np.asarray(want_chunk), atol=TOL, rtol=0)
        np.testing.assert_allclose(np.asarray(got_kv["latent"]),
                                   np.asarray(want_kv["latent"]), atol=TOL,
                                   rtol=0)
        got_whole, *_ = fwd(p, jnp.asarray(ids[None, :32]), 0,
                            *cache_init(1, 256, jnp.float32))
        assert calls[L:] == [(1, 1, 32, W)] * L
        np.testing.assert_allclose(np.asarray(got_whole),
                                   np.asarray(want_whole), atol=TOL, rtol=0)
    else:
        good = call(16, 64)
        assert lpa.fits(*good)
        assert not lpa.fits(*_PREFILL_REFUSED[case[len("refused: "):]](
            *good))


def test_the_router_takes_no_bias():
    """`select_bias=None`: the k largest scores themselves, weights 2.5 s /
    sum s; a bias of zeros gives the same experts and weights."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.distributed import moe

    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(12, 64)), jnp.float32)
    w = jnp.asarray(rng.normal(0, 0.1, (64, 16)), jnp.float32)
    e, g = moe.sigmoid_topk_routing(x, w, None, 4, True, 2.5)
    s = np.asarray(jax.nn.sigmoid(x @ w))
    want = np.argsort(-s, axis=-1)[:, :4]
    np.testing.assert_array_equal(np.sort(np.asarray(e), -1),
                                  np.sort(want, -1))
    np.testing.assert_allclose(np.asarray(g).sum(-1), 2.5, rtol=1e-6)
    top = np.take_along_axis(s, np.asarray(e), -1)
    np.testing.assert_allclose(np.asarray(g),
                               2.5 * top / top.sum(-1, keepdims=True),
                               rtol=1e-5)
    e0, g0 = moe.sigmoid_topk_routing(x, w, jnp.zeros((16,)), 4, True, 2.5)
    np.testing.assert_array_equal(np.asarray(e), np.asarray(e0))
    np.testing.assert_array_equal(np.asarray(g), np.asarray(g0))
    wg, wu = (jnp.asarray(rng.normal(0, 0.1, (16, 64, 32)), jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.normal(0, 0.1, (16, 32, 64)), jnp.float32)
    y, _ = moe.moe_dropless_layer(x, w, None, wg, wu, wd, 4, scale=2.5)
    y0, _ = moe.moe_dropless_layer(x, w, jnp.zeros((16,)), wg, wu, wd, 4,
                                   scale=2.5)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y0))


def test_the_shares_add_up(ref):
    """The 16 shares of one held expert each (a 16-way deployment of this
    size), their routed parts plus the shared expert ONCE, add up to what
    the uncut layer gives: in the program's layer and in the reference's."""
    import jax.numpy as jnp

    from benchmark.reference import axk1 as reference
    from paddle_tpu.distributed import moe

    P, D, _ = ref
    pre = "layers.1.moe."
    h = jnp.asarray(np.random.default_rng(5).normal(size=(48, 64)),
                    jnp.float32)
    shared = tuple(P[pre + f"shared.{n}.weight"]
                   for n in ("gate", "up", "down"))
    whole_ref = np.asarray(reference.moe_layer(P, pre, h, D))

    def program(first, count, with_shared):
        y, counts = moe.moe_dropless_layer(
            h, P[pre + "router.weight"], None,
            P[pre + "experts.gate"][first:first + count],
            P[pre + "experts.up"][first:first + count],
            P[pre + "experts.down"][first:first + count], D.top_k,
            shared=shared if with_shared else None, held=(first, count),
            normalize=True, scale=D.routed_scale)
        return np.asarray(y), counts

    whole, counts = program(0, 16, True)
    np.testing.assert_allclose(whole, whole_ref, atol=TOL, rtol=0)
    assert int(counts["assignments_held"]) == 48 * 4
    parts = [program(e, 1, False) for e in range(16)]
    total = sum(y for y, _ in parts) + np.asarray(moe.gated_mlp(h, *shared))
    np.testing.assert_allclose(total, whole, atol=TOL, rtol=0)
    assert sum(int(c["assignments_held"]) for _, c in parts) == 48 * 4
    # the reference's shares, the same way
    sub = {k: v for k, v in P.items() if k.startswith(pre)}
    ref_total = np.zeros_like(whole_ref)
    for e in range(16):
        part = dict(sub)
        for n in ("gate", "up", "down"):
            part[pre + "experts." + n] = sub[pre + "experts." + n][e:e + 1]
        ref_total += np.asarray(reference.moe_layer(
            part, pre, h, D._replace(held=(e, 1), shared=0)))
    ref_total += np.asarray(reference._mlp(h, *shared, "float32"))
    np.testing.assert_allclose(ref_total, whole_ref, atol=TOL, rtol=0)


# -- through the serving engine ----------------------------------------------------
def _engine(m, **kw):
    from paddle_tpu.inference.serving import ServingEngine

    kw.setdefault("prompt_buckets", (16, 32, 64, 128))
    return ServingEngine(m, max_batch=3, **kw)


@pytest.mark.parametrize("chunk", [None, 16])
def test_prefill_then_decode_through_the_cache(ref, chunk):
    """Prompts of three lengths admitted whole (bucketed) or in chunks of
    16 with other slots live, then greedy steps: at every served position
    the reference's logit of the served token is its best (float32 both
    sides: the gap is the order of the sums, under TOL), and the engine's
    cache row holds the latents the reference's layers give."""
    _, _, logits = ref
    eng = _engine(_model(), prefill_chunk=chunk)
    reqs = [(_ids(n, seed=n), new) for n, new in ((9, 6), (40, 9), (97, 5))]
    rids = [eng.submit(ids, max_new_tokens=new) for ids, new in reqs]
    eng.run_until_complete()
    for (ids, new), rid in zip(reqs, rids):
        out = list(eng.get_request(rid).output_ids)
        assert len(out) == new
        full = logits(np.concatenate([ids, out]))
        rows = full[len(ids) - 1: len(ids) + new - 1]
        gap = rows.max(-1) - rows[np.arange(new), out]
        assert gap.max() < TOL, gap
    st = eng.stats()
    assert st["steps"].get("prefill_chunk", 0) == (0 if chunk is None
                                                   else 1 + 3 + 7)
    assert set(st["state_bytes"]["held"]) == {"kv"}
    assert st["state_bytes"]["held"]["kv"] == 3 * 3 * 256 * 128 * 4
    assert st["kv_tiles_read"] == st["kv_tiles_held"] > 0
    assert st["moe_assignments"] > 0 and \
        0 < st["moe_assignments_held"] == st["moe_assignments"]
    # the dispatch gathers the rows walked or, where the tiles are small,
    # every padded row; the combine adds the rows walked back
    assert st["moe_rows_moved"] >= 2 * st["moe_rows_computed"] > 0
    assert st["lookahead"]["rounds"] > 0


def test_decode_logits_with_other_rows_live(ref):
    """Logits, not tokens: a row prefilled in chunks into a 3-slot cache
    whose other rows hold other requests, then one absorbed step a token:
    every step's logits against the reference's full forward."""
    import jax.numpy as jnp

    _, _, logits = ref
    m = _model()
    (fwd, logits_of, cache_init), p = _fns(m)
    kv, rest = cache_init(3, 128, jnp.float32)
    seqs = [_ids(n, seed=70 + n) for n in (50, 53, 61)]
    for r, ids in enumerate(seqs):            # rows admitted one by one
        row, rrest = cache_init(1, 128, jnp.float32)
        for off in range(0, 32, 16):          # two chunks of 16 at offsets
            _, row, rrest = fwd(p, jnp.asarray(ids[None, off:off + 16]),
                                jnp.int32(off), row, rrest)
        kv = {"latent": kv["latent"].at[:, r].set(row["latent"][:, 0])}
    pos = np.full((3,), 32, np.int32)
    for _ in range(18):
        toks = np.array([s[q] for s, q in zip(seqs, pos)], np.int32)
        x, kv, rest = fwd(p, jnp.asarray(toks[:, None]), jnp.asarray(pos),
                          kv, rest)
        got = np.asarray(logits_of(p, x[:, 0]))
        for r, ids in enumerate(seqs):
            want = logits(ids[:pos[r] + 1])[-1]
            np.testing.assert_allclose(got[r], want, atol=TOL, rtol=0)
        pos += 1


def test_chunked_against_whole_prompt_admission_and_a_handed_off_row():
    """The same requests through an engine that admits whole prompts and one
    that admits in chunks of 16 give the same tokens and the same cache
    row up to the order of the sums; a row prefilled by one engine is
    admitted by another (`admit_prefilled`, held to the described tree)."""
    import jax.numpy as jnp

    m = _model()
    whole, chunked = _engine(m), _engine(m, prefill_chunk=16)
    ids = _ids(45, seed=9)
    outs = []
    for eng in (whole, chunked):
        rid = eng.submit(ids, max_new_tokens=8)
        eng.run_until_complete()
        outs.append(list(eng.get_request(rid).output_ids))
    assert outs[0] == outs[1]
    a = np.asarray(whole._kc["latent"])[:, 0, :52]
    b = np.asarray(chunked._kc["latent"])[:, 0, :52]
    np.testing.assert_allclose(a, b, atol=TOL, rtol=0)
    # hand-off: the prefill of one engine admitted by another
    padded = np.zeros((1, 64), np.int32)
    padded[0, :45] = ids
    kc1, vc1, logits = whole._prefill(whole._params, jnp.asarray(padded),
                                      np.int32(45))
    decode = _engine(m)
    rid = decode.admit_prefilled(ids, (kc1, vc1), logits, max_new_tokens=8)
    decode.run_until_complete()
    assert list(decode.get_request(rid).output_ids) == outs[0]
    bad = ({"latent": kc1["latent"][:, :, :, :64]}, vc1)
    with pytest.raises(ValueError, match="hand-off row"):
        decode.admit_prefilled(ids, bad, logits, max_new_tokens=2)


def test_one_slot_prefills_in_chunks_at_a_time():
    """Three prompts of several chunks submitted at once: one slot prefills
    at a time (one side row held), every round carries at most one chunk,
    the queue's head waits for the last chunk of the one before it, and the
    tokens are those of whole-prompt admission."""
    m = _model()
    reqs = [(_ids(n, seed=n), 5) for n in (70, 45, 90)]
    outs = []
    for chunk in (None, 16):
        eng = _engine(m, prefill_chunk=chunk)
        rids = [eng.submit(ids, max_new_tokens=new) for ids, new in reqs]
        chunks = [0]
        while eng.has_work():
            eng.step()
            assert len(eng._prefilling) <= 1
            chunks.append(eng.stats()["steps"].get("prefill_chunk", 0))
        outs.append([list(eng.get_request(r).output_ids) for r in rids])
        if chunk:
            assert chunks[-1] == 5 + 3 + 6
            assert max(b - a for a, b in zip(chunks, chunks[1:])) == 1
    assert outs[0] == outs[1]


@pytest.mark.parametrize("kernel", [True, False])
def test_chunk_kv_tiles_counts_the_walked_tiles(monkeypatch, kernel):
    """A prompt of two chunks of 16 (columns 0..15, then 16..31; key tiles
    of 16): the kernel walks 1 then 2 tiles in each of 3 layers, and every
    `serve/prefill_chunk` phase notes its own; 0 where the einsum loop
    runs. The tokens are the same either way."""
    from paddle_tpu import trace

    lpa = _small_prefill_kernel(monkeypatch)
    if kernel:
        monkeypatch.setattr(lpa, "live_only", lpa.fits)
    eng = _engine(_model(), prefill_chunk=16)
    since = trace._now_ns()
    rid = eng.submit(_ids(20, seed=3), max_new_tokens=4)
    eng.run_until_complete()
    rows, _ = trace.phases(since)
    noted = [c["chunk_kv_tiles"] for name, *_, c in rows
             if name == "serve/prefill_chunk"]
    layers = eng.cfg.num_layers
    assert noted == ([layers * 1, layers * 2] if kernel else [0, 0])
    assert eng.stats()["chunk_kv_tiles"] == sum(noted)
    # the reference's tokens: the engine of the einsum loop's
    ref_eng = _engine(_model(), prefill_chunk=16)
    ref_rid = ref_eng.submit(_ids(20, seed=3), max_new_tokens=4)
    monkeypatch.undo()
    ref_eng.run_until_complete()
    assert list(eng.get_request(rid).output_ids) == \
        list(ref_eng.get_request(ref_rid).output_ids)


def test_a_slot_reused_by_a_shorter_request_is_a_fresh_slot(ref):
    _, _, logits = ref
    eng = _engine(_model())
    for n, new in ((90, 4), (12, 10)):
        ids = _ids(n, seed=n)
        rid = eng.submit(ids, max_new_tokens=new)
        eng.run_until_complete()
        out = list(eng.get_request(rid).output_ids)
        rows = logits(np.concatenate([ids, out]))[n - 1: n + new - 1]
        assert (rows.max(-1) - rows[np.arange(new), out]).max() < TOL


def test_both_expert_families_are_refused_each_by_name():
    """What the engine refuses follows what the adapter declares it serves
    (`not_served`), not the kinds of its cache leaves: the latent family has
    `kv` leaves only and is refused a paged pool all the same."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.serving import decode_model as dm
    from tests.test_solar_open2 import _model as solar_model

    models = {"axk1": _model(), "solar_open2": solar_model()}
    small = paddle.models.GPTForCausalLM(paddle.models.GPTConfig(
        vocab_size=256, hidden_size=32, num_layers=1, num_heads=2,
        max_seq_len=256, dropout=0.0))
    for name, m in models.items():
        adapter = dm.resolve(m)
        assert adapter.name == name
        assert set(adapter.not_served) == {"paged_kv", "draft_model",
                                           "tp_mesh", "lora", "cache_dtype"}
        for kw, word in ((dict(draft_model=small), "draft_model"),
                         (dict(cache_dtype="int8"), "cache_dtype"),
                         (dict(max_adapters=2), "max_adapters"),
                         (dict(tp_mesh=object()), "tp_mesh")):
            with pytest.raises(ValueError, match=name + ".*" + word):
                ServingEngine(m, max_batch=2, prompt_buckets=(16, 32), **kw)
        paddle.set_flags({"paged_kv": True})
        try:
            with pytest.raises(ValueError, match=name + ".*paged_kv"):
                ServingEngine(m, max_batch=2, prompt_buckets=(16, 32))
        finally:
            paddle.set_flags({"paged_kv": False})
    kinds = {name: {leaf["kind"] for leaf in
                    dm.state_leaves(dm.resolve(m).cache_spec(m.cfg))}
             for name, m in models.items()}
    assert kinds == {"axk1": {"kv"},
                     "solar_open2": {"kv", "recurrent", "conv"}}
    # an adapter that declares nothing is refused nothing here: GPT's
    assert dm.get_decode_model("gpt").not_served == {}
    assert "axk1" in dm.registered_decode_models()
