"""The decode step's length-aware attention (ops/decode_attention.py), off the
chip: the kernel in interpret mode against the masked einsums over all T
columns, which reads `block` chooses it for, and the engine's count of the
tiles it reads."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import GPTConfig, GPTForCausalLM, gpt
from paddle_tpu.ops import decode_attention as da


LIVE_ONLY = da.live_only      # before any test steers it


def _masked_einsums(kc, vc, q, i, pos):
    """What `block` computes for one query a row: every column, then the
    mask. In float32, so that each dtype's kernel is held to the same."""
    t_max, hd = kc.shape[3], q.shape[-1]
    f32 = jnp.float32
    att = jnp.einsum("bhtd,bhTd->bhtT", q.astype(f32),
                     kc[i].astype(f32)) / math.sqrt(hd)
    live = jnp.arange(t_max) <= jnp.clip(pos, 0, t_max - 1)[:, None]
    att = jax.nn.softmax(jnp.where(live[:, None, None], att, -jnp.inf), -1)
    return jnp.einsum("bhtT,bhTd->bhtd", att, vc[i].astype(f32))


@pytest.mark.parametrize("rows,kvh,g,hd,t_max,dtype", [
    (8, 20, 1, 64, 1024, jnp.bfloat16),      # gpt2-large
    (8, 5, 1, 64, 1024, jnp.bfloat16),       # its share of four chips
    (5, 4, 1, 32, 256, jnp.bfloat16),
    (6, 12, 1, 64, 384, jnp.float32),
    (3, 2, 1, 16, 128, jnp.bfloat16),        # one tile a row
    (4, 2, 2, 64, 256, jnp.bfloat16),        # grouped queries: the einsums
    (4, 8, 1, 128, 256, jnp.bfloat16),       # hd fills the lanes: the einsums
    (4, 4, 1, 64, 200, jnp.bfloat16),        # T no whole number of tiles
], ids=["gpt2_large", "tp_local_heads", "narrow", "f32", "one_tile",
        "gqa", "hd128", "ragged_T"])
def test_kernel_reads_what_the_masked_einsums_read(rows, kvh, g, hd, t_max,
                                                   dtype):
    ks = jax.random.split(jax.random.PRNGKey(rows * kvh), 3)
    shape = (3, rows, kvh, t_max, hd)
    kc = jax.random.normal(ks[0], shape, jnp.float32).astype(dtype)
    vc = jax.random.normal(ks[1], shape, jnp.float32).astype(dtype)
    q = jax.random.normal(ks[2], (rows, kvh * g, 1, hd),
                          jnp.float32).astype(dtype)
    if g > 1 or hd >= 128 or t_max % 128:
        assert not da.fits(kc, q)
        return
    assert da.fits(kc, q)
    # the first column, both sides of a tile's edge, the last column, and
    # an idle row's stale position beyond the cache
    pos = jnp.asarray(np.resize(np.array(
        [0, 127, 128, t_max - 1, t_max + 4000, 77, t_max // 2, 129],
        np.int32), rows))
    got = da.decode_attention(kc, vc, q, 1, pos, interpret=True)
    assert got.shape == q.shape and got.dtype == q.dtype
    want = _masked_einsums(kc, vc, q, 1, pos)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5   # bf16 p and result
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), atol=tol, rtol=tol)
    # columns past pos[b] are not read: junk there changes nothing
    dead = jnp.arange(t_max)[None, :] > jnp.clip(pos, 0, t_max - 1)[:, None]
    junk = jnp.where(dead[None, :, None, :, None], jnp.asarray(1e4, dtype),
                     kc)
    again = da.decode_attention(junk, junk + vc, q, 1, pos, interpret=True)
    live_v = jnp.where(dead[None, :, None, :, None], 0, junk + vc)
    np.testing.assert_allclose(
        np.asarray(again, np.float32),
        np.asarray(_masked_einsums(junk, live_v, q, 1, pos)),
        atol=tol * 4, rtol=tol)


def _tiny(**kw):
    paddle.seed(0)
    m = GPTForCausalLM(GPTConfig(
        vocab_size=128, hidden_size=128, num_layers=2, num_heads=4,
        max_seq_len=256, dropout=0.0, **kw))
    m.eval()
    return m


@pytest.fixture
def kernel_calls(monkeypatch):
    """Skips the choice's platform test, as on a chip (off one the kernel
    interprets), and notes the calls of the entry `block` takes: the one
    that stores the step's column too."""
    calls, real = [], da.decode_attention_store

    def noted(kleaf, *a, **kw):
        calls.append(kleaf.shape)
        return real(kleaf, *a, **kw)

    monkeypatch.setattr(da, "live_only", da.fits)
    monkeypatch.setattr(da, "decode_attention_store", noted)
    return calls


class TestBlockChooses:
    """`block` takes the kernel for one query a row at per-row positions
    over a plain cache on a TPU, and the einsums for everything else."""

    def _fwd(self, cache_dtype=None, **cfg):
        model = _tiny(**cfg)
        _, _, params = gpt._decode_params(model, "the model")
        params = {n: v.astype(jnp.bfloat16) for n, v in params.items()}
        fwd, _, cache_init = gpt._decode_fns(model.cfg, False, False,
                                             cache_dtype=cache_dtype)
        kc, vc = cache_init(4, 256, jnp.bfloat16)
        return fwd, params, kc, vc

    def test_the_kernel_for_a_decode_step_and_the_same_result(
            self, kernel_calls, monkeypatch):
        fwd, params, kc, vc = self._fwd()
        toks = jnp.asarray([[3], [9], [27], [81]], jnp.int32)
        warm = jnp.asarray(np.random.RandomState(0).randint(
            0, 128, (4, 200)).astype(np.int32))
        _, kc, vc = fwd(params, warm, 0, kc, vc)       # a prompt: einsums
        assert kernel_calls == []
        pos = jnp.asarray([0, 127, 128, 199], jnp.int32)
        x, *_ = fwd(params, toks, pos, kc, vc)
        assert kernel_calls == [(2, 4, 4, 256, 32)] * 2     # both layers
        monkeypatch.setattr(da, "live_only", lambda kc, q: False)
        x_ref, *_ = fwd(params, toks, pos, kc, vc)
        assert len(kernel_calls) == 2
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(x_ref, np.float32),
                                   atol=0.06, rtol=0.03)

    @pytest.mark.parametrize("case", ["several_columns", "scalar_pos",
                                      "window", "key_valid", "int8", "fp8",
                                      "gqa", "off_the_chip"])
    def test_the_einsums_otherwise(self, kernel_calls, monkeypatch, case):
        cfg = {"window": {"attention_window": 64},
               "gqa": {"num_kv_heads": 2}}.get(case, {})
        fwd, params, kc, vc = self._fwd(
            cache_dtype=case if case in ("int8", "fp8") else None, **cfg)
        toks = jnp.asarray([[3], [9], [27], [81]], jnp.int32)
        pos = jnp.asarray([0, 127, 128, 199], jnp.int32)
        kw = {}
        if case == "several_columns":
            toks = jnp.tile(toks, (1, 3))
        elif case == "scalar_pos":
            pos = 5
        elif case == "key_valid":
            kw["key_valid"] = jnp.ones((4, 256), bool)
        elif case == "off_the_chip":       # the choice as it ships
            monkeypatch.setattr(da, "live_only", LIVE_ONLY)
        x, *_ = fwd(params, toks, pos, kc, vc, **kw)
        assert kernel_calls == []
        assert np.isfinite(np.asarray(x, np.float32)).all()


class TestEngine:
    def test_greedy_tokens_are_generates_and_tiles_are_counted(
            self, kernel_calls):
        from paddle_tpu.inference.serving import ServingEngine

        model = _tiny()
        eng = ServingEngine(model, max_batch=3)
        assert eng._kv_tile == 128
        seen, real = [], eng._dispatch_decode

        def noted(active):
            # the step walks every row: a free one rides along at column 0
            assert all(eng._pos[s] == 0 for s in range(3)
                       if s not in active)
            seen.append(eng._pos.copy())
            return real(active)

        eng._dispatch_decode = noted
        rng = np.random.RandomState(11)
        prompts = [rng.randint(0, 128, (n,)).astype(np.int32)
                   for n in (5, 120, 9, 150, 127)]
        rids = [eng.submit(p, max_new_tokens=12) for p in prompts]
        res = eng.run_until_complete()
        assert kernel_calls
        for rid, p in zip(rids, prompts):
            want = np.asarray(model.generate(
                paddle.to_tensor(p[None]), max_new_tokens=12,
                temperature=0.0)._data)[0, len(p):]
            np.testing.assert_array_equal(res[rid].tokens, want)
        st = eng.stats()
        assert st["kv_tiles_held"] == 2 * 3 * len(seen)
        assert any((p == 0).any() for p in seen)        # below 3 of 3
        assert st["kv_tiles_read"] == sum(int((p // 128 + 1).sum())
                                          for p in seen)
        assert 0 < st["kv_tiles_read"] < st["kv_tiles_held"]
        # ... and on the phases the benchmark reads
        from paddle_tpu import trace

        rows, _ = trace.phases()
        counts = [c for n, *_, c in rows
                  if n == "serve/decode_dispatch" and c][-len(seen):]
        assert sum(c["kv_tiles_read"] for c in counts) == st["kv_tiles_read"]
        assert sum(c["kv_tiles_held"] for c in counts) == st["kv_tiles_held"]

    @pytest.mark.parametrize("where", ["on_the_chip", "off_the_chip"])
    def test_tiles_written_back_are_counted(self, request, where):
        """Where the step's attention stores the step's column, a tile of
        keys and one of values a row a layer, every step; where the store
        is a call or a select of its own, none."""
        from paddle_tpu import trace
        from paddle_tpu.inference.serving import ServingEngine

        if where == "on_the_chip":
            request.getfixturevalue("kernel_calls")
        eng = ServingEngine(_tiny(), max_batch=3)
        eng.submit(np.arange(7, dtype=np.int32), max_new_tokens=5)
        eng.submit(np.arange(130, dtype=np.int32), max_new_tokens=3)
        eng.run_until_complete()
        st = eng.stats()
        steps = st["lookahead"]["rounds"]       # decode steps dispatched
        a_step = 2 * 3 * 2 if where == "on_the_chip" else 0   # x rows x layers
        assert steps and st["kv_tiles_written"] == a_step * steps
        rows, _ = trace.phases()
        counts = [c for n, *_, c in rows
                  if n == "serve/decode_dispatch" and c][-steps:]
        assert [c["kv_tiles_written"] for c in counts] == [a_step] * steps

    def test_off_the_chip_every_tile_is_read(self):
        from paddle_tpu.inference.serving import ServingEngine

        eng = ServingEngine(_tiny(), max_batch=2)
        assert eng._kv_tile == eng.T        # the einsums: the whole row
        eng.submit(np.arange(7, dtype=np.int32), max_new_tokens=4)
        eng.run_until_complete()
        st = eng.stats()
        assert st["kv_tiles_read"] == st["kv_tiles_held"] > 0
