"""TPP micro-kernel registry (ISSUE 11, ops/tpp.py): each blocked
primitive matches its reference math within a per-op band (fp32
interpret mode is bit-exact for the elementwise kernels and
accumulation-order-tight for the matmuls), the two ported ops
differentiate correctly (reference-math backward), the registry keys by
(op, dtype, block) and meters calls + analytic costs, and the GPT block
routes through the ports only under FLAGS_tpp_kernels with a dense
fallback for shapes the registry can't tile."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle

#: per-op comparison bands (CPU interpret mode, fp32): elementwise
#: kernels are bit-exact; blocked matmuls may differ by accumulation
#: order only
TOL = {"matmul": 1e-5, "bias_act": 0.0, "softmax_rows": 1e-6,
       "masked_reduce": 0.0, "ln_matmul": 1e-5, "fused_mlp": 1e-5}


@pytest.fixture(autouse=True)
def _restore_flags():
    yield
    paddle.set_flags({"tpp_kernels": False})


@pytest.fixture(scope="module")
def tpp():
    from paddle_tpu.ops import tpp as mod

    return mod


@pytest.fixture(scope="module")
def data():
    rng = np.random.RandomState(0)
    return {
        "x": jnp.asarray(rng.randn(24, 32).astype(np.float32)),
        "w1": jnp.asarray(rng.randn(32, 128).astype(np.float32) * 0.1),
        "b1": jnp.asarray(rng.randn(128).astype(np.float32) * 0.1),
        "w2": jnp.asarray(rng.randn(128, 32).astype(np.float32) * 0.1),
        "b2": jnp.asarray(rng.randn(32).astype(np.float32) * 0.1),
        "gamma": jnp.asarray(1.0 + 0.1 * rng.randn(32).astype(np.float32)),
        "beta": jnp.asarray(0.1 * rng.randn(32).astype(np.float32)),
        "mask": jnp.asarray(rng.rand(24, 32) > 0.5),
    }


class TestMicroKernels:
    def test_matmul_bias_act_epilogue(self, tpp, data):
        got = tpp.matmul(data["x"], data["w1"], bias=data["b1"],
                         act="gelu")
        ref = jax.nn.gelu(data["x"] @ data["w1"] + data["b1"],
                          approximate=False)
        assert float(jnp.abs(got - ref).max()) <= TOL["matmul"]

    def test_matmul_input_activation(self, tpp, data):
        got = tpp.matmul(data["x"], data["w1"], in_act="relu")
        ref = jnp.maximum(data["x"], 0.0) @ data["w1"]
        assert float(jnp.abs(got - ref).max()) <= TOL["matmul"]

    def test_bias_act(self, tpp, data):
        got = tpp.bias_act(data["x"] @ data["w1"], data["b1"],
                           act="gelu")
        ref = jax.nn.gelu(data["x"] @ data["w1"] + data["b1"],
                          approximate=False)
        assert float(jnp.abs(got - ref).max()) <= TOL["bias_act"]

    def test_softmax_rows(self, tpp, data):
        got = tpp.softmax_rows(data["x"])
        ref = jax.nn.softmax(data["x"], axis=-1)
        assert float(jnp.abs(got - ref).max()) <= TOL["softmax_rows"]

    def test_masked_reduce_sum_and_max(self, tpp, data):
        x, mask = data["x"], data["mask"]
        got = tpp.masked_reduce(x, mask, "sum")[:, 0]
        ref = jnp.where(mask, x, 0.0).sum(-1)
        assert float(jnp.abs(got - ref).max()) <= TOL["masked_reduce"]
        gmax = tpp.masked_reduce(x, mask, "max")[:, 0]
        rmax = jnp.where(mask, x, -jnp.inf).max(-1)
        assert float(jnp.abs(gmax - rmax).max()) <= TOL["masked_reduce"]

    def test_untileable_shapes_raise(self, tpp):
        with pytest.raises(ValueError, match="tile"):
            tpp.matmul(jnp.zeros((7, 32)), jnp.zeros((32, 32)))
        assert tpp.supported_2d(7, 32, 32, "float32") is None
        assert tpp.supported_2d(24, 32, 32, "int32") is None


class TestPortedOps:
    def test_ln_matmul_forward_and_grads(self, tpp, data):
        x, g, be = data["x"], data["gamma"], data["beta"]
        w, b = data["w1"], data["b1"]
        got = tpp.ln_matmul(x, g, be, w, b)
        ref = tpp._ln_matmul_ref(x, g, be, w, b)
        assert float(jnp.abs(got - ref).max()) <= TOL["ln_matmul"]
        for argnum in range(5):
            gk = jax.grad(lambda *a: tpp.ln_matmul(*a).sum(),
                          argnums=argnum)(x, g, be, w, b)
            gr = jax.grad(lambda *a: tpp._ln_matmul_ref(*a).sum(),
                          argnums=argnum)(x, g, be, w, b)
            assert float(jnp.abs(gk - gr).max()) <= 1e-4, argnum

    def test_fused_mlp_forward_and_grads(self, tpp, data):
        args = (data["x"], data["w1"], data["b1"], data["w2"],
                data["b2"])
        got = tpp.fused_mlp(*args, False)
        ref = tpp._mlp_ref(*args, False)
        assert float(jnp.abs(got - ref).max()) <= TOL["fused_mlp"]
        for argnum in range(5):
            gk = jax.grad(lambda *a: tpp.fused_mlp(*a, False).sum(),
                          argnums=argnum)(*args)
            gr = jax.grad(lambda *a: tpp._mlp_ref(*a, False).sum(),
                          argnums=argnum)(*args)
            assert float(jnp.abs(gk - gr).max()) <= 1e-4, argnum

    def test_tanh_gelu_variant(self, tpp, data):
        args = (data["x"], data["w1"], data["b1"], data["w2"],
                data["b2"])
        got = tpp.fused_mlp(*args, True)
        ref = tpp._mlp_ref(*args, True)
        assert float(jnp.abs(got - ref).max()) <= TOL["fused_mlp"]


class TestRegistry:
    def test_keyed_by_op_dtype_block_and_counts_calls(self, tpp, data):
        before = {(r["op"], r["dtype"], tuple(r["block"])): r["calls"]
                  for r in tpp.registry_table()}
        tpp.softmax_rows(data["x"])
        tpp.softmax_rows(data["x"])
        after = {(r["op"], r["dtype"], tuple(r["block"])): r["calls"]
                 for r in tpp.registry_table()}
        key = ("softmax_rows", "float32", (8, 32))
        assert after[key] == before.get(key, 0) + 2

    def test_cost_registry_visible(self, tpp, data):
        from paddle_tpu.trace import costs

        tpp.ln_matmul(data["x"], data["gamma"], data["beta"],
                      data["w1"], data["b1"])
        entry = costs.get("tpp", "ln_matmul")
        assert entry is not None
        assert entry["flops"] > 0 and entry["calls"] >= 1

    def test_call_counter_metered(self, tpp, data):
        from paddle_tpu import monitor

        reg = monitor.default_registry()
        fam = reg.get("tpp_kernel_calls_total")
        base = 0
        if fam is not None:
            base = sum(s.value for s in fam.series()
                       if s.labels.get("op") == "softmax_rows")
        tpp.softmax_rows(data["x"])
        fam = monitor.default_registry().get("tpp_kernel_calls_total")
        now = sum(s.value for s in fam.series()
                  if s.labels.get("op") == "softmax_rows")
        assert now == base + 1


class TestGPTIntegration:
    def _forward_logits(self, tpp_on, hidden=32, seq=16):
        from paddle_tpu.models import GPTConfig, GPTForCausalLM

        paddle.set_flags({"tpp_kernels": tpp_on})
        paddle.seed(0)
        cfg = GPTConfig(vocab_size=64, hidden_size=hidden, num_layers=1,
                        num_heads=2, max_seq_len=32, dropout=0.0)
        m = GPTForCausalLM(cfg)
        m.eval()
        ids = paddle.to_tensor(
            np.arange(2 * seq, dtype=np.int32).reshape(2, seq) % 64)
        return np.asarray(m(ids)._data)

    def test_armed_forward_matches_dense_in_band(self):
        dense = self._forward_logits(False)
        armed = self._forward_logits(True)
        np.testing.assert_allclose(armed, dense, rtol=1e-4, atol=1e-5)

    def test_untileable_model_falls_back_dense_bitexact(self):
        # hidden 36 has no registry block edge: the armed forward must
        # take the dense path and stay BIT-identical
        dense = self._forward_logits(False, hidden=36)
        armed = self._forward_logits(True, hidden=36)
        assert dense.tobytes() == armed.tobytes()

    @pytest.mark.parametrize("approx", [False, True])
    def test_compiled_block_mlp_raises_by_name_for_exact_gelu(
            self, tpp, approx):
        """Lowering for the chip (interpret=False): an exact-GELU block
        raises by name BEFORE any kernel is built — never None, which would
        send the block dense while FLAGS_tpp_kernels says otherwise. The
        tanh form passes the gate (its lowering fails here only because
        this backend is a CPU)."""
        from paddle_tpu.models import GPTConfig, GPTForCausalLM

        paddle.seed(0)
        m = GPTForCausalLM(GPTConfig(
            vocab_size=64, hidden_size=32, num_layers=1, num_heads=2,
            max_seq_len=32, dropout=0.0, gelu_approx=approx))
        blk = m.gpt.blocks[0]
        x = jnp.zeros((2, 16, 32), jnp.float32)
        want = ValueError if approx else NotImplementedError
        match = "interpret mode" if approx else \
            r"tpp\.gpt_block_mlp: exact \(erf\) GELU"
        with pytest.raises(want, match=match):
            tpp.gpt_block_mlp(x, blk.ln2, blk.mlp, interpret=False)

    def test_ports_land_in_registry_after_armed_train_step(self, tpp):
        from paddle_tpu.distributed.mesh import build_mesh
        from paddle_tpu.distributed.spmd import SpmdTrainer
        from paddle_tpu.models import (GPTConfig, GPTForCausalLM,
                                       GPTPretrainLoss)

        paddle.set_flags({"tpp_kernels": True})
        paddle.seed(0)
        cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=1,
                        num_heads=2, max_seq_len=32, dropout=0.0)
        model = GPTForCausalLM(cfg)
        opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=model.parameters())
        mesh = build_mesh((1,), ("dp",), devices=jax.devices()[:1])
        tr = SpmdTrainer(model, opt, loss_fn=GPTPretrainLoss(),
                         mesh=mesh)
        rng = np.random.RandomState(0)
        ids = rng.randint(0, 64, (2, 16)).astype(np.int32)
        lb = rng.randint(0, 64, (2, 16)).astype(np.int32)
        loss = tr.train_step(ids, lb)
        assert np.isfinite(float(np.asarray(loss._data)))
        ops = {r["op"].split("|")[0] for r in tpp.registry_table()}
        assert "ln_matmul" in ops
        assert any(o.startswith("fused_mlp") for o in ops)
