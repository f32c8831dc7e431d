"""Warm-start APIs (framework/aot.py): Program.aot_compile,
SpmdTrainer.aot_build and ServingEngine.warmup compile from shape specs,
in memory; the first live call then compiles nothing and the results are
bit-identical to a cold run's. What outlives the process is jax's own
compile cache (tests/test_compile_cache.py)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.framework import aot


def _flat_compiles(site=None):
    out = {}
    metric = monitor.default_registry().get("compile_cache_total")
    if metric is None:
        return out
    for s in metric.series():
        if site and s.labels.get("site") != site:
            continue
        key = (s.labels.get("event"), s.labels.get("source"))
        out[key] = out.get(key, 0) + int(s.value)
    return out


def _make_trainer():
    from paddle_tpu.distributed.mesh import build_mesh
    from paddle_tpu.distributed.spmd import SpmdTrainer
    from paddle_tpu.models import GPTConfig, GPTForCausalLM, GPTPretrainLoss

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=256, hidden_size=32, num_layers=1,
                    num_heads=2, max_seq_len=16, dropout=0.0)
    model = GPTForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    mesh = build_mesh((1,), ("dp",), devices=jax.devices()[:1])
    return SpmdTrainer(model, opt, loss_fn=GPTPretrainLoss(), mesh=mesh)


def _train_batch():
    rng = np.random.RandomState(0)
    return (rng.randint(0, 256, (2, 16)).astype(np.int32),
            rng.randint(0, 256, (2, 16)).astype(np.int32))


def _make_engine(max_seq=32):
    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=256, hidden_size=32, num_layers=1,
                    num_heads=2, max_seq_len=max_seq, dropout=0.0)
    model = GPTForCausalLM(cfg)
    model.eval()
    from paddle_tpu.inference.serving import ServingEngine as SE

    return SE(model, max_batch=2)


class TestCachedJitWarm:
    def test_warm_compiles_in_memory_and_lands_in_the_cost_registry(self):
        """warm() AOT-compiles the signature in memory: live calls never
        retrace, and the executable's flops + HBM kinds land in the
        device cost registry under (site, program label), so
        MFU/breakdown joins work for a program that was only warmed."""
        from paddle_tpu.trace import costs

        monitor.reset()
        costs.reset()
        cj = aot.cached_jit(lambda a: (a @ a).sum(), site="t", label="w")
        spec = jax.ShapeDtypeStruct((8, 8), jnp.float32)
        assert cj.warm(spec)
        assert not cj.warm(spec)
        entry = costs.get("t", "w")
        assert entry is not None and entry["flops"] > 0
        assert entry["peak_bytes"] > 0
        out = cj(jnp.ones((8, 8), jnp.float32))
        assert float(out) == 64.0 * 8
        assert _flat_compiles("t") == {("miss", "fresh"): 1,
                                       ("hit", "memory"): 1}
        assert cj.executed() == {"calls": 1, "flops": entry["flops"]}
        flops_g = monitor.default_registry().get("program_flops")
        assert any(s.labels == {"site": "t", "sig": "w"}
                   and s.value == entry["flops"]
                   for s in flops_g.series())


class TestExecutorWarmStart:
    def _program(self):
        import paddle_tpu.static as st

        paddle.seed(0)
        main, startup = st.Program(), st.Program()
        st.enable_static()
        try:
            with st.program_guard(main, startup):
                x = st.data("x", [None, 4])
                w = paddle.create_parameter([4, 4])
                y = paddle.matmul(x, w)
        finally:
            st.disable_static()
        return main, startup, y

    def test_aot_compile_parity(self):
        import paddle_tpu.static as st

        feed = {"x": np.ones((2, 4), np.float32)}
        exe = st.Executor()
        monitor.reset()
        main, startup, y = self._program()
        exe.run(startup)
        (r1,) = exe.run(main, feed=feed, fetch_list=[y])
        assert _flat_compiles("executor") == {("miss", "fresh"): 1}
        # aot_compile from specs: run() then needs no compile at all
        monitor.reset()
        main2, startup2, y2 = self._program()
        exe.run(startup2)
        spec = {"x": ((2, 4), "float32")}
        assert main2.aot_compile(spec, fetch_list=[y2]) == "fresh"
        assert main2.aot_compile(spec, fetch_list=[y2]) == "memory"
        (r2,) = exe.run(main2, feed=feed, fetch_list=[y2])
        assert _flat_compiles("executor") == {("miss", "fresh"): 1,
                                              ("hit", "memory"): 2}
        np.testing.assert_array_equal(r1, r2)


class TestTrainerWarmStart:
    def test_aot_build_parity_and_zero_compiles(self):
        x, y = _train_batch()
        monitor.reset()
        cold = _make_trainer()
        cold_losses = [float(np.asarray(cold.train_step(x, y)._data))
                       for _ in range(2)]
        assert _flat_compiles("trainer")[("miss", "fresh")] == 1
        # warm trainer: aot_build from specs compiles the step; the first
        # train_step performs ZERO compiles and the trajectory is
        # bit-identical to the cold trainer's
        monitor.reset()
        warm = _make_trainer()
        assert warm.aot_build([((2, 16), "int32"),
                               ((2, 16), "int32")]) == "fresh"
        assert warm.compiled_text() is not None
        compiles = monitor.counter("compile_total", labelnames=("site",))
        before = compiles.labels(site="trainer").value
        assert before == 1
        warm_losses = [float(np.asarray(warm.train_step(x, y)._data))
                       for _ in range(2)]
        assert compiles.labels(site="trainer").value == before
        assert warm_losses == cold_losses
        assert _flat_compiles("trainer") == {("miss", "fresh"): 1,
                                             ("hit", "memory"): 2}

    def test_partial_batch_keeps_full_batch_executable(self):
        """Executables are kept per batch signature: a trailing partial
        batch compiles its own step instead of tripping the full-batch
        executable's call guard (which would permanently disable the
        compiled path)."""
        x, y = _train_batch()
        monitor.reset()
        tr = _make_trainer()
        tr.aot_build([((2, 16), "int32"), ((2, 16), "int32")])
        tr.train_step(x, y)
        (full,) = [e[0] for e in tr._compiled_store.values()]
        loss_p = tr.train_step(x[:1], y[:1])  # trailing partial batch
        assert np.isfinite(float(np.asarray(loss_p._data)))
        assert len(tr._compiled_store) == 2
        # the full-batch signature still runs from its own executable,
        # which no call rejected
        compiles = monitor.counter("compile_total", labelnames=("site",))
        before = compiles.labels(site="trainer").value
        tr.train_step(x, y)
        assert compiles.labels(site="trainer").value == before
        assert aot.executable_of(full) is not None
        flat = _flat_compiles("trainer")
        assert flat[("hit", "memory")] >= 1 and flat[("miss", "fresh")] == 2


class TestServingWarmStart:
    def test_warmup_parity_and_zero_compiles(self):
        rng = np.random.RandomState(0)
        prompt = rng.randint(0, 256, (8,)).astype(np.int32)
        cold = _make_engine()
        cold.submit(prompt, max_new_tokens=4)
        out_cold = cold.run_until_complete()[0].tokens.tolist()
        # fresh engine, warmed from shape specs: traffic compiles nothing
        monitor.reset()
        warm = _make_engine()
        counts = warm.warmup()
        assert counts["prefill"] >= 1 and counts["step_greedy"] == 1
        compiles = monitor.counter("compile_total", labelnames=("site",))
        before = compiles.labels(site="serving").value
        warm.submit(prompt, max_new_tokens=4)
        out_warm = warm.run_until_complete()[0].tokens.tolist()
        assert compiles.labels(site="serving").value == before
        assert out_warm == out_cold  # bit-identical greedy stream
        # everything the traffic used was warmed: memory hits, and no
        # compile beyond warmup's own (it also compiles programs this
        # traffic never runs, step_sample among them)
        flat = _flat_compiles("serving")
        assert flat[("hit", "memory")] >= 3
        assert flat[("miss", "fresh")] == sum(counts.values())

    def test_draft_engine_warmup_covers_admission(self):
        """Speculative engines row-copy into the DRAFT cache too (its
        shapes differ from the target's): warmup must cover those admit/
        copy signatures or the first admission pays a fresh compile."""
        from paddle_tpu.inference.serving import ServingEngine
        from paddle_tpu.models import GPTConfig, GPTForCausalLM

        paddle.seed(0)
        cfg = GPTConfig(vocab_size=256, hidden_size=32, num_layers=1,
                        num_heads=2, max_seq_len=32, dropout=0.0)
        target = GPTForCausalLM(cfg)
        draft = GPTForCausalLM(cfg)
        target.eval()
        draft.eval()
        eng = ServingEngine(target, max_batch=2, draft_model=draft,
                            spec_k=2)
        monitor.reset()
        eng.warmup(sampling=False)
        compiles = monitor.counter("compile_total", labelnames=("site",))
        before = compiles.labels(site="serving").value
        rng = np.random.RandomState(0)
        eng.submit(rng.randint(0, 256, (8,)).astype(np.int32),
                   max_new_tokens=4)
        assert eng.run_until_complete()[0].tokens.shape[0] == 4
        assert compiles.labels(site="serving").value == before

    def test_tp_engine_warmup_specs_carry_cache_sharding(self):
        """Tensor-parallel engines: eval_shape drops the side caches'
        NamedSharding, so warmup must re-attach it — otherwise the warmed
        admit/chunk executables are compiled for unsharded rows, rejected
        at first admission, and silently handed back to the lazy jit."""
        from paddle_tpu.distributed.mesh import build_mesh
        from paddle_tpu.inference.serving import ServingEngine
        from paddle_tpu.models import GPTConfig, GPTForCausalLM

        if len(jax.devices()) < 2:
            pytest.skip("needs 2 virtual devices")
        paddle.seed(0)
        cfg = GPTConfig(vocab_size=256, hidden_size=32, num_layers=1,
                        num_heads=2, max_seq_len=32, dropout=0.0)
        model = GPTForCausalLM(cfg)
        model.eval()
        mesh = build_mesh((2,), ("mp",), devices=jax.devices()[:2])
        eng = ServingEngine(model, max_batch=2, tp_mesh=mesh)
        monitor.reset()
        eng.warmup(sampling=False)
        rng = np.random.RandomState(0)
        eng.submit(rng.randint(0, 256, (8,)).astype(np.int32),
                   max_new_tokens=3)
        assert eng.run_until_complete()[0].tokens.shape[0] == 3
        # traffic ran the warmed executables: memory hits, and no warmed
        # executable rejected its call
        assert _flat_compiles("serving")[("hit", "memory")] >= 3
        warmed = [c for p in vars(eng).values()
                  if isinstance(p, aot.CachedJit)
                  for c in p._store.values()]
        assert len(warmed) >= 3
        assert all(aot.executable_of(c) is not None for c in warmed)
