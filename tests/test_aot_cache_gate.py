"""Tier-1 gate for the compile sites (framework/aot.py): nothing warmed
and nothing forced, every site behaves as a bare jax.jit — no lowering,
the executor's telemetry as it always was — and a warm-start API never
hands back a lazy jit. (The CachedJit fast path's cost is held in
tests/test_disarmed_overhead.py.)"""
import numpy as np

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.framework import aot


class TestUnwarmedIsExactlyBefore:
    def test_compile_cached_returns_the_jit_untouched(self, monkeypatch):
        jitted = jax.jit(lambda a: a + 1)

        def boom(*a, **k):
            raise AssertionError("an unforced compile_cached lowered")
        monkeypatch.setattr(aot, "_canonical_specs", boom)
        got, source = aot.compile_cached(jitted, (jnp.ones(3),))
        assert got is jitted and source == "bypass"
        assert aot.executable_of(got) is None

    def test_metrics_identical_to_before(self):
        """The executor reports miss(fresh)/hit(memory) exactly as the
        pre-AOT instrumentation did — one fresh compile, then memory
        hits, and no other source anywhere."""
        import paddle_tpu.static as st

        monitor.reset()
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
        exe = st.Executor()
        exe.run(startup)
        feed = {"x": np.ones((2, 4), np.float32)}
        exe.run(main, feed=feed, fetch_list=[y])
        exe.run(main, feed=feed, fetch_list=[y])
        cache = monitor.counter("compile_cache_total",
                                labelnames=("site", "event", "sig",
                                            "source"))
        sig = "x:float32[2,4]"
        assert cache.labels(site="executor", event="miss", sig=sig,
                            source="fresh").value == 1
        assert cache.labels(site="executor", event="hit", sig=sig,
                            source="memory").value == 1
        metric = monitor.default_registry().get("compile_cache_total")
        assert {s.labels.get("source") for s in metric.series()} \
            <= {"fresh", "memory"}

    def test_aot_compile_forces_in_memory_without_flag(self):
        """Warm-start must never hand back a lazy jit: Program.aot_compile
        AOT-compiles in memory and the later run() pays no compile."""
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
        exe = st.Executor()
        exe.run(startup)
        assert main.aot_compile({"x": ((2, 4), "float32")},
                                fetch_list=[y]) == "fresh"
        compiles = monitor.counter("compile_total", labelnames=("site",))
        before = compiles.labels(site="executor").value
        (r,) = exe.run(main, feed={"x": np.ones((2, 4), np.float32)},
                       fetch_list=[y])
        assert np.isfinite(r).all()
        assert compiles.labels(site="executor").value == before
