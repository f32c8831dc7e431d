"""Structured tracing + device cost accounting (ISSUE 5): span model
(nesting/ids/attrs), ring-buffer cap, serving end-to-end request traces,
trainer MFU joined from the cost registry, chrome-trace export with
parent/flow integrity, and the JSONL span-log round-trip."""
import json
import threading

import numpy as np
import pytest

import jax

import paddle_tpu as paddle
from paddle_tpu import monitor, trace
from paddle_tpu.trace import costs


@pytest.fixture(autouse=True)
def _traced():
    """Each test runs with tracing ON against a clean buffer/registry and
    leaves the process exactly as it found it (flag off by default)."""
    trace.clear()
    costs.reset()
    trace.enable()
    yield
    trace.disable()
    trace.clear()
    costs.reset()
    paddle.set_flags({"trace_log_path": ""})


def _tiny_gpt():
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=1,
                    num_heads=2, max_seq_len=64, dropout=0.0)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


class TestSpanModel:
    def test_nesting_inherits_trace_and_parent(self):
        with trace.span("outer", subsystem="t", a=1) as outer:
            assert trace.current_span() is outer
            with trace.span("inner") as inner:
                assert inner.parent_id == outer.span_id
                assert inner.trace_id == outer.trace_id
        assert trace.current_span() is None
        rec = {s.name: s for s in trace.spans()}
        assert set(rec) == {"outer", "inner"}
        assert rec["outer"].attrs == {"a": 1}
        assert rec["outer"].end_ns >= rec["outer"].start_ns
        # inner closed first: buffer order is end order
        assert [s.name for s in trace.spans()] == ["inner", "outer"]

    def test_span_ids_unique_and_attrs_settable(self):
        with trace.span("a") as s1:
            s1.set(k="v", n=2)
        with trace.span("b") as s2:
            pass
        assert s1.span_id != s2.span_id
        assert s1.trace_id != s2.trace_id   # separate roots, separate traces
        assert s1.attrs == {"k": "v", "n": 2}

    def test_start_span_and_emit_explicit_parenting(self):
        root = trace.start_span("root", subsystem="t")
        child = trace.start_span("child", parent=root)
        child.end(done=True)
        trace.emit("retro", root.start_ns, root.start_ns + 1000,
                   parent=root, x=1)
        root.end()
        by_name = {s.name: s for s in trace.spans()}
        assert by_name["child"].parent_id == root.span_id
        assert by_name["child"].trace_id == root.trace_id
        assert by_name["retro"].parent_id == root.span_id
        assert by_name["retro"].end_ns - by_name["retro"].start_ns == 1000
        assert by_name["child"].attrs["done"] is True

    def test_end_is_idempotent(self):
        s = trace.start_span("once")
        s.end()
        first_end = s.end_ns
        s.end(ignored=1)
        assert s.end_ns == first_end
        assert sum(1 for x in trace.spans() if x.span_id == s.span_id) == 1
        assert "ignored" not in s.attrs

    def test_ring_buffer_cap_drops_oldest(self):
        old_cap = trace.capacity()
        try:
            trace.set_capacity(8)
            for i in range(20):
                with trace.span(f"s{i}"):
                    pass
            got = [s.name for s in trace.spans()]
            assert got == [f"s{i}" for i in range(12, 20)]
        finally:
            trace.set_capacity(old_cap)

    def test_disabled_is_noop(self):
        trace.disable()
        with trace.span("ghost") as s:
            s.set(a=1)
        assert not trace.spans()
        assert trace.start_span("ghost2").end() is not None

    def test_threads_get_independent_stacks(self):
        seen = {}

        def worker():
            with trace.span("w") as s:
                seen["parent"] = s.parent_id

        with trace.span("main"):
            t = threading.Thread(target=worker)
            t.start()
            t.join()
        # the worker's span must NOT parent onto main's stack
        assert seen["parent"] is None

    def test_callable_module_keeps_the_math_op(self):
        # paddle.trace was the matrix-trace op before the module existed
        x = paddle.to_tensor(np.arange(9, dtype=np.float32).reshape(3, 3))
        assert float(np.asarray(paddle.trace(x)._data)) == 12.0
        assert paddle.trace is trace


class TestServingRequestTrace:
    def test_request_lifecycle_spans_share_one_trace_id(self):
        from paddle_tpu.inference.serving import ServingEngine

        m = _tiny_gpt()
        eng = ServingEngine(m, max_batch=2)
        rng = np.random.RandomState(0)
        rids = [eng.submit(rng.randint(0, 64, (n,)).astype(np.int32),
                           max_new_tokens=4) for n in (5, 9)]
        res = eng.run_until_complete()
        for rid in rids:
            req = res[rid]
            assert req.trace_id is not None
            mine = [s for s in trace.spans() if s.trace_id == req.trace_id]
            names = {s.name for s in mine}
            assert {"request", "queue_wait", "prefill", "decode"} <= names
            root = next(s for s in mine if s.name == "request")
            assert root.attrs["finish_reason"] == "length"
            assert root.attrs["new_tokens"] == 4
            # every child parents back to the root
            for s in mine:
                if s.name != "request":
                    assert s.parent_id == root.span_id
            # 1 prefill token + 3 decode steps = max_new_tokens
            assert sum(1 for s in mine if s.name == "decode") == 3
        # the two requests got DISTINCT trace ids
        assert res[rids[0]].trace_id != res[rids[1]].trace_id

    def test_chunked_prefill_emits_chunk_spans(self):
        from paddle_tpu.inference.serving import ServingEngine

        m = _tiny_gpt()
        eng = ServingEngine(m, max_batch=2, prefill_chunk=4)
        rng = np.random.RandomState(0)
        rid = eng.submit(rng.randint(0, 64, (10,)).astype(np.int32),
                         max_new_tokens=2)
        eng.run_until_complete()
        req = eng.get_request(rid)
        chunks = [s for s in trace.spans()
                  if s.trace_id == req.trace_id
                  and s.name == "prefill_chunk"]
        assert len(chunks) == 3   # ceil(10 / 4)
        assert [c.attrs["offset"] for c in chunks] == [0, 4, 8]

    def test_breakdown_joins_cost_registry(self):
        from paddle_tpu.inference.serving import ServingEngine

        m = _tiny_gpt()
        eng = ServingEngine(m, max_batch=2)
        rng = np.random.RandomState(0)
        eng.submit(rng.randint(0, 64, (5,)).astype(np.int32),
                   max_new_tokens=4)
        eng.run_until_complete()
        bd = eng.stats()["breakdown"]
        assert bd["wall_ms_total"] > 0
        assert "decode_greedy" in bd["kinds"] and "prefill" in bd["kinds"]
        # FLAGS_trace forced executables through the cost registry, so
        # the flops join is live and the serving-side MFU is finite
        row = bd["kinds"]["decode_greedy"]
        assert row["flops_per_call"] > 0
        assert np.isfinite(bd["mfu"]) and bd["mfu"] > 0
        fr = sum(r["wall_fraction"] for r in bd["kinds"].values())
        assert abs(fr - 1.0) < 1e-9

    def test_queue_wait_ends_at_admission_and_finish_while_queued(self):
        from paddle_tpu.inference.serving import ServingEngine

        m = _tiny_gpt()
        eng = ServingEngine(m, max_batch=1)
        rng = np.random.RandomState(0)
        r1 = eng.submit(rng.randint(0, 64, (5,)).astype(np.int32),
                        max_new_tokens=2)
        r2 = eng.submit(rng.randint(0, 64, (5,)).astype(np.int32),
                        max_new_tokens=2)
        assert eng.cancel(r2) is True   # finished while still queued
        eng.run_until_complete()
        req2 = eng.get_request(r2)
        mine2 = [s for s in trace.spans() if s.trace_id == req2.trace_id]
        root2 = next(s for s in mine2 if s.name == "request")
        assert root2.attrs["finish_reason"] == "cancelled"
        assert any(s.name == "queue_wait" for s in mine2)
        req1 = eng.get_request(r1)
        waits = [s for s in trace.spans()
                 if s.trace_id == req1.trace_id and s.name == "queue_wait"]
        assert len(waits) == 1 and "wait_ms" in waits[0].attrs


class TestTrainerCostJoin:
    def _trainer(self):
        from paddle_tpu.distributed.mesh import build_mesh
        from paddle_tpu.distributed.spmd import SpmdTrainer

        paddle.seed(0)
        model = paddle.nn.Linear(4, 1)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=model.parameters())
        mesh = build_mesh((1,), ("dp",), devices=jax.devices()[:1])
        return SpmdTrainer(model, opt, loss_fn=paddle.nn.MSELoss(),
                           mesh=mesh)

    def test_step_span_and_finite_mfu(self):
        tr = self._trainer()
        x = np.ones((2, 4), np.float32)
        y = np.zeros((2, 1), np.float32)
        tr.train_step(x, y)
        tr.train_step(x, y)
        steps = [s for s in trace.spans() if s.name == "train_step"]
        assert len(steps) == 2
        assert steps[0].attrs["source"] in ("fresh", "disk")
        assert steps[1].attrs["source"] == "memory"
        sig = steps[0].attrs["sig"]
        entry = costs.get("trainer", sig)
        assert entry is not None and entry["flops"] > 0
        st = tr.stats()
        assert st["steps"] == 2
        assert st["flops_per_step"] == entry["flops"]
        assert st["mfu"] is not None
        assert np.isfinite(st["mfu"]) and st["mfu"] > 0
        assert st["hbm"]["peak_bytes"] > 0
        assert st["breakdown"]["dispatch_ms_total"] >= 0

    def test_program_gauges_exported(self):
        monitor.reset()
        tr = self._trainer()
        tr.train_step(np.ones((2, 4), np.float32),
                      np.zeros((2, 1), np.float32))
        flops = monitor.default_registry().get("program_flops")
        assert flops is not None
        sites = {s.labels["site"] for s in flops.series()}
        assert "trainer" in sites
        hbm = monitor.default_registry().get("program_hbm_bytes")
        kinds = {s.labels["kind"] for s in hbm.series()
                 if s.labels.get("site") == "trainer"}
        assert {"peak", "argument", "output", "temp"} <= kinds

    def test_two_trainers_same_batch_sig_do_not_clobber(self):
        """The site-global cost table keys by batch signature only; each
        trainer must join its OWN executable's flops (metrics_dump --all
        runs several models at identical shapes)."""
        from paddle_tpu.distributed.mesh import build_mesh
        from paddle_tpu.distributed.spmd import SpmdTrainer

        paddle.seed(0)
        mesh = build_mesh((1,), ("dp",), devices=jax.devices()[:1])

        def trainer(model):
            opt = paddle.optimizer.SGD(learning_rate=0.1,
                                       parameters=model.parameters())
            return SpmdTrainer(model, opt, loss_fn=paddle.nn.MSELoss(),
                               mesh=mesh)

        small = trainer(paddle.nn.Linear(4, 1))
        big = trainer(paddle.nn.Sequential(
            paddle.nn.Linear(4, 64), paddle.nn.ReLU(),
            paddle.nn.Linear(64, 1)))
        x = np.ones((2, 4), np.float32)
        y = np.zeros((2, 1), np.float32)
        small.train_step(x, y)
        big.train_step(x, y)   # same batch sig, different executable
        f_small = small.stats()["flops_per_step"]
        f_big = big.stats()["flops_per_step"]
        assert f_small and f_big and f_small < f_big

    def test_peak_bytes_subtracts_donation_alias(self):
        """Donated buffers appear in both argument and output sizes;
        peak must not double-count them (the serving KV caches are the
        canonical case)."""
        import jax.numpy as jnp

        from paddle_tpu.framework import aot

        cj = aot.cached_jit(lambda c, x: (c + x, c.sum()), site="t",
                            label="donated", donate_argnums=(0,))
        cj.warm(jax.ShapeDtypeStruct((256, 256), jnp.float32),
                jax.ShapeDtypeStruct((256, 256), jnp.float32))
        e = costs.get("t", "donated")
        assert e is not None and e["alias_bytes"] > 0
        assert e["peak_bytes"] == (e["argument_bytes"] + e["output_bytes"]
                                   + e["temp_bytes"]
                                   + e["generated_code_bytes"]
                                   - e["alias_bytes"])

    def test_peak_flops_finite_and_overridable(self):
        assert costs.peak_flops() > 0
        paddle.set_flags({"device_peak_flops": 123.0})
        try:
            assert costs.peak_flops() == 123.0
        finally:
            paddle.set_flags({"device_peak_flops": 0.0})

    @pytest.mark.parametrize("peak", [costs.peak_flops,
                                      costs.peak_hbm_bandwidth])
    def test_unknown_tpu_kind_raises_known_kind_and_cpu_answer(self, peak):
        """The nominal constants are for the CPU harness only: a TPU the
        table does not know is an error, never a default."""
        import types

        def dev(platform, kind):
            return types.SimpleNamespace(platform=platform,
                                         device_kind=kind)

        assert peak(dev("tpu", "TPU v5 lite")) in (197e12, 0.8e12)
        assert peak(dev("cpu", "cpu")) in (1e12, 1e11)
        with pytest.raises(ValueError, match="TPU v99"):
            peak(dev("tpu", "TPU v99"))


class TestChromeExport:
    def test_export_loads_and_parents_resolve(self, tmp_path):
        from paddle_tpu.inference.serving import ServingEngine

        m = _tiny_gpt()
        eng = ServingEngine(m, max_batch=2)
        rng = np.random.RandomState(0)
        eng.submit(rng.randint(0, 64, (5,)).astype(np.int32),
                   max_new_tokens=3)
        eng.run_until_complete()
        path = str(tmp_path / "trace.json")
        trace.export_chrome(path)
        with open(path) as f:
            doc = json.load(f)
        slices = [e for e in doc["traceEvents"]
                  if e.get("cat") == "span" and e["ph"] == "X"]
        assert slices
        # the acceptance criterion: queue/prefill/decode slices in the
        # chrome JSON share the request's ONE trace_id
        lifecycle = [e for e in slices
                     if e["name"] in ("queue_wait", "prefill", "decode")]
        assert {e["name"] for e in lifecycle} == {"queue_wait", "prefill",
                                                  "decode"}
        assert len({e["args"]["trace_id"] for e in lifecycle}) == 1
        ids = {e["args"]["span_id"] for e in slices}
        for e in slices:
            parent = e["args"].get("parent_id")
            if parent is not None:
                assert parent in ids, (e["name"], parent)
        # flow chain: the request's spans are linked start->...->finish
        flows = [e for e in doc["traceEvents"] if e.get("cat") == "flow"]
        assert any(e["ph"] == "s" for e in flows)
        assert any(e["ph"] == "f" for e in flows)
        # the step phases are drawn as one more process; the occupancy
        # the old counter track sampled is the root phase's `active`
        drawn = [e for e in doc["traceEvents"] if e.get("cat") == "phase"]
        steps = [e for e in drawn if e["name"] == "serve/step"]
        assert steps and all("active" in e["args"] for e in steps)
        assert any(e["name"] == "serve/decode_wait"
                   and e["args"]["parent"] == "serve/step" for e in drawn)
        assert len({e["pid"] for e in drawn}) == 1
        # subsystem process naming
        meta = {e["args"]["name"] for e in doc["traceEvents"]
                if e["ph"] == "M"}
        assert {"serving", "phases", "serve"} <= meta

    def test_old_profiler_export_uses_merged_exporter(self, tmp_path):
        from paddle_tpu import profiler

        profiler.start_profiler()
        with profiler.RecordEvent("outer"):
            with profiler.RecordEvent("inner"):
                pass
        with trace.span("aside", subsystem="t"):
            pass
        profiler.stop_profiler()
        path = str(tmp_path / "old_api.json")
        profiler.export_chrome_tracing(path)
        with open(path) as f:
            doc = json.load(f)
        host = [e for e in doc["traceEvents"] if e.get("cat") == "host"]
        assert {e["name"] for e in host} == {"outer", "inner"}
        # sorted by start time: outer begins before inner
        assert [e["name"] for e in host] == ["outer", "inner"]
        assert host[0]["args"]["depth"] == 0
        assert host[1]["args"]["depth"] == 1
        # the old API's output now carries span context too
        assert any(e.get("cat") == "span" and e["name"] == "aside"
                   for e in doc["traceEvents"])

    def test_profiler_summary_honors_sorted_by(self):
        from paddle_tpu import profiler

        with profiler.Profiler() as p:
            for _ in range(3):
                with profiler.RecordEvent("many_fast"):
                    pass
            import time as _t

            with profiler.RecordEvent("one_slow"):
                _t.sleep(0.02)
        by_total = p.summary(sorted_by="total")
        assert by_total[0]["name"] == "one_slow"
        by_calls = p.summary(sorted_by="calls")
        assert by_calls[0]["name"] == "many_fast"


class TestJsonlRoundTrip:
    def test_span_log_round_trips(self, tmp_path):
        log = str(tmp_path / "spans.jsonl")
        paddle.set_flags({"trace_log_path": log})
        with trace.span("outer", subsystem="t", a=1):
            with trace.span("inner"):
                pass
        paddle.set_flags({"trace_log_path": ""})
        recs = trace.load_spans(log)
        assert [r["name"] for r in recs] == ["inner", "outer"]
        live = {s.span_id: s for s in trace.spans()}
        for r in recs:
            s = live[r["span_id"]]
            assert r["trace_id"] == s.trace_id
            assert r["parent_id"] == s.parent_id
            assert r["attrs"] == s.attrs
            assert r["start_ns"] == s.start_ns
            assert r["end_ns"] == s.end_ns

    def test_checkpoint_spans_tagged_with_bytes(self, tmp_path):
        p = str(tmp_path / "w.pdparams")
        paddle.save({"w": paddle.to_tensor(np.ones(8, np.float32))}, p)
        paddle.load(p)
        names = [s.name for s in trace.spans()]
        assert "checkpoint/save" in names and "checkpoint/load" in names
        import os

        for s in trace.spans():
            if s.name.startswith("checkpoint/"):
                assert s.attrs["bytes"] == os.path.getsize(p)

    def test_collective_span_tagged_with_bytes(self):
        from paddle_tpu.distributed import collective

        collective.all_reduce(
            paddle.to_tensor(np.ones(4, np.float32)))
        sp = next(s for s in trace.spans()
                  if s.name == "collective/all-reduce")
        assert sp.attrs["bytes"] == 16
