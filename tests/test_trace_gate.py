"""Tier-1 gate for the tracing layer (ISSUE 5): with FLAGS_trace unset
every span call site is a single boolean check — no Span object is ever
constructed, nothing lands in the ring buffer, no trace/cost metric
series appear, and serving/trainer behavior is bit-identical to the
pre-PR engines — at the same <5µs/call bar as the monitor/failpoints
fast paths. Plus: tools/trace_dump.py --json exit codes are pinned."""
import importlib.util
import os
import sys

import numpy as np
import pytest

import jax

import paddle_tpu as paddle
from paddle_tpu import monitor, trace

#: metric families this PR introduced — with the flag unset NONE of them
#: may grow a series on the serving/trainer/executor paths
TRACE_FAMILIES = ("program_flops", "program_hbm_bytes",
                  "device_hbm_used_bytes")


@pytest.fixture(autouse=True)
def _disabled():
    trace.disable()
    trace.clear()
    yield
    trace.disable()
    trace.clear()


def _forbid_spans(monkeypatch):
    """Constructing a Span (or recording one) with tracing off is a
    regression — the zero-overhead contract."""
    def boom(*a, **k):
        raise AssertionError("trace span machinery ran with FLAGS_trace "
                             "unset")
    monkeypatch.setattr(trace, "Span", boom)
    monkeypatch.setattr(trace, "_record", boom)


def _tiny_model():
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=1,
                    num_heads=2, max_seq_len=64, dropout=0.0)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


class TestInertByDefault:
    def test_hot_paths_never_construct_spans(self, monkeypatch, tmp_path):
        _forbid_spans(monkeypatch)
        # checkpoint write + read
        p = str(tmp_path / "s.pdparams")
        paddle.save({"w": paddle.to_tensor(np.ones(3))}, p)
        paddle.load(p)
        # collective
        from paddle_tpu.distributed import collective

        collective.all_reduce(paddle.to_tensor(np.ones(2, np.float32)))
        # executor compile + run
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
        (r,) = exe.run(main, feed={"x": np.ones((2, 4), np.float32)},
                       fetch_list=[y])
        assert np.isfinite(r).all()
        # trainer step
        from paddle_tpu.distributed.mesh import build_mesh
        from paddle_tpu.distributed.spmd import SpmdTrainer

        model = paddle.nn.Linear(4, 1)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=model.parameters())
        mesh = build_mesh((1,), ("dp",), devices=jax.devices()[:1])
        tr = SpmdTrainer(model, opt, loss_fn=paddle.nn.MSELoss(), mesh=mesh)
        tr.train_step(np.ones((2, 4), np.float32),
                      np.zeros((2, 1), np.float32))
        assert not trace.spans()

    def test_serving_and_trainer_metrics_have_zero_trace_drift(self):
        """Flag unset: the serving + trainer paths leave the metric
        registry exactly as the pre-PR instrumentation did — none of the
        trace/cost families grows a series, the serving engine keeps
        exact solo-generate parity, and the compile paths stay on the
        lazy-jit bypass (no forced AOT: miss/fresh accounting only)."""
        from paddle_tpu.inference.serving import ServingEngine

        monitor.reset()
        # the cost registry is process-global and keyed by program label:
        # an earlier test file on this worker may have captured the same
        # serving programs (order depends on xdist's file scheduling)
        trace.costs.reset()
        m = _tiny_model()
        rng = np.random.RandomState(0)
        prompts = [rng.randint(0, 64, (n,)).astype(np.int32)
                   for n in (5, 9)]
        eng = ServingEngine(m, max_batch=2)
        rids = [eng.submit(p, max_new_tokens=6) for p in prompts]
        res = eng.run_until_complete()
        for rid, p in zip(rids, prompts):
            ref = m.generate(paddle.to_tensor(p[None]), max_new_tokens=6,
                             temperature=0.0)
            np.testing.assert_array_equal(
                res[rid].tokens, np.asarray(ref._data)[0, len(p):])
            assert res[rid].trace_id is None   # no identity minted
        from paddle_tpu.distributed.mesh import build_mesh
        from paddle_tpu.distributed.spmd import SpmdTrainer

        model = paddle.nn.Linear(4, 1)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=model.parameters())
        mesh = build_mesh((1,), ("dp",), devices=jax.devices()[:1])
        tr = SpmdTrainer(model, opt, loss_fn=paddle.nn.MSELoss(), mesh=mesh)
        tr.train_step(np.ones((2, 4), np.float32),
                      np.zeros((2, 1), np.float32))

        reg = monitor.default_registry()
        for family in TRACE_FAMILIES:
            metric = reg.get(family)
            assert metric is None or not list(metric.series()), family
        # compile accounting unchanged: everything fresh/memory, no disk
        cache = reg.get("compile_cache_total")
        assert not any(s.labels.get("source") == "disk"
                       for s in cache.series())
        # stats() still works without the cost registry: wall-time split
        # present, flops/mfu absent rather than wrong
        assert tr.stats()["mfu"] is None
        bd = eng.stats()["breakdown"]
        assert bd["wall_ms_total"] > 0
        assert "mfu" not in bd
        assert not trace.spans()

    def test_snapshot_structure_identical_across_traced_import(self):
        """The registry snapshot taken after a flag-unset workload must
        be structurally identical whether or not the trace module has
        ever been exercised in-process — same families, same series
        keys, same counter values (histogram sums carry wall time and
        are compared on count only)."""
        from paddle_tpu.inference.serving import ServingEngine

        def run_once():
            monitor.reset()
            m = _tiny_model()
            rng = np.random.RandomState(0)
            eng = ServingEngine(m, max_batch=2)
            eng.submit(rng.randint(0, 64, (5,)).astype(np.int32),
                       max_new_tokens=4)
            eng.run_until_complete()
            out = {}
            for fam in monitor.snapshot()["metrics"]:
                for s in fam["series"]:
                    key = (fam["name"],
                           tuple(sorted(s["labels"].items())))
                    out[key] = (s["count"] if fam["type"] == "histogram"
                                else s["value"])
            return out

        base = run_once()
        # exercise the tracer heavily in between (enabled, then off)
        trace.enable()
        for i in range(50):
            with trace.span(f"noise{i}"):
                pass
        trace.disable()
        trace.clear()
        again = run_once()
        assert base == again


class TestTraceDumpTool:
    def _load(self):
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        spec = importlib.util.spec_from_file_location(
            "trace_dump", os.path.join(repo, "tools", "trace_dump.py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules.pop("trace_dump", None)
        spec.loader.exec_module(mod)
        return mod

    def test_serving_report_clean_and_chrome_written(self, capsys,
                                                     tmp_path):
        import json

        td = self._load()
        out = str(tmp_path / "t.json")
        rc = td.main(["--serving", "--json", "--chrome", out])
        assert rc == 0
        report = json.loads(capsys.readouterr().out)
        assert set(report) >= {"tool", "passes", "targets", "totals"}
        assert report["tool"] == "trace_dump"
        assert report["totals"]["error"] == 0
        assert report["targets"]["serving"]["trace"]["spans"] > 0
        assert report["targets"]["serving"]["cost_table"]
        with open(out) as f:
            doc = json.load(f)
        assert any(e.get("cat") == "span" for e in doc["traceEvents"])

    def test_missing_span_family_exits_1(self, capsys, monkeypatch):
        """The CI contract: a workload whose required span families do
        not appear fails the run. Silence the tracer and watch it burn."""
        import json

        td = self._load()
        monkeypatch.setattr(trace, "enable", lambda: None)
        rc = td.main(["--serving", "--json"])
        assert rc == 1
        report = json.loads(capsys.readouterr().out)
        errs = [f for f in report["targets"]["serving"]["findings"]
                if f["severity"] == "error"]
        assert any(f["pass"] == "spans-present" for f in errs)

    def test_no_target_is_an_error(self):
        td = self._load()
        with pytest.raises(SystemExit):
            td.main(["--json"])
