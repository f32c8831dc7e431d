"""Tier-1 gate for the fault-tolerance layer (ISSUE 4): with nothing armed
every failpoint site is a single boolean check — no fire machinery runs, no
robustness metric series appear, serving/trainer outputs are bit-identical
to the pre-PR engine — and the per-call overhead holds the same <5µs bar as
the monitor's disabled fast path. Plus: tools/chaos_check.py emits the
graph_lint report schema and exits 1 when a recovery path breaks."""
import importlib.util
import os
import sys

import numpy as np
import pytest

import jax

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.testing import failpoints as fp


@pytest.fixture(autouse=True)
def _disarmed():
    fp.reset()
    yield
    fp.reset()


def _forbid_fire(monkeypatch):
    """Any entry into the fire machinery while nothing is armed is a
    regression — the zero-overhead contract."""
    def boom(*a, **k):
        raise AssertionError("failpoint fire machinery ran with nothing "
                             "armed")
    monkeypatch.setattr(fp, "_fire", boom)


def _tiny_model():
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=1,
                    num_heads=2, max_seq_len=64, dropout=0.0)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


class TestInertByDefault:
    def test_hot_paths_never_enter_fire_machinery(self, monkeypatch,
                                                  tmp_path):
        _forbid_fire(monkeypatch)
        # checkpoint write + read
        p = str(tmp_path / "s.pdparams")
        paddle.save({"w": paddle.to_tensor(np.ones(3))}, p)
        paddle.load(p)
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
        # collective
        from paddle_tpu.distributed import collective

        collective.all_reduce(paddle.to_tensor(np.ones(2, np.float32)))
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

    def test_serving_behavior_and_metrics_identical_to_before(self):
        """Nothing armed, no deadlines/priorities used: the engine's greedy
        output keeps exact solo-generate parity and NONE of the robustness
        metric families grow a series — the zero-drift contract."""
        from paddle_tpu.inference.serving import ServingEngine

        monitor.reset()
        m = _tiny_model()
        rng = np.random.RandomState(0)
        prompts = [rng.randint(0, 64, (n,)).astype(np.int32)
                   for n in (5, 9)]
        eng = ServingEngine(m, max_batch=2)
        rids = [eng.submit(p, max_new_tokens=8) for p in prompts]
        res = eng.run_until_complete()
        for rid, p in zip(rids, prompts):
            ref = m.generate(paddle.to_tensor(p[None]), max_new_tokens=8,
                             temperature=0.0)
            np.testing.assert_array_equal(
                res[rid].tokens, np.asarray(ref._data)[0, len(p):])
            assert res[rid].finish_reason == "length"
        assert eng.health()["state"] == "ok"

        reg = monitor.default_registry()
        for family in ("failpoint_trigger_total", "request_shed_total",
                       "train_step_skipped_total",
                       "checkpoint_recover_total"):
            metric = reg.get(family)
            assert metric is None or not list(metric.series()), family
        assert monitor.counter(
            "request_deadline_exceeded_total").value == 0
        finished = reg.get("serving_requests_finished_total")
        bad = {"error", "deadline", "shed", "cancelled", "engine_stalled"}
        assert not any(s.labels.get("reason") in bad
                       for s in finished.series())

    def test_checkpoint_formats_interoperate(self, tmp_path):
        """The durability footer must not break old readers' expectations:
        a file saved now loads through the plain pickle path (pickle stops
        at its STOP opcode) and a footerless legacy file still loads."""
        import pickle

        p = str(tmp_path / "s.pdparams")
        paddle.save({"v": 41}, p)
        with open(p, "rb") as f:
            assert pickle.load(f) == {"v": 41}   # footer invisible to pickle
        legacy = str(tmp_path / "legacy.pdparams")
        with open(legacy, "wb") as f:
            pickle.dump({"v": 42}, f, protocol=4)
        assert paddle.load(legacy) == {"v": 42}


class TestChaosCheckTool:
    def _load(self):
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        spec = importlib.util.spec_from_file_location(
            "chaos_check", os.path.join(repo, "tools", "chaos_check.py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules.pop("chaos_check", None)
        spec.loader.exec_module(mod)
        return mod

    def test_all_recovery_paths_hold(self, capsys):
        import json

        cc = self._load()
        rc = cc.main(["--json"])
        assert rc == 0
        report = json.loads(capsys.readouterr().out)
        assert set(report) >= {"tool", "passes", "targets", "totals"}
        assert report["tool"] == "chaos_check"
        assert report["totals"]["error"] == 0
        names = {f["pass"]
                 for f in report["targets"]["chaos"]["findings"]}
        assert names == set(cc.PASSES)

    def test_broken_recovery_path_exits_1(self, capsys, monkeypatch):
        """The CI contract: a recovery path that stops recovering fails
        the run. Break the saver's fallback walk and watch it burn."""
        import json

        from paddle_tpu.incubate.checkpoint import auto_checkpoint as ac

        cc = self._load()

        def no_fallback(self, no=None):
            nums = self.get_checkpoint_numbers()
            return self._load_one(nums[-1])   # pre-PR behavior: crash

        monkeypatch.setattr(ac.CheckpointSaver, "load_checkpoint",
                            no_fallback)
        rc = cc.main(["--json", "--only", "ckpt_fallback"])
        assert rc == 1
        report = json.loads(capsys.readouterr().out)
        errs = [f for f in report["targets"]["chaos"]["findings"]
                if f["severity"] == "error"]
        assert any(f["pass"] == "ckpt_fallback" for f in errs)
