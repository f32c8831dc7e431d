"""Tier-1 gate for the black-box flight recorder (ISSUE 7): with
FLAGS_blackbox unset every beacon()/note() call site is a single boolean
check — no beacon registers, nothing lands in the ring, no blackbox_*
metric series appears, NO sentinel thread starts, and serving behavior
is bit-identical to the pre-PR engine — the same <5µs/call bar as the
monitor/failpoints/trace fast paths. Plus: tools/blackbox_dump.py
--read/--json exit codes are pinned."""
import importlib.util
import json
import os
import sys
import threading

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.monitor import blackbox

#: metric families this PR introduced — with the flag unset NONE of them
#: may grow a series on any instrumented path
BLACKBOX_FAMILIES = ("blackbox_dump_total", "blackbox_ring_events_total")


@pytest.fixture(autouse=True)
def _disabled():
    blackbox.stop_sentinel()
    blackbox.disable()
    blackbox.reset()
    yield
    blackbox.stop_sentinel()
    blackbox.disable()
    blackbox.reset()


def _tiny_model():
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=1,
                    num_heads=2, max_seq_len=64, dropout=0.0)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


class TestInertByDefault:
    def test_no_sentinel_thread_with_flag_unset(self):
        """The sentinel thread only exists once armed: a default process
        must never grow a watcher thread."""
        assert not blackbox.sentinel_running()
        names = {t.name for t in threading.enumerate()}
        assert blackbox.SENTINEL_THREAD_NAME not in names
        # beacons with the flag unset must not auto-start it either
        for _ in range(10):
            blackbox.beacon("gate")
        assert not blackbox.sentinel_running()

    def test_serving_parity_and_zero_metric_drift(self):
        """Flag unset: the beacon-instrumented serving + trainer paths
        leave the registry without a single blackbox_* series, the
        engine keeps exact solo-generate parity, and no beacon site
        registers anywhere."""
        from paddle_tpu.inference.serving import ServingEngine

        monitor.reset()
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
        from paddle_tpu.distributed.mesh import build_mesh
        from paddle_tpu.distributed.spmd import SpmdTrainer
        import jax

        model = paddle.nn.Linear(4, 1)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=model.parameters())
        mesh = build_mesh((1,), ("dp",), devices=jax.devices()[:1])
        tr = SpmdTrainer(model, opt, loss_fn=paddle.nn.MSELoss(),
                         mesh=mesh)
        tr.train_step(np.ones((2, 4), np.float32),
                      np.zeros((2, 1), np.float32))

        reg = monitor.default_registry()
        for family in BLACKBOX_FAMILIES:
            # the family may EXIST if an earlier test exercised the
            # recorder in-process (registries keep zeroed series across
            # reset); the gate is that this flag-unset workload never
            # MOVES it
            metric = reg.get(family)
            assert metric is None or all(
                s.value == 0 for s in metric.series()), family
        assert blackbox.beacons() == {}
        assert blackbox.ring() == []

    def test_snapshot_structure_identical_across_blackbox_use(self):
        """The registry snapshot after a flag-unset workload must be
        structurally identical whether or not the recorder was ever
        exercised in-process (enabled, then back off)."""
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
        # exercise the beacon machinery heavily in between (beacons only:
        # note()/dump() legitimately register their blackbox_* counters —
        # opting the recorder in IS allowed to grow the registry), then
        # flip it back off
        blackbox.enable(install=False)
        for i in range(50):
            blackbox.beacon(f"noise{i % 3}")
            blackbox.set_context("noise", i)
        blackbox.disable()
        blackbox.reset()
        again = run_once()
        assert base == again


class TestBlackboxDumpTool:
    def _load(self):
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        spec = importlib.util.spec_from_file_location(
            "blackbox_dump", os.path.join(repo, "tools",
                                          "blackbox_dump.py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules.pop("blackbox_dump", None)
        spec.loader.exec_module(mod)
        return mod

    def _bundle(self, tmp_path):
        blackbox.enable(install=False)
        try:
            blackbox.beacon("gate_tool")
            path = blackbox.dump("signal", site="gate_tool",
                                 dir_=str(tmp_path))
        finally:
            blackbox.disable()
        assert path is not None
        return path

    def test_valid_bundle_exits_zero(self, tmp_path, capsys):
        tool = self._load()
        path = self._bundle(tmp_path)
        rc = tool.main(["--read", path, "--json"])
        assert rc == 0
        report = json.loads(capsys.readouterr().out)
        assert report["tool"] == "blackbox_dump"
        assert set(report) >= {"tool", "passes", "targets", "totals"}
        assert report["totals"]["error"] == 0
        (target,) = report["targets"].values()
        assert target["bundle"]["site"] == "gate_tool"

    def test_missing_bundle_exits_one(self, tmp_path, capsys):
        tool = self._load()
        rc = tool.main(["--read", str(tmp_path / "nope.json"), "--json"])
        assert rc == 1
        report = json.loads(capsys.readouterr().out)
        errs = [f for t in report["targets"].values()
                for f in t["findings"] if f["severity"] == "error"]
        assert any(f["pass"] == "bundle-valid" for f in errs)

    def test_malformed_bundle_exits_one(self, tmp_path):
        tool = self._load()
        bad = tmp_path / "bad.json"
        bad.write_text("{definitely not json")
        assert tool.main(["--read", str(bad)]) == 1
        # well-formed JSON missing required keys is just as malformed
        partial = tmp_path / "partial.json"
        partial.write_text(json.dumps({"reason": "stall"}))
        assert tool.main(["--read", str(partial)]) == 1

    def test_pretty_printer_names_the_wedge(self, tmp_path, capsys):
        tool = self._load()
        path = self._bundle(tmp_path)
        rc = tool.main(["--read", path])
        assert rc == 0
        out = capsys.readouterr().out
        assert "gate_tool" in out
        assert "threads" in out

    def test_no_action_is_an_error(self):
        tool = self._load()
        with pytest.raises(SystemExit):
            tool.main([])
