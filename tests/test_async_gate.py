"""Tier-1 gate for the async dispatch + TPP stack (ISSUE 11): with
FLAGS_async_dispatch and FLAGS_tpp_kernels both unset, the trainer and
the GPT forward are EXACTLY the pre-PR ones — neither
paddle_tpu.distributed.async_dispatch nor paddle_tpu.ops.tpp is ever
imported (subprocess pin), params are byte-identical whether or not the
armed paths were exercised in-process, no async_*/tpp_* metric series or
dispatch/* span appears, train_step returns a plain Tensor (not a
StepHandle), and the disarmed per-step flag checks cost the same
one-lookup bar as every other disabled fast path. Plus: the
tools/metrics_dump.py --async exit-code contract and the
tools/chaos_check.py async_nonfinite registration."""
import importlib.util
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import flags, monitor, trace
from paddle_tpu.distributed.mesh import build_mesh
from paddle_tpu.distributed.spmd import SpmdTrainer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

#: metric families this PR introduced — with the flags unset NONE of
#: them may grow a series on the trainer path
ASYNC_FAMILIES = ("async_verdict_fetch_total", "async_window_depth",
                  "tpp_kernel_calls_total")

_PLAIN_TRAINER = (
    "import jax; jax.config.update('jax_platforms', 'cpu')\n"
    "import hashlib\n"
    "import numpy as np\n"
    "import paddle_tpu as paddle\n"
    "from paddle_tpu import nn\n"
    "from paddle_tpu.distributed.mesh import build_mesh\n"
    "from paddle_tpu.distributed.spmd import SpmdTrainer\n"
    "def run_plain():\n"
    "    paddle.seed(0)\n"
    "    net = nn.Sequential(nn.Linear(8, 8), nn.Linear(8, 4))\n"
    "    opt = paddle.optimizer.AdamW(learning_rate=1e-3,\n"
    "        parameters=net.parameters())\n"
    "    mesh = build_mesh((1,), ('dp',), devices=jax.devices()[:1])\n"
    "    tr = SpmdTrainer(net, opt, loss_fn=nn.MSELoss(), mesh=mesh)\n"
    "    x = paddle.to_tensor(np.ones((4, 8), np.float32))\n"
    "    y = paddle.to_tensor(np.ones((4, 4), np.float32))\n"
    "    for _ in range(3):\n"
    "        tr.train_step(x, y)\n"
    "    h = hashlib.sha256()\n"
    "    for k in sorted(tr.params):\n"
    "        h.update(np.ascontiguousarray(\n"
    "            np.asarray(tr.params[k])).tobytes())\n"
    "    return h.hexdigest()\n")


def _run(code):
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout


class TestInertByDefault:
    @pytest.mark.slow
    def test_plain_subprocess_never_imports_async_or_tpp_and_pins_params(
            self):
        """The structural zero-overhead pin, in one subprocess: a plain
        trainer run (a) never imports async_dispatch or ops.tpp, and
        (b) produces byte-identical params before vs after an
        async-armed trainer AND a TPP-armed GPT forward ran in the same
        process — the disarmed paths are the pre-PR paths."""
        _run(
            _PLAIN_TRAINER +
            "d1 = run_plain()\n"
            "import sys\n"
            "assert 'paddle_tpu.distributed.async_dispatch' not in \\\n"
            "    sys.modules, 'async_dispatch imported on the plain path'\n"
            "assert 'paddle_tpu.ops.tpp' not in sys.modules, \\\n"
            "    'ops.tpp imported on the plain path'\n"
            "paddle.set_flags({'async_dispatch': True, 'async_window': 2,\n"
            "                  'check_nan_inf': True,\n"
            "                  'tpp_kernels': True})\n"
            "from paddle_tpu.models import (GPTConfig, GPTForCausalLM,\n"
            "                               GPTPretrainLoss)\n"
            "paddle.seed(1)\n"
            "cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=1,\n"
            "                num_heads=2, max_seq_len=32, dropout=0.0)\n"
            "m2 = GPTForCausalLM(cfg)\n"
            "opt2 = paddle.optimizer.AdamW(learning_rate=1e-3,\n"
            "    parameters=m2.parameters())\n"
            "mesh2 = build_mesh((1,), ('dp',), devices=jax.devices()[:1])\n"
            "tr2 = SpmdTrainer(m2, opt2, loss_fn=GPTPretrainLoss(),\n"
            "                  mesh=mesh2)\n"
            "rng = np.random.RandomState(0)\n"
            "ids = rng.randint(0, 64, (2, 16)).astype(np.int32)\n"
            "lb = rng.randint(0, 64, (2, 16)).astype(np.int32)\n"
            "for _ in range(3):\n"
            "    h = tr2.train_step(ids, lb)\n"
            "tr2.guard_sync()\n"
            "from paddle_tpu.distributed.async_dispatch import StepHandle\n"
            "assert isinstance(h, StepHandle)\n"
            "assert 'paddle_tpu.ops.tpp' in sys.modules\n"
            "from paddle_tpu.ops import tpp\n"
            "assert any(r['op'] == 'ln_matmul'\n"
            "           for r in tpp.registry_table())\n"
            "paddle.set_flags({'async_dispatch': False,\n"
            "                  'check_nan_inf': False,\n"
            "                  'tpp_kernels': False})\n"
            "d2 = run_plain()\n"
            "assert d1 == d2, ('flag-unset trainer params drifted after '\n"
            "    'the async/TPP paths were exercised in-process')\n"
            "print('OK')\n")

    def test_flag_unset_zero_series_spans_plain_tensor(self):
        """In-process: a flag-unset trainer run grows no async-PR
        series, emits no dispatch/* span even with tracing on, keeps a
        single executable, and returns a plain Tensor."""
        from paddle_tpu import nn
        from paddle_tpu.core.tensor import Tensor

        monitor.reset()
        trace.clear()
        trace.enable()
        try:
            paddle.seed(0)
            net = nn.Linear(8, 4)
            opt = paddle.optimizer.SGD(learning_rate=0.1,
                                       parameters=net.parameters())
            mesh = build_mesh((1,), ("dp",), devices=jax.devices()[:1])
            tr = SpmdTrainer(net, opt, loss_fn=nn.MSELoss(), mesh=mesh)
            for _ in range(3):
                out = tr.train_step(np.ones((4, 8), np.float32),
                                    np.zeros((4, 4), np.float32))
        finally:
            trace.disable()
        assert type(out) is Tensor
        reg = monitor.default_registry()
        for family in ASYNC_FAMILIES:
            metric = reg.get(family)
            assert metric is None or all(
                (s.count if hasattr(s, "count") and s.kind == "histogram"
                 else s.value) == 0
                for s in metric.series()), family
        assert not [s.name for s in trace.spans()
                    if s.name.startswith("dispatch/")]
        assert len(tr._compiled_store) == 1
        assert tr._pending_verdicts == []   # no guard, nothing pending
        assert tr._verdict_fetches == 0

    def test_flags_defined_with_defaults(self):
        assert flags.get_flag("async_dispatch") is False
        assert flags.get_flag("async_window") == 8
        assert flags.get_flag("tpp_kernels") is False
        assert flags.get_flag("overlap_grad_comm") is False

    def test_post_hoc_toggle_raises(self):
        from paddle_tpu import nn

        paddle.seed(0)
        net = nn.Linear(4, 2)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=net.parameters())
        mesh = build_mesh((1,), ("dp",), devices=jax.devices()[:1])
        tr = SpmdTrainer(net, opt, loss_fn=nn.MSELoss(), mesh=mesh)
        paddle.set_flags({"async_dispatch": True})
        try:
            with pytest.raises(RuntimeError, match="async_dispatch"):
                tr.train_step(np.ones((2, 4), np.float32),
                              np.zeros((2, 2), np.float32))
        finally:
            paddle.set_flags({"async_dispatch": False})

    def test_overlap_without_quantized_raises(self):
        from paddle_tpu import nn

        paddle.set_flags({"overlap_grad_comm": True})
        try:
            paddle.seed(0)
            net = nn.Linear(4, 2)
            opt = paddle.optimizer.SGD(learning_rate=0.1,
                                       parameters=net.parameters())
            mesh = build_mesh((1,), ("dp",), devices=jax.devices()[:1])
            with pytest.raises(ValueError, match="overlap_grad_comm"):
                SpmdTrainer(net, opt, loss_fn=nn.MSELoss(), mesh=mesh)
        finally:
            paddle.set_flags({"overlap_grad_comm": False})

    def test_chaos_pass_registered(self):
        spec = importlib.util.spec_from_file_location(
            "chaos_check", os.path.join(REPO, "tools", "chaos_check.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert "async_nonfinite" in mod.PASSES


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules.pop(name, None)
    spec.loader.exec_module(mod)
    return mod


class TestAsyncToolGate:
    def test_metrics_dump_async_missing_metrics_exits_1(
            self, capsys, monkeypatch):
        md = _load_tool("metrics_dump")
        monkeypatch.setattr(md, "run_async_loop", lambda **kw: None)
        rc = md.main(["--async", "--json"])
        assert rc == 1
        report = json.loads(capsys.readouterr().out)
        msgs = [f["message"]
                for f in report["targets"]["async"]["findings"]
                if f["pass"] == "metrics-present"]
        assert any("async_verdict_fetch_total" in m for m in msgs)
        assert any("tpp_kernel_calls_total" in m for m in msgs)

    @pytest.mark.slow
    def test_metrics_dump_async_green_subprocess(self):
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools",
                                          "metrics_dump.py"),
             "--async", "--json"],
            cwd=REPO, capture_output=True, text=True, timeout=560)
        assert out.returncode == 0, out.stderr[-2000:]

    @pytest.mark.slow
    def test_parity_async_exact_exits_0(self, capsys):
        """The acceptance-criterion pin: the async-dispatch A/B is
        verified EXACT (zero tolerance, zero divergence)."""
        pc = _load_tool("parity_check")
        rc = pc.main(["--ab", "async_dispatch", "--steps", "2",
                      "--json"])
        assert rc == 0
        report = json.loads(capsys.readouterr().out)
        assert report["totals"]["error"] == 0
        assert report["targets"]["async_dispatch"]["report"][
            "max_abs_loss_diff"] == 0.0

    @pytest.mark.slow
    def test_parity_tpp_with_negative_control(self, capsys):
        """One CI lane, both directions: the TPP target passes its
        declared per-op band AND its lr-perturbed twin diverges (exit
        1) — the band is a gate, not a rubber stamp."""
        pc = _load_tool("parity_check")
        rc = pc.main(["--ab", "tpp_kernels", "--perturb-lr", "8",
                      "--steps", "2", "--json"])
        assert rc == 1
        report = json.loads(capsys.readouterr().out)
        targets = report["targets"]
        assert targets["tpp_kernels"]["counts"]["error"] == 0
        ctrl = targets["tpp_kernels+perturb_lr"]
        assert ctrl["counts"]["error"] == 1
        assert ctrl["report"]["diverged"]

    @pytest.mark.slow
    def test_chaos_async_nonfinite_green(self):
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools",
                                          "chaos_check.py"),
             "--only", "async_nonfinite", "--json"],
            cwd=REPO, capture_output=True, text=True, timeout=560)
        assert out.returncode == 0, out.stderr[-2000:]
        report = json.loads(out.stdout)
        assert report["totals"]["error"] == 0
