"""Tier-1 gate for the bandwidth-frugal dp stack (ISSUE 10): with
FLAGS_quantized_allreduce and FLAGS_shard_weight_update both unset, the
trainer is EXACTLY the pre-PR trainer — paddle_tpu.distributed.compress
is never imported (subprocess pin), params are byte-identical whether or
not the compressed path was ever exercised in-process, no
collective_bytes_saved_total / quantize_error_norm series or
collective/quantized span appears, one executable serves the whole run
(zero recompile drift), and the per-step flag checks cost the same
one-lookup bar as every other disabled fast path. Plus: the
tools/metrics_dump.py --quantized, tools/parity_check.py target, and
tools/chaos_check.py quantized_nonfinite exit-code contracts."""
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
COMPRESS_FAMILIES = ("collective_bytes_saved_total", "quantize_error_norm")

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
    def test_plain_subprocess_never_imports_compress_and_pins_params(
            self):
        """The structural zero-overhead pin, in one subprocess: a plain
        trainer run (a) never imports distributed.compress, and (b)
        produces byte-identical params before vs after a quantized +
        update-sharded trainer ran in the same process — the disarmed
        step is the pre-PR step, unpolluted by the armed path."""
        _run(
            _PLAIN_TRAINER +
            "d1 = run_plain()\n"
            "import sys\n"
            "assert 'paddle_tpu.distributed.compress' not in \\\n"
            "    sys.modules, 'compress imported on the plain path'\n"
            "paddle.set_flags({'quantized_allreduce': True,\n"
            "    'quantized_allreduce_min_size': 1,\n"
            "    'shard_weight_update': True})\n"
            "paddle.seed(1)\n"
            "net2 = nn.Linear(4, 2)\n"
            "opt2 = paddle.optimizer.SGD(learning_rate=0.1,\n"
            "    parameters=net2.parameters())\n"
            "mesh2 = build_mesh((1,), ('dp',), devices=jax.devices()[:1])\n"
            "tr2 = SpmdTrainer(net2, opt2, loss_fn=nn.MSELoss(),\n"
            "                  mesh=mesh2)\n"
            "tr2.train_step(np.ones((2, 4), np.float32),\n"
            "               np.zeros((2, 2), np.float32))\n"
            "assert tr2.quantize_error() is not None\n"
            "assert 'paddle_tpu.distributed.compress' in sys.modules\n"
            "paddle.set_flags({'quantized_allreduce': False,\n"
            "                  'shard_weight_update': False})\n"
            "d2 = run_plain()\n"
            "assert d1 == d2, ('flag-unset trainer params drifted after '\n"
            "    'the compressed path was exercised in-process')\n"
            "print('OK')\n")

    def test_flag_unset_zero_series_spans_and_recompiles(self):
        """In-process: a flag-unset trainer run grows no compress-PR
        series, emits no collective/quantized span even with tracing on,
        and one executable serves every step (no exec-key churn)."""
        from paddle_tpu import nn

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
                tr.train_step(np.ones((4, 8), np.float32),
                              np.zeros((4, 4), np.float32))
        finally:
            trace.disable()
        reg = monitor.default_registry()
        for family in COMPRESS_FAMILIES:
            metric = reg.get(family)
            assert metric is None or all(
                (s.count if hasattr(s, "count") and s.kind == "histogram"
                 else s.value) == 0
                for s in metric.series()), family
        assert "collective/quantized" not in {s.name
                                              for s in trace.spans()}
        assert len(tr._compiled_store) == 1
        key = next(iter(tr._compiled_store))
        assert key[-2:] == (False, False)   # the two new exec-key legs
        assert tr.stats()["quantize_error_norm"] is None
        assert "__qar_residual__" not in tr.opt_state

    def test_flags_defined_and_read_at_ctor(self):
        assert flags.get_flag("quantized_allreduce") is False
        assert flags.get_flag("shard_weight_update") is False
        assert flags.get_flag("quantized_allreduce_bits") == 8
        assert flags.get_flag("quantized_allreduce_min_size") == 1024

    def test_chaos_pass_registered(self):
        spec = importlib.util.spec_from_file_location(
            "chaos_check", os.path.join(REPO, "tools", "chaos_check.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert "quantized_nonfinite" in mod.PASSES


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules.pop(name, None)
    spec.loader.exec_module(mod)
    return mod


class TestCompressToolGate:
    def test_metrics_dump_quantized_missing_metrics_exits_1(
            self, capsys, monkeypatch):
        md = _load_tool("metrics_dump")
        monkeypatch.setattr(md, "run_quantized_loop", lambda **kw: None)
        rc = md.main(["--quantized", "--json"])
        assert rc == 1
        report = json.loads(capsys.readouterr().out)
        msgs = [f["message"]
                for f in report["targets"]["quantized"]["findings"]
                if f["pass"] == "metrics-present"]
        assert any("collective_bytes_saved_total" in m for m in msgs)
        assert any("op=quantized_all_reduce" in m for m in msgs)

    @pytest.mark.slow
    def test_metrics_dump_quantized_green_subprocess(self):
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools",
                                          "metrics_dump.py"),
             "--quantized", "--json"],
            cwd=REPO, capture_output=True, text=True, timeout=560)
        assert out.returncode == 0, out.stderr[-2000:]

    @pytest.mark.slow
    def test_parity_shard_weight_update_exact_exits_0(self, capsys):
        """The acceptance-criterion pin: the update-sharding A/B is
        verified EXACT (zero tolerance, zero divergence)."""
        pc = _load_tool("parity_check")
        rc = pc.main(["--ab", "shard_weight_update", "--steps", "2",
                      "--json"])
        assert rc == 0
        report = json.loads(capsys.readouterr().out)
        assert report["totals"]["error"] == 0
        assert report["targets"]["shard_weight_update"]["report"][
            "max_abs_loss_diff"] == 0.0

    @pytest.mark.slow
    def test_parity_quantized_with_negative_control(self, capsys):
        """One CI lane, both directions: the quantized target passes its
        declared band AND its lr-perturbed twin diverges (exit 1) —
        the band is a gate, not a rubber stamp."""
        pc = _load_tool("parity_check")
        rc = pc.main(["--ab", "quantized_allreduce", "--perturb-lr",
                      "8", "--steps", "2", "--json"])
        assert rc == 1
        report = json.loads(capsys.readouterr().out)
        targets = report["targets"]
        assert targets["quantized_allreduce"]["counts"]["error"] == 0
        ctrl = targets["quantized_allreduce+perturb_lr"]
        assert ctrl["counts"]["error"] == 1
        assert ctrl["report"]["diverged"]

    @pytest.mark.slow
    def test_chaos_quantized_nonfinite_green(self):
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools",
                                          "chaos_check.py"),
             "--only", "quantized_nonfinite", "--json"],
            cwd=REPO, capture_output=True, text=True, timeout=560)
        assert out.returncode == 0, out.stderr[-2000:]
        report = json.loads(out.stdout)
        assert report["totals"]["error"] == 0
