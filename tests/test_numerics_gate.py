"""Tier-1 gate for the numerics telescope (ISSUE 9): with FLAGS_numerics
unset the trainer is EXACTLY the pre-PR trainer — the compiled step is
byte-identical (params bit-equal across processes that did / did not
ever exercise the telescope), paddle_tpu.monitor.numerics is never even
imported, no numerics_* metric series or numerics/fetch span appears,
and the per-step overhead is the same one-boolean-check bar as the
monitor/failpoints/trace/blackbox fast paths. Plus: the
tools/metrics_dump.py --numerics and tools/parity_check.py exit-code
contracts are pinned."""
import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import flags, monitor, trace
from paddle_tpu.testing import failpoints

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

#: metric families this PR introduced — with the flag unset NONE of them
#: may grow a series on the trainer path
NUMERICS_FAMILIES = (
    "numerics_grad_norm", "numerics_param_norm", "numerics_update_ratio",
    "numerics_grad_rms", "numerics_grad_absmax", "numerics_loss",
    "numerics_nonfinite_total", "numerics_anomaly_total",
    "numerics_fetch_ms")

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
    def test_plain_subprocess_never_imports_numerics_and_pins_params(
            self):
        """The structural zero-overhead pin, cross-process: a plain
        trainer run (a) never imports the telescope module and (b)
        produces byte-identical params whether or not the telescope was
        ever armed earlier in the process."""
        plain = _run(
            _PLAIN_TRAINER +
            "digest = run_plain()\n"
            "import sys\n"
            "bad = [k for k in sys.modules\n"
            "       if k == 'paddle_tpu.monitor.numerics'\n"
            "       or k == 'paddle_tpu.testing.parity']\n"
            "assert not bad, f'telescope imported eagerly: {bad}'\n"
            "print('DIGEST', digest)\n")
        exercised = _run(
            _PLAIN_TRAINER +
            # arm the telescope, run a DIFFERENT trainer under it, then
            # disarm — the plain run after must be bit-identical to the
            # never-armed process's
            "paddle.set_flags({'numerics': True,\n"
            "                  'numerics_interval': 1})\n"
            "paddle.seed(1)\n"
            "net2 = nn.Linear(4, 2)\n"
            "opt2 = paddle.optimizer.SGD(learning_rate=0.1,\n"
            "    parameters=net2.parameters())\n"
            "mesh2 = build_mesh((1,), ('dp',), devices=jax.devices()[:1])\n"
            "tr2 = SpmdTrainer(net2, opt2, loss_fn=nn.MSELoss(),\n"
            "                  mesh=mesh2)\n"
            "tr2.train_step(np.ones((2, 4), np.float32),\n"
            "               np.zeros((2, 2), np.float32))\n"
            "assert tr2.stats()['numerics'] is not None\n"
            "paddle.set_flags({'numerics': False})\n"
            "print('DIGEST', run_plain())\n")
        d1 = plain.split("DIGEST ")[1].split()[0]
        d2 = exercised.split("DIGEST ")[1].split()[0]
        assert d1 == d2, (
            "flag-unset trainer params drifted after the telescope was "
            "exercised in-process — the disarmed step is not the pre-PR "
            "step")

    def test_flag_unset_zero_series_and_spans(self):
        """In-process form: a flag-unset trainer run moves no numerics_*
        series and emits no numerics/fetch span even with tracing on."""
        import jax

        from paddle_tpu import nn
        from paddle_tpu.distributed.mesh import build_mesh
        from paddle_tpu.distributed.spmd import SpmdTrainer

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
            for _ in range(2):
                tr.train_step(np.ones((4, 8), np.float32),
                              np.zeros((4, 4), np.float32))
        finally:
            trace.disable()
        reg = monitor.default_registry()
        for family in NUMERICS_FAMILIES:
            metric = reg.get(family)
            assert metric is None or all(
                (s.count if hasattr(s, "count") and s.kind == "histogram"
                 else s.value) == 0
                for s in metric.series()), family
        assert "numerics/fetch" not in {s.name for s in trace.spans()}
        assert tr.stats()["numerics"] is None
        # the trainer's own span family is intact
        assert "train_step" in {s.name for s in trace.spans()}

    def test_lazy_attrs_not_star_exported(self):
        """The lazy numerics/parity attributes must stay OUT of
        __all__ — `from ... import *` resolves every listed name, which
        would import the telescope in a plain process."""
        import paddle_tpu.monitor as mon
        import paddle_tpu.testing as testing_pkg

        assert "numerics" not in mon.__all__
        assert "parity" not in testing_pkg.__all__

    def test_define_flag_preserves_pre_set_values(self):
        """Detector flags live in the lazily-imported module: a
        set_flags() made BEFORE that import must survive the module's
        own define_flag calls."""
        probe = "numerics_gate_probe_flag"
        try:
            paddle.set_flags({probe: 17})
            assert flags.define_flag(probe, 3, "probe") == 17
            assert flags.get_flag(probe) == 17
            assert flags._REGISTRY[probe]["default"] == 3
        finally:
            flags._REGISTRY.pop(probe, None)

    def test_registrations(self):
        """The trainer/batch site and the scale action are registered;
        arming a typo still fails fast."""
        assert "trainer/batch" in failpoints.SITES
        failpoints.arm("trainer/batch", "scale:2")
        try:
            assert failpoints.armed() == {"trainer/batch": "scale:2"}
        finally:
            failpoints.reset()
        with pytest.raises(ValueError):
            failpoints.arm("trainer/batch", "scale")
        assert flags.get_flag("numerics") is not None   # flag defined
        assert flags.get_flag("numerics_interval") == 1


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules.pop(name, None)
    spec.loader.exec_module(mod)
    return mod


class TestNumericsToolGate:
    def test_metrics_dump_numerics_missing_metrics_exits_1(
            self, capsys, monkeypatch):
        md = _load_tool("metrics_dump")
        monkeypatch.setattr(md, "run_numerics_loop", lambda **kw: None)
        rc = md.main(["--numerics", "--json"])
        assert rc == 1
        report = json.loads(capsys.readouterr().out)
        missing = {f["message"].split("'")[1]
                   for f in report["targets"]["numerics"]["findings"]
                   if f["pass"] == "metrics-present"}
        assert "numerics_grad_norm" in missing
        assert "numerics_anomaly_total" in missing

    @pytest.mark.slow
    def test_metrics_dump_numerics_green_subprocess(self):
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools",
                                          "metrics_dump.py"),
             "--numerics", "--json"],
            cwd=REPO, capture_output=True, text=True, timeout=560)
        assert out.returncode == 0, out.stderr[-2000:]

    def test_parity_check_identical_ab_exits_0(self, capsys):
        """The acceptance-criterion pin: an identical-config A/B (the
        PR 4 guard's bit-exact contract) exits 0."""
        pc = _load_tool("parity_check")
        rc = pc.main(["--ab", "check_nan_inf", "--steps", "2", "--json"])
        assert rc == 0
        report = json.loads(capsys.readouterr().out)
        assert report["tool"] == "parity_check"
        assert report["totals"]["error"] == 0
        assert report["targets"]["check_nan_inf"]["report"][
            "max_abs_loss_diff"] == 0.0

    def test_parity_check_injected_divergence_exits_1_naming_stat(
            self, capsys):
        pc = _load_tool("parity_check")
        rc = pc.main(["--perturb-lr", "8", "--steps", "2", "--json"])
        assert rc == 1
        report = json.loads(capsys.readouterr().out)
        errs = [f for f in report["targets"]["perturb_lr"]["findings"]
                if f["severity"] == "error"]
        assert errs and "step" in errs[0]["message"]
        d = report["targets"]["perturb_lr"]["report"]["first_divergence"]
        assert d is not None and d["stat"]
        assert d["stat"] in errs[0]["message"]

    def test_parity_check_no_target_is_an_error(self):
        pc = _load_tool("parity_check")
        with pytest.raises(SystemExit):
            pc.main(["--json"])

    def test_chaos_numerics_pass_registered(self):
        cc = _load_tool("chaos_check")
        assert "numerics_anomaly" in cc.PASSES
