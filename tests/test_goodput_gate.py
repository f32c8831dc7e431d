"""Tier-1 gate for the goodput ledger + weight-version lineage (ISSUE
20): with FLAGS_goodput unset, training is EXACTLY the pre-PR path —
paddle_tpu.monitor.goodput is never imported (subprocess pin), trained
params are byte-identical whether or not an armed run was ever
exercised in the same process (the accountant is NON-structural: it
books host-side wall clock and joins no executable key), no
goodput_seconds_total / goodput_fraction / serving_* series appears,
and the disarmed per-step hook costs the same one-lookup bar as every
other disabled fast path. Plus the tool contracts: metrics_dump
--goodput and the chaos goodput_attribution pass exit 0."""
import importlib.util
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import flags, monitor
from paddle_tpu.distributed.mesh import build_mesh
from paddle_tpu.distributed.spmd import SpmdTrainer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

#: metric families this PR introduced — with the flag unset NONE may move
GOODPUT_FAMILIES = ("goodput_seconds_total", "goodput_fraction",
                    "serving_weight_version",
                    "serving_stale_sessions_total")


def _tiny_dp():
    from paddle_tpu import nn

    paddle.seed(0)
    net = nn.Linear(8, 4)
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=net.parameters())
    mesh = build_mesh((1,), ("dp",), devices=jax.devices()[:1])
    return SpmdTrainer(net, opt, loss_fn=nn.MSELoss(), mesh=mesh)


_PLAIN_TRAIN = (
    "import os\n"
    "os.environ.setdefault('XLA_FLAGS',\n"
    "    '--xla_force_host_platform_device_count=8')\n"
    "import jax; jax.config.update('jax_platforms', 'cpu')\n"
    "import hashlib\n"
    "import numpy as np\n"
    "import paddle_tpu as paddle\n"
    "from paddle_tpu import nn\n"
    "from paddle_tpu.distributed.mesh import build_mesh\n"
    "from paddle_tpu.distributed.spmd import SpmdTrainer\n"
    "def run():\n"
    "    paddle.seed(0)\n"
    "    net = nn.Linear(8, 4)\n"
    "    opt = paddle.optimizer.SGD(learning_rate=0.1,\n"
    "                               parameters=net.parameters())\n"
    "    mesh = build_mesh((1,), ('dp',), devices=jax.devices()[:1])\n"
    "    tr = SpmdTrainer(net, opt, loss_fn=nn.MSELoss(), mesh=mesh)\n"
    "    rng = np.random.RandomState(0)\n"
    "    for _ in range(3):\n"
    "        tr.train_step(rng.rand(4, 8).astype(np.float32),\n"
    "                      rng.rand(4, 4).astype(np.float32))\n"
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
    def test_plain_subprocess_never_imports_goodput_and_pins_params(self):
        """The zero-overhead pin, in one subprocess: plain runs (a)
        never import monitor.goodput, and (b) train byte-identical
        params before vs after an ARMED run in the same process — and
        the armed run itself matches, because the accountant never
        touches the compiled program (non-structural)."""
        _run(
            _PLAIN_TRAIN +
            "h1 = run()\n"
            "import sys\n"
            "assert 'paddle_tpu.monitor.goodput' not in sys.modules,\\\n"
            "    'goodput imported on the plain path'\n"
            "paddle.set_flags({'goodput': True})\n"
            "h_armed = run()\n"
            "assert 'paddle_tpu.monitor.goodput' in sys.modules\n"
            "from paddle_tpu.monitor import goodput\n"
            "run_obj = goodput.current_run()\n"
            "assert run_obj is not None and \\\n"
            "    run_obj.buckets['step'] > 0, 'armed run booked no step'\n"
            "assert h_armed == h1, ('armed params are not byte-identical'\n"
            "    ' — the accountant leaked into the compiled step')\n"
            "goodput.reset()\n"
            "paddle.set_flags({'goodput': False})\n"
            "h2 = run()\n"
            "assert h1 == h2, ('flag-unset params drifted after the '\n"
            "    'armed accountant was exercised in-process')\n"
            "print('OK')\n")

    def test_flag_unset_zero_series(self):
        """In-process: a flag-unset run grows no goodput-PR series."""
        monitor.reset()
        tr = _tiny_dp()
        rng = np.random.RandomState(0)
        for _ in range(2):
            tr.train_step(rng.rand(4, 8).astype(np.float32),
                          rng.rand(4, 4).astype(np.float32))
        assert tr._goodput is None
        flat = monitor.flatten(monitor.snapshot())
        # earlier tests in the same process may have left the (zeroed)
        # family registered — drift means a series actually moved
        goodput_series = [k for k, v in flat.items()
                          if k.startswith(GOODPUT_FAMILIES) and v]
        assert not goodput_series, goodput_series

    def test_flags_defined_and_default_off(self):
        assert flags.get_flag("goodput") is False
        assert flags.get_flag("goodput_stall_s") == 2.0

    def test_weight_version_minted_without_flag(self):
        """Lineage is always on (it is metadata, not accounting): a
        plain trainer mints version 0/init and bumps per applied step
        with origin `step` — no goodput import involved."""
        tr = _tiny_dp()
        assert tr.weight_version.counter == 0
        assert tr.weight_version.origin == "init"
        rng = np.random.RandomState(0)
        tr.train_step(rng.rand(4, 8).astype(np.float32),
                      rng.rand(4, 4).astype(np.float32))
        assert tr.weight_version.counter == 1
        assert tr.weight_version.origin == "step"


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules.pop(name, None)
    spec.loader.exec_module(mod)
    return mod


class TestGoodputToolGates:
    def test_perf_report_goodput_empty_ledger_exits_1(self, capsys,
                                                      tmp_path):
        """--goodput against a ledger with no run/goodput rows is a loud
        error, never a silent green."""
        pr = _load_tool("perf_report")
        rc = pr.main(["--goodput", "--path",
                      str(tmp_path / "missing.jsonl"), "--json"])
        assert rc == 1
        report = json.loads(capsys.readouterr().out)
        msgs = [f for f in report["targets"]["goodput"]["findings"]
                if f["pass"] == "perf-ledger-empty"]
        assert msgs and msgs[0]["severity"] == "error"

    @pytest.mark.slow
    def test_metrics_dump_goodput_green_subprocess(self):
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools",
                                          "metrics_dump.py"),
             "--goodput", "--json"],
            cwd=REPO, capture_output=True, text=True, timeout=560)
        assert out.returncode == 0, out.stderr[-2000:]
        report = json.loads(out.stdout)
        assert report["totals"]["error"] == 0

    @pytest.mark.slow
    def test_chaos_goodput_attribution_green_subprocess(self):
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools",
                                          "chaos_check.py"),
             "--only", "goodput_attribution", "--json"],
            cwd=REPO, capture_output=True, text=True, timeout=560)
        assert out.returncode == 0, \
            out.stdout[-2000:] + out.stderr[-2000:]
        report = json.loads(out.stdout)
        assert report["totals"]["error"] == 0
        msgs = [f["message"] for t in report["targets"].values()
                for f in t["findings"]
                if f["pass"] == "goodput_attribution"]
        assert msgs and "kill time" in msgs[0], msgs
