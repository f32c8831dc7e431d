"""Tier-1 gate for the perf ledger (ISSUE 17): with FLAGS_perf_ledger
unset, training is EXACTLY the pre-PR path — paddle_tpu.monitor.
perfledger is never imported (subprocess pin), trained params are
byte-identical whether or not the armed ledger was ever exercised in
the same process (the ledger is NON-structural: it observes host-side
timings and joins no executable key), no perf_ledger_rows_total /
perf_regression_total series appears, and the disarmed per-step hook
costs the same one-lookup bar as every other disabled fast path. Plus
the tools/perf_report.py exit-code contract: --check against an empty
ledger is a loud error, --calibrate emits a table plan_search
--calibrated can price with."""
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
LEDGER_FAMILIES = ("perf_ledger_rows_total", "perf_regression_total")


def _tiny_dp():
    from paddle_tpu import nn

    paddle.seed(0)
    net = nn.Linear(8, 4)
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=net.parameters())
    mesh = build_mesh((1,), ("dp",), devices=jax.devices()[:1])
    return SpmdTrainer(net, opt, loss_fn=nn.MSELoss(), mesh=mesh)


_PLAIN_TRAIN = (
    "import os, tempfile\n"
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
    def test_plain_subprocess_never_imports_ledger_and_pins_params(self):
        """The zero-overhead pin, in one subprocess: plain runs (a)
        never import monitor.perfledger, and (b) train byte-identical
        params before vs after an ARMED run in the same process — and
        the armed run itself matches, because the ledger never touches
        the compiled program (non-structural)."""
        _run(
            _PLAIN_TRAIN +
            "h1 = run()\n"
            "import sys\n"
            "assert 'paddle_tpu.monitor.perfledger' not in sys.modules,\\\n"
            "    'perfledger imported on the plain path'\n"
            "path = tempfile.mktemp(suffix='.jsonl')\n"
            "paddle.set_flags({'perf_ledger': True,\n"
            "                  'perf_ledger_path': path,\n"
            "                  'perf_ledger_interval': 1})\n"
            "h_armed = run()\n"
            "assert 'paddle_tpu.monitor.perfledger' in sys.modules\n"
            "from paddle_tpu.monitor import perfledger\n"
            "rows = perfledger.load_rows(path)\n"
            "assert rows and rows[0]['site'] == 'trainer', rows[:1]\n"
            "assert h_armed == h1, ('armed params are not byte-identical'\n"
            "    ' — the ledger leaked into the compiled step')\n"
            "paddle.set_flags({'perf_ledger': False,\n"
            "                  'perf_ledger_path': ''})\n"
            "perfledger.reset_ledger()\n"
            "h2 = run()\n"
            "assert h1 == h2, ('flag-unset params drifted after the '\n"
            "    'armed ledger was exercised in-process')\n"
            "os.unlink(path)\n"
            "print('OK')\n")

    def test_flag_unset_zero_series(self):
        """In-process: a flag-unset run grows no ledger-PR series."""
        monitor.reset()
        tr = _tiny_dp()
        rng = np.random.RandomState(0)
        for _ in range(2):
            tr.train_step(rng.rand(4, 8).astype(np.float32),
                          rng.rand(4, 4).astype(np.float32))
        assert tr._perf_ledger is None
        flat = monitor.flatten(monitor.snapshot())
        # earlier tests in the same process may have left the (zeroed)
        # family registered — drift means a series actually moved
        ledger_series = [k for k, v in flat.items()
                         if k.startswith(LEDGER_FAMILIES) and v]
        assert not ledger_series, ledger_series

    def test_flags_defined_and_default_off(self):
        assert flags.get_flag("perf_ledger") is False
        assert flags.get_flag("perf_ledger_path") == ""
        assert flags.get_flag("perf_ledger_sigma") == 4.0
        assert flags.get_flag("perf_ledger_warmup") == 5
        assert flags.get_flag("perf_ledger_interval") == 1


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules.pop(name, None)
    spec.loader.exec_module(mod)
    return mod


class TestPerfReportGate:
    def test_check_empty_ledger_exits_1(self, capsys, tmp_path):
        """--check against a missing/empty ledger is a loud error
        (perf-ledger-empty), never a silent green."""
        pr = _load_tool("perf_report")
        rc = pr.main(["--check", "--path",
                      str(tmp_path / "missing.jsonl"), "--json"])
        assert rc == 1
        report = json.loads(capsys.readouterr().out)
        msgs = [f for f in report["targets"]["check"]["findings"]
                if f["pass"] == "perf-ledger-empty"]
        assert msgs and msgs[0]["severity"] == "error"

    def test_calibrate_table_prices_plan_search(self, capsys, tmp_path):
        """--calibrate over synthetic rows emits a constants table that
        CostModel(constants=) / plan_search --calibrated can consume."""
        from paddle_tpu.analysis import calibrate
        from paddle_tpu.monitor import perfledger as pl

        path, out = str(tmp_path / "l.jsonl"), str(tmp_path / "t.json")
        env = pl.env_fingerprint()
        for i in range(6):
            pl.append_row(path, {
                "v": pl.SCHEMA_VERSION, "ts": float(i), "site": "trainer",
                "sig": "s", "mesh": None, "env": env,
                "metrics": {"step_ms": 4.0, "exec_ms": 4.0,
                            "flops_per_step": 1e9,
                            "bytes_per_step": 1e8}})
        pr = _load_tool("perf_report")
        rc = pr.main(["--calibrate", "--path", path, "--out", out,
                      "--json"])
        assert rc == 0
        report = json.loads(capsys.readouterr().out)
        assert report["targets"]["calibrate"]["counts"]["error"] == 0
        table = calibrate.load_table(out)
        constants = calibrate.constants_for_cost_model(table)
        # 1e9 flops in 4ms -> 2.5e11 flops/s, exactly
        assert constants["peak_flops"] == pytest.approx(2.5e11)
        assert constants["hbm_bandwidth"] == pytest.approx(2.5e10)
        ps = _load_tool("plan_search")
        report, results = ps.build_report(["gpt"], calibrated=out)
        assert report["totals"]["error"] == 0
        assert report["calibration"]["constants"][
            "peak_flops"] == pytest.approx(2.5e11)
        assert results["gpt"].ranked

    @pytest.mark.slow
    def test_record_then_check_contract_subprocess(self):
        """The acceptance loop, end to end in one subprocess: --record
        appends rows; a clean --check exits 0; a --check with a planted
        in-window slowdown exits 1 and names trainer/step_ms."""
        tool = os.path.join(REPO, "tools", "perf_report.py")
        import tempfile

        path = tempfile.mktemp(suffix=".jsonl")
        try:
            for _ in range(2):
                out = subprocess.run(
                    [sys.executable, tool, "--record", "--steps", "6",
                     "--path", path],
                    cwd=REPO, capture_output=True, text=True,
                    timeout=560)
                assert out.returncode == 0, out.stderr[-2000:]
            out = subprocess.run(
                [sys.executable, tool, "--check", "--steps", "6",
                 "--path", path, "--json"],
                cwd=REPO, capture_output=True, text=True, timeout=560)
            assert out.returncode == 0, \
                out.stdout[-2000:] + out.stderr[-2000:]
            out = subprocess.run(
                [sys.executable, tool, "--check", "--steps", "6",
                 "--path", path, "--inject", "trainer/batch=delay:400",
                 "--json"],
                cwd=REPO, capture_output=True, text=True, timeout=560)
            assert out.returncode == 1, \
                out.stdout[-2000:] + out.stderr[-2000:]
            report = json.loads(out.stdout)
            msgs = [f["message"]
                    for f in report["targets"]["check"]["findings"]
                    if f["pass"] == "perf-regression"]
            assert any("trainer/step_ms" in m for m in msgs), msgs
        finally:
            if os.path.exists(path):
                os.unlink(path)

    @pytest.mark.slow
    def test_metrics_dump_ledger_green_subprocess(self):
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools",
                                          "metrics_dump.py"),
             "--ledger", "--json"],
            cwd=REPO, capture_output=True, text=True, timeout=560)
        assert out.returncode == 0, out.stderr[-2000:]
        report = json.loads(out.stdout)
        assert report["totals"]["error"] == 0
