"""Tier-1 gate for the MPMD stage-program runtime (ISSUE 15): with
FLAGS_mpmd unset, PipelineTrainer and DisaggregatedPool are EXACTLY the
pre-PR runtimes — paddle_tpu.distributed.stage is never imported
(subprocess pin), pipeline params and pool completions are byte-identical
whether or not the armed MPMD path was ever exercised in-process, no
stage_graph/stage_step span and no {op=stage_edge} series appears, the
flag is joined into the dp trainer's _exec_key (and AOT extra_key) so an
armed world can never alias a disarmed executable, the disarmed per-step
flag checks cost the same one-lookup bar as every other disabled fast
path, and a post-construction toggle raises instead of silently
re-basing a live runtime. Plus: the tools/metrics_dump.py --mpmd,
tools/parity_check.py mpmd_* targets, and tools/chaos_check.py
stage_backpressure exit-code contracts."""
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
from paddle_tpu.distributed.pipeline import PipelineTrainer
from paddle_tpu.distributed.spmd import SpmdTrainer
from paddle_tpu.models import GPTConfig, GPTForCausalLM

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

#: span names this PR introduced — with the flag unset NONE may appear
STAGE_SPANS = ("stage_graph", "stage_step")


def _tiny_pipeline(**kw):
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                    num_heads=2, max_seq_len=32, dropout=0.0)
    model = GPTForCausalLM(cfg)
    pre, stages, post = model.pipeline_split(2)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    mesh = build_mesh((2,), ("pp",), devices=jax.devices()[:2])
    return PipelineTrainer(pre, stages, post, opt, mesh=mesh, n_micro=2,
                           schedule_mode="1F1B", **kw)


_PLAIN_RUNTIMES = (
    "import os\n"
    "os.environ.setdefault('XLA_FLAGS',\n"
    "    '--xla_force_host_platform_device_count=8')\n"
    "import jax; jax.config.update('jax_platforms', 'cpu')\n"
    "import hashlib\n"
    "import numpy as np\n"
    "import paddle_tpu as paddle\n"
    "from paddle_tpu.distributed.mesh import build_mesh\n"
    "from paddle_tpu.distributed.pipeline import PipelineTrainer\n"
    "from paddle_tpu.models import GPTConfig, GPTForCausalLM\n"
    "from paddle_tpu.serving.disagg import DisaggregatedPool\n"
    "def build_pipe(**kw):\n"
    "    paddle.seed(0)\n"
    "    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,\n"
    "                    num_heads=2, max_seq_len=32, dropout=0.0)\n"
    "    model = GPTForCausalLM(cfg)\n"
    "    pre, stages, post = model.pipeline_split(2)\n"
    "    opt = paddle.optimizer.AdamW(learning_rate=1e-3,\n"
    "        parameters=model.parameters())\n"
    "    mesh = build_mesh((2,), ('pp',), devices=jax.devices()[:2])\n"
    "    return PipelineTrainer(pre, stages, post, opt, mesh=mesh,\n"
    "                           n_micro=2, schedule_mode='1F1B', **kw)\n"
    "def run_pipe(**kw):\n"
    "    tr = build_pipe(**kw)\n"
    "    rng = np.random.RandomState(0)\n"
    "    for _ in range(2):\n"
    "        tr.train_step(rng.randint(0, 64, (4, 16)).astype(np.int32),\n"
    "                      rng.randint(0, 64, (4, 16)).astype(np.int32))\n"
    "    h = hashlib.sha256()\n"
    "    for k in sorted(tr.params):\n"
    "        h.update(np.ascontiguousarray(\n"
    "            np.asarray(tr.params[k])).tobytes())\n"
    "    return h.hexdigest()\n"
    "def run_pool(**kw):\n"
    "    paddle.seed(0)\n"
    "    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=1,\n"
    "                    num_heads=2, max_seq_len=64, dropout=0.0)\n"
    "    m = GPTForCausalLM(cfg)\n"
    "    m.eval()\n"
    "    rng = np.random.RandomState(0)\n"
    "    pool = DisaggregatedPool(m, prefill_workers=1,\n"
    "                             decode_engines=1, max_batch=2, **kw)\n"
    "    rids = [pool.submit(rng.randint(0, 64, (n,)).astype(np.int32),\n"
    "                        max_new_tokens=5) for n in (5, 8)]\n"
    "    res = pool.run_until_complete()\n"
    "    return tuple(tuple(int(t) for t in res[r].tokens)\n"
    "                 for r in rids)\n")


def _run(code):
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout


class TestInertByDefault:
    @pytest.mark.slow
    def test_plain_subprocess_never_imports_stage_and_pins_outputs(self):
        """The structural zero-overhead pin, in one subprocess: plain
        pipeline + pool runs (a) never import distributed.stage, and
        (b) produce byte-identical params/completions before vs after
        armed MPMD runs of BOTH runtimes in the same process — the
        disarmed step is the pre-PR step, unpolluted by the armed
        path."""
        _run(
            _PLAIN_RUNTIMES +
            "d1 = run_pipe()\n"
            "c1 = run_pool()\n"
            "import sys\n"
            "assert 'paddle_tpu.distributed.stage' not in sys.modules, \\\n"
            "    'stage imported on the plain path'\n"
            "paddle.set_flags({'mpmd': True})\n"
            "run_pipe()\n"
            "c_armed = run_pool()\n"
            "run_pool(compress=8)\n"
            "assert 'paddle_tpu.distributed.stage' in sys.modules\n"
            "assert c_armed == c1, ('armed pool completions are not '\n"
            "    'byte-identical to the monolithic hand-off')\n"
            "paddle.set_flags({'mpmd': False})\n"
            "d2 = run_pipe()\n"
            "c2 = run_pool()\n"
            "assert d1 == d2, ('flag-unset pipeline params drifted after '\n"
            "    'the MPMD path was exercised in-process')\n"
            "assert c1 == c2, ('flag-unset pool completions drifted '\n"
            "    'after the MPMD path was exercised in-process')\n"
            "print('OK')\n")

    def test_flag_unset_zero_series_spans_and_no_runner(self):
        """In-process: a flag-unset pipeline run grows no stage-PR
        series, emits no stage_graph/stage_step span even with tracing
        on, and constructs no MPMD runner or edge objects."""
        monitor.reset()
        trace.clear()
        trace.enable()
        try:
            tr = _tiny_pipeline()
            rng = np.random.RandomState(0)
            for _ in range(2):
                tr.train_step(rng.randint(0, 64, (4, 16)).astype(np.int32),
                              rng.randint(0, 64, (4, 16)).astype(np.int32))
        finally:
            trace.disable()
        assert tr._mpmd_runner is None
        names = {s.name for s in trace.spans()}
        for span in STAGE_SPANS:
            assert span not in names, span
        flat = monitor.flatten(monitor.snapshot())
        # earlier tests in the same process may have left the (zeroed)
        # family registered — drift means a series actually moved
        stage_series = [k for k, v in flat.items()
                        if ("op=stage_edge" in k
                            or k.startswith("kv_handoff_bytes_total")) and v]
        assert not stage_series, stage_series

    def test_mpmd_joined_into_exec_key(self, monkeypatch):
        """The flag is part of the dp trainer's executable identity: the
        leg of _exec_key that _mpmd_active() feeds is False on a disarmed
        trainer, True on an armed twin, and the keys differ ONLY in that
        leg — an armed world can never alias a disarmed executable."""
        from paddle_tpu import nn

        batch = (np.ones((4, 8), np.float32), np.zeros((4, 4), np.float32))

        def one_step():
            paddle.seed(0)
            net = nn.Linear(8, 4)
            opt = paddle.optimizer.SGD(learning_rate=0.1,
                                       parameters=net.parameters())
            mesh = build_mesh((1,), ("dp",), devices=jax.devices()[:1])
            tr = SpmdTrainer(net, opt, loss_fn=nn.MSELoss(), mesh=mesh)
            tr.train_step(*batch)
            return tr, next(iter(tr._compiled_store))

        tr, plain_key = one_step()
        # which leg is the mpmd one: the leg that moves with _mpmd_active
        monkeypatch.setattr(tr, "_mpmd_active", lambda: "the mpmd leg")
        (leg,) = [i for i, (a, b) in enumerate(
            zip(plain_key, tr._exec_key(batch))) if a != b]
        paddle.set_flags({"mpmd": True})
        try:
            _, armed_key = one_step()
        finally:
            paddle.set_flags({"mpmd": False})
        assert plain_key[leg] is False and armed_key[leg] is True
        assert len(plain_key) == len(armed_key)
        assert [i for i, (a, b) in enumerate(zip(plain_key, armed_key))
                if a != b] == [leg]

    def test_post_construction_toggle_raises(self):
        """FLAGS_mpmd is consumed at construction: flipping it under a
        live disarmed trainer raises instead of silently re-basing the
        schedule onto stage programs mid-run."""
        tr = _tiny_pipeline()
        rng = np.random.RandomState(0)
        x = rng.randint(0, 64, (4, 16)).astype(np.int32)
        paddle.set_flags({"mpmd": True})
        try:
            with pytest.raises(RuntimeError, match="FLAGS_mpmd"):
                tr.train_step(x, x)
        finally:
            paddle.set_flags({"mpmd": False})

    def test_edge_options_require_the_flag(self):
        """stage_meshes/compress are MPMD edge options: passing them to
        a disarmed trainer is a loud error, not a silent no-op."""
        with pytest.raises(ValueError, match="mpmd"):
            _tiny_pipeline(compress=8)

    def test_flags_defined_and_default_off(self):
        assert flags.get_flag("mpmd") is False

    def test_chaos_pass_registered(self):
        spec = importlib.util.spec_from_file_location(
            "chaos_check", os.path.join(REPO, "tools", "chaos_check.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert "stage_backpressure" in mod.PASSES


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules.pop(name, None)
    spec.loader.exec_module(mod)
    return mod


class TestStageToolGate:
    def test_metrics_dump_mpmd_missing_metrics_exits_1(
            self, capsys, monkeypatch):
        md = _load_tool("metrics_dump")
        monkeypatch.setattr(md, "run_mpmd_loop", lambda **kw: None)
        rc = md.main(["--mpmd", "--json"])
        assert rc == 1
        report = json.loads(capsys.readouterr().out)
        msgs = [f["message"]
                for f in report["targets"]["mpmd"]["findings"]
                if f["pass"] == "metrics-present"]
        assert any("kv_handoff_bytes_total" in m for m in msgs)
        assert any("op=stage_edge" in m for m in msgs)

    @pytest.mark.slow
    def test_metrics_dump_mpmd_green_subprocess(self):
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools",
                                          "metrics_dump.py"),
             "--mpmd", "--json"],
            cwd=REPO, capture_output=True, text=True, timeout=560)
        assert out.returncode == 0, out.stderr[-2000:]

    @pytest.mark.slow
    def test_parity_mpmd_pipeline_exact_with_negative_control(
            self, capsys):
        """One CI lane, both directions: the acceptance-criterion pin —
        the armed 1F1B trajectory is EXACT (zero divergence) — AND its
        lr-perturbed twin diverges (exit 1), so the band is a gate, not
        a rubber stamp."""
        pc = _load_tool("parity_check")
        rc = pc.main(["--ab", "mpmd_pipeline", "--perturb-lr", "8",
                      "--steps", "2", "--json"])
        assert rc == 1
        report = json.loads(capsys.readouterr().out)
        targets = report["targets"]
        assert targets["mpmd_pipeline"]["counts"]["error"] == 0
        assert targets["mpmd_pipeline"]["report"][
            "max_abs_loss_diff"] == 0.0
        ctrl = targets["mpmd_pipeline+perturb_lr"]
        assert ctrl["counts"]["error"] == 1
        assert ctrl["report"]["diverged"]

    @pytest.mark.slow
    def test_parity_mpmd_quantized_edge_within_band(self, capsys):
        """The compress=8 activation edge trains inside its declared
        band against the unquantized armed reference."""
        pc = _load_tool("parity_check")
        rc = pc.main(["--ab", "mpmd_quantized_edge", "--steps", "2",
                      "--json"])
        assert rc == 0
        report = json.loads(capsys.readouterr().out)
        assert report["totals"]["error"] == 0

    @pytest.mark.slow
    def test_chaos_stage_backpressure_green(self):
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools",
                                          "chaos_check.py"),
             "--only", "stage_backpressure", "--json"],
            cwd=REPO, capture_output=True, text=True, timeout=560)
        assert out.returncode == 0, out.stderr[-2000:]
        report = json.loads(out.stdout)
        assert report["totals"]["error"] == 0
