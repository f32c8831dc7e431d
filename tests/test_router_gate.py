"""Tier-1 router gate: the multi-engine tier costs a plain single-engine
deployment NOTHING when no Router/DisaggregatedPool is constructed.

Pins (ISSUE 6 satellite):
 - constructing + running a plain ServingEngine never imports
   serving/router.py or serving/disagg.py (lazy package surface);
 - a plain engine run leaves ZERO router/kv_handoff metric series and
   ZERO route/kv_handoff spans;
 - the engine's idle step() stays host-cheap (the handoff queue adds one
   empty-list truthiness check);
 - tools/{trace_dump,metrics_dump}.py --router exit 1 when the router
   span/metric families are missing (the CI contract in executable form).
"""
import importlib.util
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor, trace
from paddle_tpu.inference.serving import ServingEngine
from paddle_tpu.models import GPTConfig, GPTForCausalLM

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _model():
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=1,
                    num_heads=2, max_seq_len=64, dropout=0.0)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


class TestZeroOverheadSingleEngine:
    def test_plain_engine_never_imports_router(self):
        """The structural form of 'zero overhead': no Router constructed
        -> the router/disagg modules are never even imported (and with
        them, none of their metric registrations)."""
        code = (
            "import jax; jax.config.update('jax_platforms', 'cpu')\n"
            "import numpy as np\n"
            "import paddle_tpu as paddle\n"
            "from paddle_tpu.inference.serving import ServingEngine\n"
            "from paddle_tpu.models import GPTConfig, GPTForCausalLM\n"
            "paddle.seed(0)\n"
            "m = GPTForCausalLM(GPTConfig(vocab_size=64, hidden_size=32,\n"
            "    num_layers=1, num_heads=2, max_seq_len=32, dropout=0.0))\n"
            "m.eval()\n"
            "eng = ServingEngine(m, max_batch=1)\n"
            "eng.submit(np.arange(4, dtype=np.int32), max_new_tokens=2)\n"
            "eng.run_until_complete()\n"
            "import sys\n"
            "bad = [k for k in sys.modules if k in (\n"
            "    'paddle_tpu.serving.router', 'paddle_tpu.serving.disagg')]\n"
            "assert not bad, f'router tier imported eagerly: {bad}'\n"
            "print('LAZY_OK')\n")
        out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                             capture_output=True, text=True, timeout=560)
        assert out.returncode == 0, out.stderr[-2000:]
        assert "LAZY_OK" in out.stdout

    def test_plain_engine_zero_router_metrics_and_spans(self):
        monitor.reset()
        trace.clear()
        trace.enable()
        try:
            m = _model()
            eng = ServingEngine(m, max_batch=2)
            rng = np.random.RandomState(0)
            for n in (4, 7):
                eng.submit(rng.randint(0, 64, (n,)).astype(np.int32),
                           max_new_tokens=3)
            eng.run_until_complete()
        finally:
            trace.disable()
        flat = monitor.flatten(monitor.snapshot())
        # zeroed () series can survive monitor.reset() when an earlier
        # in-process test imported the router tier — zero overhead means
        # nothing was RECORDED by the plain engine run
        leaked = {k: v for k, v in flat.items()
                  if k.startswith(("router_", "kv_handoff"))
                  and (v["count"] if isinstance(v, dict) else v)}
        assert not leaked, leaked
        names = {s.name for s in trace.spans()}
        assert not names & {"route", "kv_handoff"}, names
        # the engine's own families are intact (the refactor onto the
        # DecodeModel registry changed no instrumentation)
        assert {"request", "queue_wait", "prefill", "decode"} <= names
        assert eng.stats()["requests"]["handoff"] == 0

def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules.pop(name, None)
    spec.loader.exec_module(mod)
    return mod


class TestRouterToolGates:
    def test_trace_dump_router_missing_spans_exits_1(self, capsys,
                                                     monkeypatch):
        td = _load_tool("trace_dump")
        monkeypatch.setattr(trace, "enable", lambda: None)
        rc = td.main(["--router", "--json"])
        assert rc == 1
        import json

        report = json.loads(capsys.readouterr().out)
        missing = {f["message"].split("'")[1]
                   for f in report["targets"]["router"]["findings"]
                   if f["pass"] == "spans-present"}
        assert {"route", "kv_handoff"} <= missing

    def test_metrics_dump_router_missing_metrics_exits_1(self, capsys,
                                                         monkeypatch):
        md = _load_tool("metrics_dump")
        monkeypatch.setattr(md, "run_router_loop", lambda **kw: None)
        rc = md.main(["--router", "--json"])
        assert rc == 1
        import json

        report = json.loads(capsys.readouterr().out)
        missing = {f["message"].split("'")[1]
                   for f in report["targets"]["router"]["findings"]
                   if f["pass"] == "metrics-present"}
        # router_requests_total is labeled, so monitor.reset() drops its
        # series entirely; unlabeled families may survive as zeroed ()
        # series when an earlier in-process test touched them
        assert "router_requests_total" in missing

    @pytest.mark.slow
    def test_router_tools_green_end_to_end(self):
        """Subprocess CI form: both --router tools run clean at HEAD."""
        for tool in ("trace_dump", "metrics_dump"):
            out = subprocess.run(
                [sys.executable, os.path.join(REPO, "tools",
                                              f"{tool}.py"),
                 "--router", "--json"],
                cwd=REPO, capture_output=True, text=True, timeout=560)
            assert out.returncode == 0, (tool, out.stderr[-2000:])
