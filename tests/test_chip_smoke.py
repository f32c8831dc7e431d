"""CPU-side guards for chip_smoke.py and the start-up rules around it.

The script's real run needs a TPU (the driver and `chiprun` make it); what a
CPU can hold it to is its contract: it refuses without a chip, a rehearsal
can never read as a pass, a failing phase fails the script, the four-chip
phase really shards, the compile cache sits where the caller says, and
nothing touches a device just by being imported. Each case runs the script
as the driver does — a fresh process — with its own timeout.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _python(args, env=None, cwd=REPO, timeout=300):
    """Run `python <args>` hermetically on the CPU, as a fresh process."""
    full = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    full.pop("XLA_FLAGS", None)
    full.pop("JAX_COMPILATION_CACHE_DIR", None)
    full.update(env or {})
    return subprocess.run([sys.executable, *args], capture_output=True,
                          text=True, cwd=cwd, env=full, timeout=timeout)


def _run(args, **kw):
    """-> (rc, the stdout lines that are JSON objects, stderr)."""
    r = _python(args, **kw)
    lines = [json.loads(ln) for ln in r.stdout.splitlines()
             if ln.startswith("{")]
    return r.returncode, lines, r.stderr


def _says_ok(lines):
    return any(ln.get("ok") is True for ln in lines)


def test_rehearsal_runs_train_and_serve_and_never_says_ok():
    rc, lines, err = _run([SMOKE, "--rehearse"])
    assert rc == 0, err[-2000:]
    phases = [ln["phase"] for ln in lines if "phase" in ln]
    assert phases == ["train", "serve", "serve_hybrid"]
    hybrid = [ln for ln in lines if ln.get("phase") == "serve_hybrid"][0]
    assert hybrid["token_gap"] <= hybrid["limit"]
    assert hybrid["moe"]["moe_assignments_held"] > 0
    train = lines[1]
    assert train["losses"][-1] < train["losses"][0]
    assert lines[-1] == {"ok": False, "rehearsal": "passed",
                         "device": {"platform": "cpu", "kind": "cpu",
                                    "count": 1}}
    assert not _says_ok(lines)


@pytest.mark.parametrize("script", ["chip_smoke.py", "bench.py"])
def test_without_a_tpu_the_plain_invocation_refuses(script):
    """Non-zero exit, a message, and not one line on stdout: no model
    metric, no "ok" — never a smaller model on the CPU."""
    rc, lines, err = _run([os.path.join(REPO, script)], timeout=120)
    assert rc != 0
    assert lines == []
    assert "TPU" in err and "Nothing was" in err


def test_multichip_refuses_with_fewer_than_four_devices():
    rc, lines, err = _run([SMOKE, "--rehearse", "--multichip"], timeout=120)
    assert rc != 0 and lines == []
    assert "needs 4 devices" in err


def test_a_phase_that_raises_ends_the_script_nonzero(tmp_path):
    """Nothing is caught and carried on from: break the serve phase's
    reference (generate) and the script must die non-zero after the train
    phase, printing no last line at all."""
    (tmp_path / "sitecustomize.py").write_text(
        "import paddle_tpu.models.gpt as g\n"
        "def boom(*a, **k):\n"
        "    raise RuntimeError('planted serve failure')\n"
        "g.GPTForCausalLM.generate = boom\n")
    rc, lines, err = _run(
        [SMOKE, "--rehearse"],
        env={"PYTHONPATH": f"{tmp_path}{os.pathsep}{REPO}"})
    assert rc != 0
    assert "planted serve failure" in err
    assert [ln["phase"] for ln in lines if "phase" in ln] == ["train"]
    assert not any("ok" in ln for ln in lines)


def test_a_wrong_served_token_is_no_tie(tmp_path):
    """On a TPU the engine's tokens may part from generate's at a tie of two
    logits (the decode step's softmax runs in float32 there, generate's in
    bf16), never at a token the model's own logits do not bear out; off the
    chip both run the same einsums and no difference is allowed at all:
    alter one token of the reference and the script must die naming the
    gap."""
    (tmp_path / "sitecustomize.py").write_text(
        "import numpy as np\n"
        "import paddle_tpu as paddle\n"
        "import paddle_tpu.models.gpt as g\n"
        "real = g.GPTForCausalLM.generate\n"
        "def altered(self, *a, **k):\n"
        "    ids = np.asarray(real(self, *a, **k)._data).copy()\n"
        "    ids[0, -3] = (ids[0, -3] + 7) % 500\n"
        "    return paddle.to_tensor(ids)\n"
        "g.GPTForCausalLM.generate = altered\n")
    rc, lines, err = _run(
        [SMOKE, "--rehearse"],
        env={"PYTHONPATH": f"{tmp_path}{os.pathsep}{REPO}"})
    assert rc != 0
    assert "differ from model.generate" in err and "logit gap" in err
    noted = [ln for ln in lines if "logit_gap" in ln]
    assert noted and noted[0]["first_difference"] == 5
    assert noted[0]["logit_gap"] > 0.5          # no tie on any platform
    assert "allowed: 0.0" in err                # both sides ran the einsums
    assert not any("ok" in ln for ln in lines)


def test_multichip_rehearsal_matches_one_chip_and_really_shards():
    rc, lines, err = _run(
        [SMOKE, "--rehearse", "--multichip"],
        env={"XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert rc == 0, err[-2000:]
    by_phase = {ln["phase"]: ln for ln in lines if "phase" in ln}
    # only the sharded trainer and what it is compared with
    assert list(by_phase) == ["one_chip_reference", "dp2_mp2_zero2",
                              "multichip_compare", "multichip_placement"]
    assert max(by_phase["multichip_compare"]["rel_diff"]) <= 1e-2
    place = by_phase["multichip_placement"]
    assert place["devices_holding_shards"] == 4
    assert place["params_split"] > 0
    assert lines[-1]["device"]["count"] == 4 and lines[-1]["ok"] is False


_CACHE_DIR = ("import jax, paddle_tpu as paddle\n"
              "print(paddle.enable_compile_cache())\n"
              "print(jax.config.jax_compilation_cache_dir)\n")


def test_cache_helper_honours_the_callers_directory(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: jax already uses it and the helper
    sets no directory in code."""
    want = str(tmp_path / "from_outside")
    r = _python(["-c", _CACHE_DIR], cwd=str(tmp_path), timeout=120,
                env={"JAX_COMPILATION_CACHE_DIR": want})
    assert r.stdout.split() == [want, want], r.stderr[-2000:]


def test_cache_helper_falls_back_to_one_path_inside_the_checkout(tmp_path):
    """Unset: the same in-checkout directory from two different working
    directories (the path is part of jax's cache key) — no /tmp, no
    tempfile name, no pid, no time."""
    got = {tuple(_python(["-c", _CACHE_DIR], cwd=cwd,
                         timeout=120).stdout.split())
           for cwd in (str(tmp_path), REPO)}
    want = os.path.join(REPO, ".jax_cache")
    assert got == {(want, want)}


@pytest.mark.parametrize("module", ["paddle_tpu",
                                    "paddle_tpu.distributed.fleet.launch"])
def test_import_initialises_no_jax_backend(module):
    """One process per chip: a parent that touched a backend holds the chip
    and its children then fail or hang. Importing the package — and the
    launcher that starts children — must leave every backend unstarted."""
    code = (f"import {module}\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge.backends_are_initialized()\n")
    r = _python(["-c", code], timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]


def test_launcher_refuses_several_processes_on_a_tpu_host():
    """--nproc_per_node > 1 is for the CPU harness (JAX_PLATFORMS=cpu);
    anywhere else each child would claim every local chip. The dead
    FLAGS_selected_tpus export is gone."""
    from paddle_tpu.distributed.fleet import launch

    launch.check_one_process_per_chip(1, {})
    launch.check_one_process_per_chip(4, {"JAX_PLATFORMS": "cpu"})
    for env in ({}, {"JAX_PLATFORMS": "tpu"}, {"JAX_PLATFORMS": "tpu,cpu"}):
        with pytest.raises(SystemExit, match="One process drives all"):
            launch.check_one_process_per_chip(2, env)
    assert "FLAGS_selected_tpus" not in launch.get_cluster_env(
        "127.0.0.1", 6070, 2, 1)
