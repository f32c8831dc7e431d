"""The one compile cache that outlives a process is jax's own, turned on
by paddle.enable_compile_cache() (core/device.py) — the cache both
benchmark cells and chip_smoke.py use. A second process that shares the
directory compiles nothing for Program.aot_compile, SpmdTrainer.aot_build
or ServingEngine.warmup, nor for the traffic behind a warmed engine, and
produces the same outputs; a directory that cannot be written costs the
cache, never the run. (Which directory the helper names is held in
tests/test_chip_smoke.py.)"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: one child process: turn the cache on as benchmark/run.py does (every
#: program kept, however small), run one entry point, and print jax's own
#: cache events — (hits, misses) inside the entry point, then inside the
#: live calls that follow it — with the outputs
CHILD = r"""
import collections, json, sys
import numpy as np
import jax
import paddle_tpu as paddle

seen = collections.Counter()
jax.monitoring.register_event_listener(lambda name, **kw: seen.update([name]))
cache_dir = paddle.enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def take():
    return [seen.pop("/jax/compilation_cache/cache_hits", 0),
            seen.pop("/jax/compilation_cache/cache_misses", 0)]


def gpt(max_seq_len):
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    paddle.seed(0)
    return GPTForCausalLM(GPTConfig(
        vocab_size=256, hidden_size=32, num_layers=1, num_heads=2,
        max_seq_len=max_seq_len, dropout=0.0))


def aot_compile():
    import paddle_tpu.static as st

    paddle.seed(0)
    main, startup = st.Program(), st.Program()
    st.enable_static()
    try:
        with st.program_guard(main, startup):
            x = st.data("x", [None, 4])
            y = paddle.matmul(x, paddle.create_parameter([4, 4]))
    finally:
        st.disable_static()
    exe = st.Executor()
    exe.run(startup)
    take()
    main.aot_compile({"x": ((2, 4), "float32")}, fetch_list=[y])
    entry = take()
    (r,) = exe.run(main, feed={"x": np.ones((2, 4), np.float32)},
                   fetch_list=[y])
    return entry, take(), np.asarray(r).tolist()


def aot_build():
    from paddle_tpu.distributed.mesh import build_mesh
    from paddle_tpu.distributed.spmd import SpmdTrainer
    from paddle_tpu.models import GPTPretrainLoss

    model = gpt(16)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    mesh = build_mesh((1,), ("dp",), devices=jax.devices()[:1])
    tr = SpmdTrainer(model, opt, loss_fn=GPTPretrainLoss(), mesh=mesh)
    take()
    tr.aot_build([((2, 16), "int32"), ((2, 16), "int32")])
    entry = take()
    ids = np.random.RandomState(0).randint(0, 256, (2, 16)).astype(np.int32)
    losses = [float(np.asarray(tr.train_step(ids, ids)._data))
              for _ in range(2)]
    return entry, take(), losses


def warmup():
    from paddle_tpu.inference.serving import ServingEngine

    model = gpt(32)
    model.eval()
    eng = ServingEngine(model, max_batch=2)
    take()
    eng.warmup()
    entry = take()
    # the engine's whole family: admission into a cache row, greedy and
    # sampled decode steps, a second request into the other row
    rng = np.random.RandomState(0)
    eng.submit(rng.randint(0, 256, (8,)).astype(np.int32), max_new_tokens=4)
    eng.submit(rng.randint(0, 256, (5,)).astype(np.int32), max_new_tokens=3,
               temperature=0.8, top_k=4, seed=1)
    done = eng.run_until_complete()
    return entry, take(), [done[i].tokens.tolist() for i in sorted(done)]


entry, live, out = {"aot_compile": aot_compile, "aot_build": aot_build,
                    "warmup": warmup}[sys.argv[1]]()
print("RESULT " + json.dumps({"cache_dir": cache_dir, "entry": entry,
                              "live": live, "out": out}))
"""


def _child(entry_point, cache_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=cache_dir,
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    proc = subprocess.run([sys.executable, "-c", CHILD, entry_point],
                          capture_output=True, text=True, timeout=600,
                          env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-4000:]
    (line,) = [ln for ln in proc.stdout.splitlines()
               if ln.startswith("RESULT ")]
    return json.loads(line[len("RESULT "):])


@pytest.fixture(scope="module", params=["aot_compile", "aot_build", "warmup"])
def two_processes(request, tmp_path_factory):
    """(first process, second process) of one entry point on one directory."""
    d = str(tmp_path_factory.mktemp("jax_cache_" + request.param))
    return _child(request.param, d), _child(request.param, d)


def test_second_process_compiles_nothing(two_processes):
    cold, warm = two_processes
    assert cold["cache_dir"] == warm["cache_dir"]
    hits, misses = cold["entry"]
    assert misses > 0 and hits == 0, cold
    # every program the entry point compiled in the first process is read
    # back in the second: not one miss, and the same number of programs
    assert warm["entry"] == [misses, 0], (cold, warm)
    assert warm["out"] == cold["out"]


def test_live_calls_behind_a_warm_start_ask_the_cache_for_nothing(
        two_processes):
    """What aot_build / warmup / aot_compile compiled is everything the
    live calls need: a submit-and-drain with greedy and sampled requests
    (two train_steps, a run) after them compiles no program, in either
    process — so there is nothing the cache could miss."""
    cold, warm = two_processes
    assert cold["live"] == [0, 0], cold
    assert warm["live"] == [0, 0], warm


def test_unwritable_cache_directory_does_not_stop_a_run(tmp_path):
    """A directory that cannot be made (its parent is a regular file:
    unwritable whatever the user id) costs the cache, not the run."""
    blocker = tmp_path / "a_file"
    blocker.write_text("not a directory")
    first = _child("aot_build", str(blocker / "cache"))
    assert first["entry"] == [0, 0] and first["live"] == [0, 0], first
    assert not (blocker / "cache").exists()
    good = _child("aot_build", str(tmp_path / "cache"))
    assert first["out"] == good["out"]
    assert good["entry"][1] > 0
