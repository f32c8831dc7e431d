"""What a disarmed feature (and the always-on step-phase timeline) costs a
call, held as a RATIO: the body is timed against a reference loop — a
plain attribute read inside a `with` over a no-op context manager — in
the same breath, best of five rounds each. A loaded worker slows both
sides, so the ratio holds where a budget in microseconds does not (the
suite runs six workers to a machine; `test_phase_under_3us` was red on
the driver for that reason alone). A burst of load that falls on one
side's rounds alone still moves a ratio (`step_phase` read over its limit
beside a chip call's wait and passed alone), so a case is timed up to
ATTEMPTS times and held to the best of them: a slow path is slow every
time, a neighbour is not.

One case for each flag's "unset costs one boolean check" claim; the
other tests of each `tests/test_*_gate.py` stay where they are. A
reference unit is 0.2-0.4 us on the sandbox's CPU; a disarmed path
measures 0.2-1.8 units, the limit is 10."""
import contextlib
import time

import numpy as np
import pytest

import jax

import paddle_tpu as paddle
from paddle_tpu import flags, monitor, trace
from paddle_tpu.distributed.mesh import build_mesh
from paddle_tpu.distributed.spmd import SpmdTrainer

#: a disarmed path may cost this many reference units a call
DISARMED = 10.0
#: a case is timed at most this often; its best reading is held to the limit
ATTEMPTS = 3


class _Probe:
    flag = False


_PROBE = _Probe()
_NOOP = contextlib.nullcontext()


def _reference():
    with _NOOP:
        _PROBE.flag


def cost_ratio(body, n, rounds=5):
    """Best-of-`rounds` time of `n` calls of `body` over the best-of-
    `rounds` time of `n` calls of the reference, the two timed in turn."""
    best_body = best_ref = float("inf")
    for _ in range(rounds):
        t0 = time.perf_counter()
        for _ in range(n):
            _reference()
        best_ref = min(best_ref, time.perf_counter() - t0)
        t0 = time.perf_counter()
        for _ in range(n):
            body()
        best_body = min(best_body, time.perf_counter() - t0)
    return best_body / best_ref


def _dp_trainer(in_dim=4, out_dim=2):
    from paddle_tpu import nn

    paddle.seed(0)
    net = nn.Linear(in_dim, out_dim)
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=net.parameters())
    mesh = build_mesh((1,), ("dp",), devices=jax.devices()[:1])
    return SpmdTrainer(net, opt, loss_fn=nn.MSELoss(), mesh=mesh)


# Each case returns (bodies, limit, n, check): every body is held to
# `limit` reference units over `n` calls; `check`, where given, runs after
# the timing and asserts what the disarmed path must not have done.

def _cached_jit_unwarmed():
    """The CachedJit fast path (nothing warmed, FLAGS_trace off): one
    empty-dict + flag check, then the wrapped jit."""
    from paddle_tpu.framework import aot

    sink = []
    cj = aot.cached_jit(jit=sink.append, site="t", label="overhead")

    def check(calls):
        assert len(sink) == calls  # every call actually delegated
    return [lambda: cj(None)], DISARMED, 20_000, check


def _step_phase():
    """The step-phase timeline is ALWAYS on (no flag), so its cost is a
    budget, not a fast path: one TraceAnnotation, two clock reads, the
    thread-local parent stack and one tuple a phase. Measured as a step
    is shaped (a root with counts, three children without): 24 reference
    units on the sandbox's CPU, 2.3 us; the limit is 15 a phase."""
    def step():
        with trace.phase("gate/step", queued=1) as root:
            with trace.phase("gate/admit"):
                pass
            with trace.phase("gate/dispatch"):
                pass
            with trace.phase("gate/wait"):
                pass
            root.counts["active"] = 2

    def check(calls):
        assert not trace.spans()
        rows, lost = trace.phases()
        trace.clear()
        assert len(rows) == 4 * calls and not lost
    trace.disable()
    trace.clear()   # an empty ring to count into
    return [step], 4 * 15.0, 4_000, check


def _failpoint():
    from paddle_tpu.testing import failpoints as fp

    fp.reset()
    return [lambda: fp.failpoint("serving/step")], DISARMED, 20_000, None


def _failpoint_transform_and_numerics_flag():
    """FLAGS_numerics unset: one flag lookup (_numerics_active) and one
    disabled transform() a step."""
    from paddle_tpu.testing import failpoints as fp

    fp.reset()
    batch = [np.ones(4, np.float32)]
    return [lambda: flags.get_flag("numerics"),
            lambda: fp.transform("trainer/batch", batch)], \
        DISARMED, 20_000, None


def _blackbox_beacon_and_note():
    from paddle_tpu.monitor import blackbox

    def check(calls):
        assert blackbox.beacons() == {} and blackbox.ring() == []
    return [lambda: blackbox.beacon("gate"),
            lambda: blackbox.note("gate", a=1)], DISARMED, 20_000, check


def _trace_span():
    def span():
        with trace.span("gate", subsystem="t", a=1):
            pass

    def check(calls):
        assert not trace.spans()
    trace.disable()
    trace.clear()
    return [span, lambda: trace.start_span("gate").end()], \
        DISARMED, 20_000, check


def _monitor_disabled():
    """With the monitor disabled every instrumented call site costs ONE
    boolean check, and records nothing."""
    c = monitor.counter("overhead_probe_total")
    h = monitor.histogram("overhead_probe_ms")
    bound = monitor.counter("overhead_probe_labeled_total",
                            labelnames=("site",)).labels(site="x")
    monitor.disable()

    def check(calls):
        monitor.enable()
        assert c.value == 0 and h.count == 0 and bound.value == 0
    return [c.inc, lambda: h.observe(1.0), bound.inc], \
        DISARMED, 20_000, check


def _async_and_tpp_flags():
    tr = _dp_trainer()
    return [tr._async_active,
            lambda: flags.get_flag("tpp_kernels", False)], \
        DISARMED, 20_000, None


def _compress_flags():
    tr = _dp_trainer()
    return [tr._compress_active, tr._shard_update_active], \
        DISARMED, 20_000, None


def _goodput_flag():
    tr = _dp_trainer(8, 4)
    return [lambda: tr._goodput is not None,
            lambda: flags.get_flag("goodput", False)], \
        DISARMED, 20_000, None


def _perf_ledger_flag():
    tr = _dp_trainer(8, 4)
    return [lambda: tr._perf_ledger is not None,
            lambda: flags.get_flag("perf_ledger", False)], \
        DISARMED, 20_000, None


def _elastic_flag():
    tr = _dp_trainer()
    tr.train_step(np.ones((2, 4), np.float32),
                  np.zeros((2, 2), np.float32))   # settle compilation
    return [tr._elastic_active], DISARMED, 20_000, None


def _mpmd_flag():
    from paddle_tpu.distributed.pipeline import PipelineTrainer
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
        max_seq_len=32, dropout=0.0))
    pre, stages, post = model.pipeline_split(2)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    mesh = build_mesh((2,), ("pp",), devices=jax.devices()[:2])
    pp = PipelineTrainer(pre, stages, post, opt, mesh=mesh, n_micro=2,
                         schedule_mode="1F1B")
    dp = _dp_trainer()
    return [pp._mpmd_active, dp._mpmd_active], DISARMED, 20_000, None


def _idle_engine_step():
    """An idle engine step is pure host bookkeeping (the router tier's
    handoff queue must add nothing measurable to it): 5-10 us, 25-40
    reference units on the sandbox's CPU; the limit is ten times that."""
    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    paddle.seed(0)
    m = GPTForCausalLM(GPTConfig(vocab_size=64, hidden_size=32,
                                 num_layers=1, num_heads=2, max_seq_len=64,
                                 dropout=0.0))
    m.eval()
    eng = ServingEngine(m, max_batch=2)
    eng.step()   # one-time lazies out of the way
    return [eng.step], 400.0, 2_000, None


def _eager_layer_call():
    """`nn.Layer.__call__` opens the `jax.named_scope` it runs under on
    every call, traced or not (docs/OBSERVABILITY.md "Device scopes"): a
    layer that does nothing, called eagerly, costs the two empty hook
    loops, that scope and the call; 8-10 reference units on the sandbox's
    CPU, of them 6-7 the scope. The limit is 25."""
    from paddle_tpu import nn

    class Through(nn.Layer):
        def forward(self, x):
            return x

    layer = Through()
    return [lambda: layer(None)], 25.0, 20_000, None


CASES = [_cached_jit_unwarmed, _step_phase, _failpoint,
         _failpoint_transform_and_numerics_flag, _blackbox_beacon_and_note,
         _trace_span, _monitor_disabled, _async_and_tpp_flags,
         _compress_flags, _goodput_flag, _perf_ledger_flag, _elastic_flag,
         _mpmd_flag, _idle_engine_step, _eager_layer_call]


def best_reading(case):
    """(worst body's ratio, limit) of the best of up to ATTEMPTS timings of
    `case`, each set up afresh and checked; stops at the first under the
    limit."""
    best = float("inf")
    for _ in range(ATTEMPTS):
        bodies, limit, n, check = case()
        try:
            ratios = [cost_ratio(body, n) for body in bodies]
        finally:
            if check is not None:
                check(5 * n)
        best = min(best, max(ratios))
        if best < limit:
            break
    return best, limit


@pytest.mark.parametrize("case", CASES,
                         ids=[c.__name__.lstrip("_") for c in CASES])
def test_cost_in_reference_units(case):
    best, limit = best_reading(case)
    assert best < limit, (
        f"{case.__name__}: {best:.2f} reference units a call at best of "
        f"{ATTEMPTS} timings against a limit of {limit} — the fast path "
        "regressed")


def test_a_slowed_phase_still_fails(monkeypatch):
    """The best of several timings forgives a neighbour, not the code: with
    20 us of work put into every `trace.phase` the step-phase case reads
    over its limit on every attempt."""
    real = trace.phase

    @contextlib.contextmanager
    def slowed(name, **counts):
        t_end = time.perf_counter() + 20e-6
        while time.perf_counter() < t_end:
            pass
        with real(name, **counts) as ph:
            yield ph

    monkeypatch.setattr(trace, "phase", slowed)
    best, limit = best_reading(_step_phase)
    assert best >= limit, (best, limit)
