"""Async double-buffered dispatch (ISSUE 11, docs/PERF.md): the armed
trainer's loss trajectory is BIT-exact vs the synchronous path while the
per-step host-sync count drops to <= 1 per FLAGS_async_window steps; the
deferred guard keeps the FLAGS_max_skip_steps contract; prefetch()
double-buffers batch marshalling; the serving engine's async step emits
identical tokens with the admission window overlapped; and the
overlapped quantized exchange stays inside the quantized parity band."""
import numpy as np
import pytest

import jax

import paddle_tpu as paddle
from paddle_tpu.distributed.mesh import build_mesh
from paddle_tpu.distributed.spmd import SpmdTrainer
from paddle_tpu.models import GPTConfig, GPTForCausalLM, GPTPretrainLoss


@pytest.fixture(autouse=True)
def _restore_flags():
    yield
    paddle.set_flags({"async_dispatch": False, "async_window": 8,
                      "check_nan_inf": False, "max_skip_steps": 3,
                      "benchmark": False})


def _gpt_trainer(lr=1e-2):
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=1,
                    num_heads=2, max_seq_len=32, dropout=0.0)
    model = GPTForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=lr,
                                 parameters=model.parameters())
    mesh = build_mesh((1,), ("dp",), devices=jax.devices()[:1])
    return SpmdTrainer(model, opt, loss_fn=GPTPretrainLoss(), mesh=mesh)


def _batches(steps, seed=0):
    rng = np.random.RandomState(seed)
    return [(rng.randint(0, 64, (2, 16)).astype(np.int32),
             rng.randint(0, 64, (2, 16)).astype(np.int32))
            for _ in range(steps)]


def _linear_trainer():
    """Float-input trainer for guard-poisoning tests (a NaN batch flows
    straight into the loss; the trainer/batch scale failpoint only
    poisons FLOAT arrays, which GPT's int32 token batches are not)."""
    paddle.seed(0)
    model = paddle.nn.Linear(4, 1)
    opt = paddle.optimizer.Adam(learning_rate=0.1,
                                parameters=model.parameters())
    mesh = build_mesh((1,), ("dp",), devices=jax.devices()[:1])
    return SpmdTrainer(model, opt, loss_fn=paddle.nn.MSELoss(),
                       mesh=mesh)


X = np.ones((2, 4), np.float32)
Y = np.zeros((2, 1), np.float32)
XNAN = X.copy()
XNAN[0, 0] = np.nan


def _run(async_on, steps=6, guard=True, window=3):
    paddle.set_flags({"async_dispatch": async_on, "async_window": window,
                      "check_nan_inf": guard})
    tr = _gpt_trainer()
    losses = [tr.train_step(*b) for b in _batches(steps)]
    tr.guard_sync()
    out = [float(np.asarray(l._data)) for l in losses]
    params = {k: np.asarray(v).copy() for k, v in tr.params.items()}
    return tr, out, params


class TestTrainerAsync:
    def test_loss_trajectory_bit_exact_vs_sync(self):
        """The acceptance criterion: armed on the tiny-GPT trainer, the
        loss trajectory is bit-exact vs the synchronous path (the
        compiled program is byte-identical; only the host's fetch
        timing moves) — params byte-equal too."""
        _, sync_losses, sync_params = _run(False)
        _, async_losses, async_params = _run(True)
        assert sync_losses == async_losses
        for k in sync_params:
            assert sync_params[k].tobytes() == async_params[k].tobytes(), k

    def test_host_sync_count_drops_to_window_rate(self):
        """Per-step host-sync count <= 1/FLAGS_async_window steps: 12
        guarded steps under window 4 cost exactly 3 verdict drains
        (plus the final guard_sync for the tail)."""
        paddle.set_flags({"async_dispatch": True, "async_window": 4,
                          "check_nan_inf": True})
        tr = _gpt_trainer()
        for b in _batches(12):
            tr.train_step(*b)
        # drains happen at ENTRY once the window fills (so the device
        # had the whole host gap to finish): steps 5 and 9 fetched
        # windows of 4; the final 4 are still banked, fetched by the
        # first boundary that wants them
        assert tr._verdict_fetches == 2
        assert len(tr._pending_verdicts) == 4
        tr.guard_sync()
        assert tr._verdict_fetches == 3
        assert len(tr._pending_verdicts) == 0
        assert tr._nonfinite_total == 0

    def test_returns_step_handle_with_schedule_identity(self):
        paddle.set_flags({"async_dispatch": True})
        from paddle_tpu.core.tensor import Tensor
        from paddle_tpu.distributed.async_dispatch import StepHandle

        tr = _gpt_trainer()
        b = _batches(2)
        h0 = tr.train_step(*b[0])
        h1 = tr.train_step(*b[1])
        assert isinstance(h0, StepHandle) and isinstance(h0, Tensor)
        assert (h0.scheduled_step, h1.scheduled_step) == (0, 1)
        assert np.isfinite(h1.result())

    def test_deferred_skip_books_within_window_and_rewinds_schedule(self):
        paddle.set_flags({"async_dispatch": True, "async_window": 4,
                          "check_nan_inf": True})
        tr = _linear_trainer()
        tr.train_step(X, Y)
        tr.train_step(X, Y)
        tr.guard_sync()
        snap = {k: np.asarray(v).copy() for k, v in tr.params.items()}
        count = tr.optimizer._step_count
        tr.train_step(XNAN, Y)
        assert tr._nonfinite_total == 0          # not fetched yet
        assert len(tr._pending_verdicts) == 1    # in flight, in window
        tr.guard_sync()
        assert tr._nonfinite_total == 1
        assert tr.optimizer._step_count == count   # schedule rewound
        for k in snap:
            assert np.asarray(tr.params[k]).tobytes() \
                == snap[k].tobytes(), k

    def test_mid_window_skip_burns_its_position_no_rng_aliasing(self):
        """A skip that is NOT the newest dispatch must not rewind the
        schedule: later applied steps already consumed the following
        rng positions — rewinding would duplicate an applied step's
        dropout rng. Only a trailing skip rewinds (the retry slot)."""
        paddle.set_flags({"async_dispatch": True, "async_window": 8,
                          "check_nan_inf": True})
        tr = _linear_trainer()
        tr.train_step(X, Y)        # pos 0, applied
        tr.train_step(XNAN, Y)     # pos 1, skipped on device
        tr.train_step(X, Y)        # pos 2, applied
        tr.train_step(X, Y)        # pos 3, applied
        count = tr.optimizer._step_count
        tr.guard_sync()
        assert tr._nonfinite_total == 1
        assert tr.optimizer._step_count == count   # pos 1 burned
        # trailing skip: the newest dispatch DOES rewind (retry slot)
        tr.train_step(XNAN, Y)
        count = tr.optimizer._step_count
        tr.guard_sync()
        assert tr.optimizer._step_count == count - 1

    def test_deferred_raise_stays_within_max_skip_contract(self):
        paddle.set_flags({"async_dispatch": True, "async_window": 8,
                          "check_nan_inf": True, "max_skip_steps": 1})
        tr = _linear_trainer()
        tr.train_step(XNAN, Y)
        tr.train_step(XNAN, Y)
        with pytest.raises(FloatingPointError, match="max_skip_steps"):
            tr.guard_sync()

    def test_prefetch_double_buffers_and_stays_bit_exact(self):
        paddle.set_flags({"async_dispatch": True})
        batches = _batches(4)
        tr = _gpt_trainer()
        plain = [float(np.asarray(tr.train_step(*b)._data))
                 for b in batches]
        paddle.set_flags({"async_dispatch": True})
        tr2 = _gpt_trainer()
        losses = []
        tr2.prefetch(*batches[0])
        for i, b in enumerate(batches):
            # step N consumes its staged copies; batch N+1 is staged
            # while step N's device work is still in flight — the
            # double-buffer. Keyed by array object identity.
            losses.append(float(np.asarray(tr2.train_step(*b)._data)))
            if i + 1 < len(batches):
                tr2.prefetch(*batches[i + 1])
        assert tr2._prefetch_hits == 4
        assert losses == plain

    def test_benchmark_keeps_same_call_visibility(self):
        """FLAGS_benchmark forces a per-step device sync anyway — the
        deferred verdict settles inside the same call, preserving the
        pre-PR skip visibility for benchmarked runs."""
        paddle.set_flags({"async_dispatch": False, "check_nan_inf": True,
                          "benchmark": True})
        tr = _linear_trainer()
        tr.train_step(XNAN, Y)
        assert tr._nonfinite_total == 1          # no guard_sync needed


class TestServingAsync:
    def _model(self):
        paddle.seed(0)
        cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=1,
                        num_heads=2, max_seq_len=64, dropout=0.0)
        m = GPTForCausalLM(cfg)
        m.eval()
        return m

    def test_engine_tokens_bit_exact_with_a_step_in_flight(self):
        """The engine's one decode loop keeps a step in flight (PR 33; it
        does what FLAGS_async_dispatch's serving arm did and more, and
        that arm is gone): tokens are generate()'s, the flag changes
        nothing, and stats()["lookahead"] says how often it engaged."""
        from paddle_tpu import trace
        from paddle_tpu.inference.serving import ServingEngine

        m = self._model()
        rng = np.random.RandomState(0)
        prompts = [rng.randint(0, 64, (n,)).astype(np.int32)
                   for n in (5, 9, 4)]

        def run(flag):
            paddle.set_flags({"async_dispatch": flag})
            try:
                eng = ServingEngine(m, max_batch=2)
                rids = [eng.submit(p, max_new_tokens=6) for p in prompts]
                res = eng.run_until_complete()
                return eng, {r: res[r].tokens.tolist() for r in rids}
            finally:
                paddle.set_flags({"async_dispatch": False})

        want = [np.asarray(m.generate(
            paddle.to_tensor(p[None]), max_new_tokens=6,
            temperature=0.0)._data)[0, len(p):].tolist() for p in prompts]
        eng0, tokens = run(False)
        assert list(tokens.values()) == want
        trace.clear()
        trace.enable()
        try:
            eng, flagged = run(True)
        finally:
            trace.disable()
        assert flagged == tokens
        la = eng.stats()["lookahead"]
        assert la == eng0.stats()["lookahead"]
        assert la["rounds"] == eng.stats()["steps"]["decode_greedy"] > 0
        # all but the first step went out with the one before it unread
        assert la["rounds_overlapped"] == la["rounds"] - 1
        assert la["tokens_discarded"] == 0 and la["in_flight"] == 0
        names = {s.name for s in trace.spans()}
        assert "decode" in names
        assert not [n for n in names if n.startswith("dispatch/")]

    def test_plain_engine_has_no_async_breakdown_or_spans(self):
        from paddle_tpu import trace
        from paddle_tpu.inference.serving import ServingEngine

        m = self._model()
        trace.clear()
        trace.enable()
        try:
            eng = ServingEngine(m, max_batch=1)
            eng.submit(np.asarray([1, 2, 3], np.int32), max_new_tokens=3)
            eng.run_until_complete()
        finally:
            trace.disable()
        assert "async_overlap" not in eng.stats()["breakdown"]
        assert not [s.name for s in trace.spans()
                    if s.name.startswith("dispatch/")]
        assert not hasattr(eng, "_async") and not eng.has_work()
        # 3 tokens: the admission's and two decode steps', both dispatched
        # by the one call that found nothing in flight
        assert eng.stats()["lookahead"] == {
            "rounds": 2, "rounds_overlapped": 1, "tokens_discarded": 0,
            "in_flight": 0}
        assert eng.stats()["health"]["steps"] == 2


class TestOverlapGradComm:
    def test_overlap_legs_stay_in_quantized_band(self):
        """The overlapped (per-leg) quantized exchange vs the fused
        bundle: different stochastic-rounding draws, same quantization
        scheme — lockstep parity within the quantized_allreduce band."""
        from paddle_tpu.testing import parity

        def build():
            paddle.seed(0)
            cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=1,
                            num_heads=2, max_seq_len=32, dropout=0.0)
            model = GPTForCausalLM(cfg)
            opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                         parameters=model.parameters())
            mesh = build_mesh((1,), ("dp",), devices=jax.devices()[:1])
            return SpmdTrainer(model, opt, loss_fn=GPTPretrainLoss(),
                               mesh=mesh)

        report = parity.run_parity(
            build, _batches(3),
            reference_flags={"quantized_allreduce": True,
                             "quantized_allreduce_min_size": 1},
            candidate_flags={"quantized_allreduce": True,
                             "quantized_allreduce_min_size": 1,
                             "overlap_grad_comm": True},
            loss_rtol=0.08, loss_atol=0.05, stat_rtol=0.6, stat_atol=0.1)
        assert not report["diverged"], report["first_divergence"]
