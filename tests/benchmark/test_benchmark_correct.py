"""What decides `correct`, at the tiny rehearsal size on the CPU.

- the plain reference against the program in float32: forward logits,
  loss, gradients and three AdamW steps agree (the test that ties the
  yardstick to the model);
- the control (the reference at fp8 put in the program's place) reads
  worse than the bf16 program on the number that separates them;
- a whole run with the timed path broken underneath comes out not
  correct, once for each fault a cell can have: a step that leaves its
  state unchanged, half of the batch left out, a served token altered
  where it is produced.
The limits of the rehearsal workloads were set on the CPU at this size;
the cells' own limits come from the chip (PERF.md).
"""
import copy
import json
import os

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TRAIN, SERVE = "rehearsal-train-tiny", "rehearsal-serve-tiny"


@pytest.fixture
def cell(monkeypatch):
    """run_cell on a rehearsal workload, with its file's dict patched."""
    from benchmark import run

    def go(workload, seed=3, seconds=1.0, **patch):
        real = run._load

        def load(*parts):
            d = real(*parts)
            if parts == ("workloads", workload + ".json"):
                d = dict(copy.deepcopy(d), **patch)
            return d

        monkeypatch.setattr(run, "_load", load)
        rc, result = run.run_cell(workload, seed, seconds, False)
        assert rc == 0
        return result

    return go


def _values(result):
    return {k: v["value"] for k, v in result["checked"].items()}


def test_reference_forward_matches_the_program_in_float32():
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from benchmark import run, weights
    from benchmark.reference import gpt as reference
    from paddle_tpu.models import GPTConfig, GPTForCausalLM, GPTPretrainLoss

    cfg = run._load("configs", "gpt2-tiny-rehearsal.json")
    model = GPTForCausalLM(GPTConfig(
        vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
        max_seq_len=128, dropout=0.0, gelu_approx=True))
    made = weights.flat(cfg, 11)
    for name, p in model.named_parameters():
        p.set_value(made[name])
    model.eval()
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 500, (2, 128), dtype=np.int32)
    labels = rng.integers(0, 500, (2, 128), dtype=np.int32)
    got = np.asarray(model(paddle.to_tensor(ids))._data)
    P = weights.stacked(cfg, 11)
    want = np.asarray(reference.logits(P, jnp.asarray(ids), 4))
    assert np.abs(got - want).max() < 2e-4 * np.abs(want).max()
    loss = float(GPTPretrainLoss()(model(paddle.to_tensor(ids)),
                                   paddle.to_tensor(labels))._data)
    assert loss == pytest.approx(float(reference.loss(
        P, jnp.asarray(ids), jnp.asarray(labels), 4)), rel=1e-5)


def test_reference_training_matches_the_program_in_float32(cell):
    """Loss, first gradient (length and direction) and three AdamW steps:
    without autocast the program IS the reference, to float32 rounding."""
    result = cell(TRAIN, autocast=None)
    got = _values(result)
    assert result["run"]["notes"]["not_compared"]["loss_gap"] < 1e-5
    assert got["grad_gap"] < 1e-4 and got["grad_diff"] < 1e-3
    assert got["delta_gap"] < 1e-2
    assert got["compiles_in_window"] == 0 and got["failed"] == 0


def test_rehearsal_cells_come_out_correct(cell):
    train = cell(TRAIN)
    assert train["correct"], train["checked"]
    assert train["attempted"] > 0 and train["failed"] == 0
    assert set(train["metrics"]) == {"train_tokens_per_s", "setup_s"}
    serve = cell(SERVE, seconds=2.0)
    assert serve["correct"], serve["checked"]
    assert set(serve["metrics"]) == {"serve_tokens_per_s",
                                     "serve_itl_p95_ms", "setup_s"}
    assert list(serve)[-1] == "checked"


def test_control_reads_worse_than_the_program():
    """The reference at fp8 in the program's place against the bf16
    program, on the number that separates them at this size (grad_diff;
    with 26 leaves the cell's own delta_gap_matrix does not), and half of
    the batch left out on the numbers the cell holds it to."""
    from benchmark import compare, run

    ctx = run.open_cell(TRAIN, 5)
    runner = run.make_runner(ctx)
    runner.setup()
    runner.release()
    program = runner.check()
    limits = ctx.workload["limits"]
    control, _ = compare.train_numbers(runner.candidate(precision="fp8"),
                                       runner.ref)
    assert program["grad_diff"] < limits["grad_diff"] < control["grad_diff"]
    assert control["grad_diff"] > 3 * program["grad_diff"]
    assert control["delta_gap_matrix"] > 3 * program["delta_gap_matrix"]
    half, _ = compare.train_numbers(runner.candidate(fault="half_batch"),
                                    runner.ref)
    for number in ("grad_gap", "delta_gap_matrix"):
        assert half[number] > 10 * program[number], number
        assert half[number] > limits[number] > program[number], number


def test_a_step_that_leaves_its_state_unchanged_is_not_correct(
        cell, monkeypatch):
    from paddle_tpu.distributed.spmd import SpmdTrainer

    real = SpmdTrainer._train_step_impl

    def frozen(self, *batch):
        import jax

        keep = jax.tree_util.tree_map(lambda x: x.copy(),
                                      (self.params, self.opt_state))
        loss = real(self, *batch)
        self.params, self.opt_state = keep
        return loss

    monkeypatch.setattr(SpmdTrainer, "_train_step_impl", frozen)
    result = cell(TRAIN)
    assert not result["correct"]
    assert _values(result)["delta_gap"] == pytest.approx(1.0, abs=1e-3)


def test_half_of_the_batch_left_out_is_not_correct(cell, monkeypatch):
    from paddle_tpu.distributed.spmd import SpmdTrainer

    real = SpmdTrainer._train_step_impl

    def half(self, *batch):
        return real(self, *[b[: b.shape[0] // 2] for b in batch])

    monkeypatch.setattr(SpmdTrainer, "_train_step_impl", half)
    result = cell(TRAIN)
    assert not result["correct"]
    got, lim = _values(result), result["checked"]
    assert got["grad_gap"] > lim["grad_gap"]["limit"]
    assert got["grad_diff"] > lim["grad_diff"]["limit"]


def test_a_served_token_altered_is_not_correct(cell, monkeypatch):
    from paddle_tpu.inference.serving import ServingEngine

    real = ServingEngine._dispatch_decode
    calls = {"n": 0}

    def altered(self, active):
        toks, kind = real(self, active)
        calls["n"] += 1
        if calls["n"] % 3 == 0:     # one step in three, every live row (the
            # check compares a sample of requests, so each has to carry it)
            toks = np.asarray(toks).copy()
            toks[active] = (toks[active] + 7) % 500
        return toks, kind

    monkeypatch.setattr(ServingEngine, "_dispatch_decode", altered)
    result = cell(SERVE, seconds=2.0)
    assert not result["correct"]
    assert _values(result)["token_gap"] > 0.5


def test_a_rehearsal_never_prints_a_result(capsys, monkeypatch):
    from benchmark import run

    assert run.main(["--workload", TRAIN, "--seed", "4", "--seconds", "1",
                     "--trace", "1"]) == 0
    out = capsys.readouterr()
    last = json.loads(out.out.strip().splitlines()[-1])
    assert last["correct"] is False and last["rehearsal"] == "passed"
    assert {"attempted", "failed", "metrics", "device", "breakdown"} <= \
        set(last)
    assert {"device_idle.train", "step_mfu.train", "dispatch_ms.train"} <= \
        set(last["metrics"])
    assert last["device"]["busy_s"] > 0 and last["device"]["window_s"] > 0
    assert "checked delta_gap_matrix" in out.err
