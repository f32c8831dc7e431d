"""Training cells: SpmdTrainer.train_step, through the entry points a user
calls, on the mesh the workload's file names.

Set-up builds ONE trainer, drives it through its first three steps by the
window's own call (`_step`), reads what `correct` compares (each loss, the
first gradient per leaf from Adam's first moment after one step, the
parameters' change per leaf after three), and hands the same trainer to the
window. After the window the program's state is freed and the plain
reference follows the same three steps (benchmark/reference/gpt.py).
"""
import collections
import contextlib
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import compare, traffic, weights
from benchmark.reference import gpt as reference


@functools.partial(jax.jit, static_argnames=("b1",))
def _first_grad(opt_state, b1):
    """After ONE Adam step m = (1 - b1) * g: the gradient as the
    optimizer got it. Per leaf its L2 norm, and the reference's evenly
    spaced sample of its elements."""
    norms, samples = {}, {}
    for n, st in opt_state.items():
        if n == "__step__":
            continue
        g = st["moment1"].astype(jnp.float32) / (1.0 - b1)
        norms[n] = jnp.sqrt(jnp.sum(jnp.square(g)))
        samples[n] = g.reshape(-1)[reference.sample_index(g.size)]
    return norms, samples


@functools.partial(jax.jit, static_argnames=("dims_",))
def _delta_norms(params, key, dims_):
    """Per-leaf L2 norm of params - (the weights the seed gives)."""
    start = weights.unstack(weights._stacked(key, dims_, None), dims_[1])
    return {n: jnp.sqrt(jnp.sum(jnp.square(
        p.astype(jnp.float32) - start[n]))) for n, p in params.items()}


class Runner:
    CHECK_STEPS = 3

    def __init__(self, ctx):
        self.ctx = ctx
        self.trainer = self.model = None
        self.readings = None

    # -- set-up --------------------------------------------------------------
    def setup(self):
        import paddle_tpu as paddle
        from paddle_tpu.distributed.mesh import build_mesh
        from paddle_tpu.distributed.split import collect_spmd_specs
        from paddle_tpu.distributed.spmd import SpmdTrainer
        from paddle_tpu.models import GPTConfig, GPTForCausalLM, \
            GPTPretrainLoss

        ctx, wl, cfg = self.ctx, self.ctx.workload, self.ctx.cfg
        self.paddle = paddle
        shape, axes = wl["mesh"]
        mesh = build_mesh(tuple(shape), tuple(axes),
                          devices=list(ctx.devices))
        tp = bool(wl.get("tensor_parallel", False))
        paddle.seed(ctx.seed)
        model = GPTForCausalLM(GPTConfig(
            vocab_size=weights.dims(cfg)[2], hidden_size=cfg["n_embd"],
            num_layers=cfg["n_layer"], num_heads=cfg["n_head"],
            max_seq_len=cfg["n_positions"], dropout=0.0,
            gelu_approx=cfg["activation_function"] == "gelu_new",
            tensor_parallel=tp))
        ctx.mark("model_constructor_s")
        made = weights.flat(cfg, ctx.seed)
        for name, p in model.named_parameters():
            p.set_value(made.pop(name))
        if made:
            raise KeyError(f"weights the model has no place for: "
                           f"{sorted(made)}")
        o = wl["optimizer"]
        self.hp = (o["lr"], o["beta1"], o["beta2"], o["epsilon"],
                   o["weight_decay"])
        opt = paddle.optimizer.AdamW(
            learning_rate=o["lr"], beta1=o["beta1"], beta2=o["beta2"],
            epsilon=o["epsilon"], weight_decay=o["weight_decay"],
            parameters=model.parameters())
        kw = {}
        stage = int(wl.get("sharding_stage", 0))
        if stage:
            kw["sharding_stage"] = stage
        if tp:
            kw["extra_param_specs"] = collect_spmd_specs(model)
        self.model = model
        self.trainer = SpmdTrainer(model, opt, loss_fn=GPTPretrainLoss(),
                                   mesh=mesh, dp_axis=axes[0], **kw)
        ctx.mark("weights_and_trainer_s")
        self.amp = (lambda: paddle.amp.auto_cast(True, dtype=wl["autocast"])
                    ) if wl.get("autocast") else contextlib.nullcontext
        mix = ctx.mix
        self.tokens_per_step = mix["batch"] * mix["seq_len"]
        self.batches = traffic.token_batches(mix, cfg["vocab_size"], ctx.seed)
        spec = ((mix["batch"], mix["seq_len"]), "int32")
        with self.amp():
            self.trainer.aot_build([spec, spec])
            ctx.mark("aot_build_s")
            losses = [self._step()]
            grad = _first_grad(self.trainer.opt_state, b1=o["beta1"])
            losses += [self._step() for _ in range(self.CHECK_STEPS - 1)]
            delta = _delta_norms(self.trainer.params,
                                 weights.seed_key(ctx.seed),
                                 weights.dims(cfg))
        grad, delta, losses = jax.device_get(
            (grad, delta, [t._data for t in losses]))
        ctx.mark("first_steps_s")
        self.readings = {
            "loss": [float(x) for x in losses],
            "grad_norm": {k: float(v) for k, v in grad[0].items()},
            "grad_sample": {k: np.asarray(v) for k, v in grad[1].items()},
            "delta_norm": {k: float(v) for k, v in delta.items()}}

    # -- the call that set-up's steps and the window's steps share -----------
    def _step(self):
        spans = self.ctx.spans
        with spans.span("data_batch"):
            batch = [self.paddle.to_tensor(a) for a in next(self.batches)]
        with spans.span("train_step"):
            return self.trainer.train_step(*batch)

    # -- the measured window ---------------------------------------------------
    def window(self, seconds):
        """Steps for `seconds`, at most two in flight (a job that logs its
        loss); closed by waiting for the last loss and a parameter leaf.
        The rate is over all steps and all the time of the window."""
        t_open = time.perf_counter()
        losses, in_flight = [], collections.deque()
        with self.amp():
            while time.perf_counter() - t_open < seconds:
                loss = self._step()
                losses.append(loss)
                in_flight.append(loss)
                if len(in_flight) > 2:
                    with self.ctx.spans.span("wait_loss"):
                        jax.block_until_ready(in_flight.popleft()._data)
            with self.ctx.spans.span("wait_last"):
                leaf = next(iter(self.trainer.params.values()))
                jax.block_until_ready((losses[-1]._data, leaf))
        window_s = time.perf_counter() - t_open
        values = np.asarray(jax.device_get([t._data for t in losses]))
        steps = len(losses)
        failed = int(np.sum(~np.isfinite(values)))
        tokens = steps * self.tokens_per_step
        return {"attempted": steps, "failed": failed,
                "window_s": window_s, "t_open": t_open, "steps": steps,
                "tokens": tokens,
                "batch": self.ctx.mix["batch"],
                "seq_len": self.ctx.mix["seq_len"],
                "end_to_end": {"train_tokens_per_s": tokens / window_s}}

    def release(self):
        self.trainer = self.model = None

    # -- what decides `correct` ----------------------------------------------
    def candidate(self, precision="float32", fault=None):
        """The reference's readings over the same three steps, flattened
        to the program's leaf names. precision/fault: the reference put in
        the program's place (the control and the planted faults)."""
        ctx, cfg = self.ctx, self.ctx.cfg
        P0 = weights.stacked(cfg, ctx.seed)
        gen = traffic.token_batches(ctx.mix, cfg["vocab_size"], ctx.seed)
        batches = [next(gen) for _ in range(self.CHECK_STEPS)]
        out = reference.train_readings(
            P0, batches, cfg["n_head"], cfg["layer_norm_epsilon"], self.hp,
            precision=precision, rows=int(ctx.workload.get("check_rows", 1)),
            fault=fault)
        L = cfg["n_layer"]
        return {"loss": [float(x) for x in out["loss"]],
                "grad_norm": compare.flatten(out["grad_norm"], L),
                "grad_sample": compare.flatten(out["grad_sample"], L,
                                               to=np.asarray),
                "delta_norm": compare.flatten(out["delta_norm"], L)}

    def check(self):
        self.ref = self.candidate()
        numbers, self.worst = compare.train_numbers(self.readings, self.ref)
        # which of these the cell is held to is its file's `limits` to say
        # (PERF.md section 2 has the readings and the reasons)
        self.ctx.notes["worst_leaf"] = self.worst
        self.ctx.notes["losses"] = {"program": self.readings["loss"],
                                    "reference": self.ref["loss"]}
        return numbers
