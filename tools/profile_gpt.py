"""Profile a bench.py GPT train step (gpt2s — the BENCH headline config —
or gpt2m via --model) on the current backend and print ONE JSON line with
the numbers a tuning session needs:

- XLA cost analysis of the compiled step: model FLOPs, bytes accessed (HBM
  traffic), and the flops/byte arithmetic intensity — tells whether the step
  is MXU-bound or HBM-bound.
- XLA memory analysis: peak temp allocation + argument/output footprint —
  tells how much batch headroom remains before OOM.
- Measured step time + achieved TFLOP/s vs the analysis FLOPs.
- Optional: --trace DIR dumps a jax.profiler trace for offline tensorboard.

The model/trainer/data come from bench._gpt_train_setup, so the profiled
program IS the benchmarked one, and the step is compiled exactly ONCE (AOT
lower+compile; the timed loop runs the same compiled executable).

Times come from a chip: like bench.py this refuses to run without a TPU
(exit 2, nothing printed on stdout).

Usage: python tools/profile_gpt.py [--batch B] [--seq S] [--steps N]
                                   [--trace DIR] [--model gpt2s|gpt2m]
"""
import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--trace", default=None,
                    help="dump a jax.profiler trace to this directory")
    ap.add_argument("--model", default="gpt2s", choices=["gpt2s", "gpt2m"],
                    help="config family (matches bench.py --config)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    import bench
    import paddle_tpu as paddle
    from paddle_tpu.core.device import on_tpu
    from paddle_tpu.core.generator import default_generator

    if not on_tpu():
        print(f"profile_gpt.py times a TPU train step; jax found "
              f"{jax.devices()[0].platform!r}. Nothing was measured.",
              file=sys.stderr)
        return 2
    paddle.enable_compile_cache()
    # defaults match bench.py's per-config batches (gpt2s 16, gpt2m 8) so
    # the profiled program is the benchmarked one
    batch = args.batch or (8 if args.model == "gpt2m" else 16)
    seq, steps = args.seq, args.steps

    cfg_fn = bench._gpt2m_cfg if args.model == "gpt2m" else bench._gpt2s_cfg
    trainer, ids, labels = bench._gpt_train_setup(cfg_fn(seq), batch)
    batch_arrays = (ids._data, labels._data)
    lr = jnp.asarray(trainer.optimizer.get_lr(), dtype=jnp.float32)
    key = default_generator().fold_in(0)

    with paddle.amp.auto_cast(True, dtype="bfloat16"):
        # ONE compile: AOT lower+compile of the exact trainer step; the timed
        # loop below runs this same executable (no second jit-cache compile)
        step_fn = trainer._build(list(batch_arrays))
        lowered = step_fn.lower(trainer.params, trainer.opt_state,
                                trainer.buffers, lr, key, *batch_arrays)
        compiled = lowered.compile()
    cost = compiled.cost_analysis()
    mem = compiled.memory_analysis()

    # warmup run (first dispatch), rebinding donated params/opt_state
    params, opt_state, buffers = trainer.params, trainer.opt_state, \
        trainer.buffers
    loss, params, opt_state, buffers = compiled(
        params, opt_state, buffers, lr, key, *batch_arrays)
    np.asarray(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss, params, opt_state, buffers = compiled(
            params, opt_state, buffers, lr, key, *batch_arrays)
    np.asarray(loss)
    dt = (time.perf_counter() - t0) / steps

    if args.trace:
        with jax.profiler.trace(args.trace):
            for _ in range(3):
                loss, params, opt_state, buffers = compiled(
                    params, opt_state, buffers, lr, key, *batch_arrays)
            np.asarray(loss)

    flops = float(cost.get("flops", 0.0)) if cost else 0.0
    bytes_acc = float(cost.get("bytes accessed", 0.0)) if cost else 0.0
    line = {
        "config": {"model": args.model, "batch": batch, "seq": seq,
                   "platform": jax.devices()[0].platform},
        "step_time_s": round(dt, 4),
        "tokens_per_sec": round(batch * seq / dt, 1),
        "xla_flops_per_step": flops,
        "xla_bytes_accessed_per_step": bytes_acc,
        "arithmetic_intensity_flops_per_byte":
            round(flops / bytes_acc, 2) if bytes_acc else None,
        "achieved_tflops_per_sec": round(flops / dt / 1e12, 2) if flops else None,
    }
    if mem is not None:
        for attr in ("temp_size_in_bytes", "argument_size_in_bytes",
                     "output_size_in_bytes", "generated_code_size_in_bytes"):
            v = getattr(mem, attr, None)
            if v is not None:
                line.setdefault("memory", {})[attr] = int(v)
    if args.trace:
        line["trace_dir"] = args.trace
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
