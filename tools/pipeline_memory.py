"""Pipeline schedule peak-memory measurement (VERDICT r2 #5).

Compiles the SAME pipeline train step under schedule_mode='F-then-B' (GPipe:
all per-tick residuals retained, O(n_ticks)) and '1F1B' (per-tick remat:
live memory bounded to the scan carries) and reports XLA's memory analysis
for both — temp_size is the transient working set the schedule exists to
bound (reference framework/section_worker.cc:98-141 built 1F1B for exactly
this). Runs on the real TPU when available (single chip: pp=1, the remat
effect is per-micro-batch and does not need multiple stages) or on a virtual
CPU mesh (pp=4) under XLA_FLAGS=--xla_force_host_platform_device_count=8.

Usage: python tools/pipeline_memory.py [--layers N] [--hidden H] [--seq S]
                                       [--n-micro M]
Prints one JSON line: {"gpipe_temp_bytes", "1f1b_temp_bytes", "ratio", ...}.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def measure(mode, pp, layers, hidden, seq, n_micro, devices, vocab=8192):
    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import optimizer as popt
    from paddle_tpu.distributed.mesh import build_mesh
    from paddle_tpu.distributed.pipeline import PipelineTrainer
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=vocab, hidden_size=hidden, num_layers=layers,
                    num_heads=8, max_seq_len=seq, dropout=0.0)
    model = GPTForCausalLM(cfg)
    pre, stages, post = model.pipeline_split(pp)
    opt = popt.AdamW(learning_rate=1e-4, parameters=model.parameters())
    mesh = build_mesh((pp,), ("pp",), devices=devices[:pp])
    tr = PipelineTrainer(pre, stages, post, opt, mesh=mesh, n_micro=n_micro,
                         schedule_mode=mode)
    rng = np.random.RandomState(0)
    mb = 2
    x = jnp.asarray(rng.randint(0, cfg.vocab_size,
                                (n_micro, mb, seq)).astype(np.int32))
    y = jnp.asarray(rng.randint(0, cfg.vocab_size,
                                (n_micro, mb, seq)).astype(np.int32))
    step = tr._build()
    lr = jnp.asarray(1e-4, jnp.float32)
    compiled = step.lower(tr.params, tr.opt_state, tr.frozen, lr, x,
                          y).compile()
    ma = compiled.memory_analysis()
    return {"temp_bytes": int(ma.temp_size_in_bytes),
            "arg_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--hidden", type=int, default=256)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--n-micro", type=int, default=8)
    args = ap.parse_args()

    import jax

    import paddle_tpu as paddle
    from paddle_tpu.core.device import on_tpu as _on_tpu

    on_tpu = _on_tpu()
    if on_tpu:
        paddle.enable_compile_cache()
    devices = jax.devices()
    pp = args.layers if len(devices) >= args.layers else max(
        d for d in (4, 2, 1) if len(devices) >= d)
    if on_tpu and len(devices) == 1:
        pp = 1  # single chip: remat-per-tick still bounds the residuals

    res = {}
    for mode, key in (("F-then-B", "gpipe"), ("1F1B", "1f1b")):
        m = measure(mode, pp, args.layers, args.hidden, args.seq,
                    args.n_micro, devices)
        res[f"{key}_temp_bytes"] = m["temp_bytes"]
        res[f"{key}_arg_bytes"] = m["arg_bytes"]
    res["ratio"] = round(res["gpipe_temp_bytes"]
                         / max(res["1f1b_temp_bytes"], 1), 3)
    res["pp"] = pp
    res["platform"] = devices[0].platform
    res["config"] = {"layers": args.layers, "hidden": args.hidden,
                     "seq": args.seq, "n_micro": args.n_micro}
    print(json.dumps(res))


if __name__ == "__main__":
    main()
