"""Per-op micro-benchmark harness + regression gate.

Reference parity: paddle/fluid/operators/benchmark/op_tester.cc (config-driven
op timing: OpTesterConfig{op, inputs, attrs, repeat}) and the CI gate
tools/test_op_benchmark.sh + tools/check_op_benchmark_result.py (relative
before/after comparison, no absolute thresholds).

TPU-native design: each case times the JITTED op (compile excluded by a
warmup; block_until_ready for honest walls). `run` writes a JSON profile;
`compare` diffs two profiles and fails on >tolerance regressions — wire it to
CI exactly like the reference's shell gate.

Usage:
  python tools/op_benchmark.py run  [--out ops_bench.json] [--repeat 50]
  python tools/op_benchmark.py compare base.json new.json [--tol 0.05]
"""
import argparse
import json
import os
import sys
import time

import numpy as np

# runnable from any cwd (the flash case imports paddle_tpu)
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _cases():
    """The benchmark suite: (name, build() -> (fn, args)). Shapes mirror the
    reference configs' production-ish sizes, scaled to run on any backend."""
    import jax
    import jax.numpy as jnp

    r = np.random.RandomState(0)

    def f32(*s):
        return jnp.asarray(r.rand(*s).astype(np.float32))

    def i32(lo, hi, *s):
        return jnp.asarray(r.randint(lo, hi, s).astype(np.int32))

    return [
        ("matmul_1024", lambda: (lambda a, b: a @ b,
                                 (f32(1024, 1024), f32(1024, 1024)))),
        ("matmul_bf16_2048", lambda: (
            lambda a, b: (a @ b),
            (f32(2048, 2048).astype(jnp.bfloat16),
             f32(2048, 2048).astype(jnp.bfloat16)))),
        ("softmax_8kx512", lambda: (lambda x: jax.nn.softmax(x, axis=-1),
                                    (f32(8192, 512),))),
        ("layernorm_8kx768", lambda: (
            lambda x, g, b: g * (x - x.mean(-1, keepdims=True))
            / jnp.sqrt(x.var(-1, keepdims=True) + 1e-5) + b,
            (f32(8192, 768), f32(768), f32(768)))),
        ("gelu_16m", lambda: (jax.nn.gelu, (f32(4096, 4096),))),
        ("reduce_sum_16m", lambda: (lambda x: x.sum(), (f32(4096, 4096),))),
        ("transpose_4kx4k", lambda: (lambda x: x.T.copy() if hasattr(x, 'copy')
                                     else jnp.transpose(x),
                                     (f32(4096, 4096),))),
        ("embedding_1m", lambda: (
            lambda tbl, ids: jnp.take(tbl, ids, axis=0),
            (f32(65536, 128), i32(0, 65536, 8192)))),
        ("conv2d_128", lambda: (
            lambda x, w: jax.lax.conv_general_dilated(
                x, w, (1, 1), "SAME",
                dimension_numbers=("NCHW", "OIHW", "NCHW")),
            (f32(8, 64, 128, 128), f32(64, 64, 3, 3)))),
        ("attention_1k", lambda: (
            lambda q, k, v: jax.nn.softmax(
                (q @ k.transpose(0, 1, 3, 2)) / 8.0, axis=-1) @ v,
            (f32(4, 12, 1024, 64), f32(4, 12, 1024, 64),
             f32(4, 12, 1024, 64)))),
        ("cumsum_16m", lambda: (lambda x: jnp.cumsum(x, axis=-1),
                                (f32(4096, 4096),))),
        ("topk_1m", lambda: (lambda x: jax.lax.top_k(x, 128),
                             (f32(256, 16384),))),
        ("sgd_update_8m", lambda: (
            lambda p, g: p - 0.01 * g, (f32(2048, 4096), f32(2048, 4096)))),
        ("cross_entropy_lse_16kx50k", lambda: (
            # the r2 hard-label CE path: logsumexp+gather, no one_hot
            lambda lg, ids: (jax.nn.logsumexp(lg.astype(jnp.float32), axis=-1)
                             - jnp.take_along_axis(
                                 lg.astype(jnp.float32), ids[:, None],
                                 axis=-1)[:, 0]).mean(),
            (f32(2048, 8192).astype(jnp.bfloat16), i32(0, 8192, 2048)))),
        ("sequence_pool_sum_4kx128", lambda: (
            lambda x, ln: (x * (jnp.arange(x.shape[1])[None, :, None]
                                < ln[:, None, None])).sum(axis=1),
            (f32(4096, 128, 64), i32(1, 128, 4096)))),
        ("segment_sum_1m", lambda: (
            lambda d, ids: jax.ops.segment_sum(d, ids, num_segments=1024),
            (f32(1 << 20, 8), i32(0, 1024, 1 << 20)))),
        ("iou_matrix_2k", lambda: (
            lambda b: (lambda lt, rb: (jnp.maximum(rb - lt, 0).prod(-1)))(
                jnp.maximum(b[:, None, :2], b[None, :, :2]),
                jnp.minimum(b[:, None, 2:], b[None, :, 2:])),
            (f32(2048, 4),))),
        ("adam_update_8m", lambda: (
            lambda p, g, m, v: (
                p - 0.01 * (0.9 * m + 0.1 * g)
                / (jnp.sqrt(0.999 * v + 0.001 * g * g) + 1e-8)),
            (f32(2048, 4096), f32(2048, 4096), f32(2048, 4096),
             f32(2048, 4096)))),
        ("flash_attention", lambda: _flash_case(f32)),
        ("int8_kv_dequant_einsum_1k", lambda: (
            # the int8 KV-cache read path: dequant fused into the einsum
            lambda q, vals, scales: jnp.einsum(
                "bhtd,bhTd->bhtT", q,
                (vals.astype(jnp.float32) * scales)),
            (f32(1, 12, 1, 64), jnp.asarray(
                r.randint(-127, 128, (1, 12, 1024, 64)).astype(np.int8)),
             f32(1, 12, 1024, 1)))),
    ]


def _flash_case(f32):
    """The serving/training hot kernel: compiled at 2k seq on TPU;
    interpret mode off-TPU shrinks to 256 to stay tractable."""
    from paddle_tpu.core.device import on_tpu as _on_tpu
    from paddle_tpu.ops.flash_attention import flash_attention

    on_tpu = _on_tpu()
    s = 2048 if on_tpu else 256
    args = (f32(1, s, 4, 64), f32(1, s, 4, 64), f32(1, s, 4, 64))
    return (lambda q, k, v: flash_attention(q, k, v, causal=True,
                                            interpret=not on_tpu)), args


def run(out_path, repeat):
    import jax

    results = {}
    for name, build in _cases():
        fn, args = build()
        jitted = jax.jit(fn)
        jax.block_until_ready(jitted(*args))  # compile + warm
        t0 = time.perf_counter()
        for _ in range(repeat):
            out = jitted(*args)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / repeat
        results[name] = {"mean_us": round(dt * 1e6, 2)}
        print(f"{name:24s} {dt * 1e6:10.2f} us", file=sys.stderr)
    profile = {
        "platform": jax.devices()[0].platform,
        "repeat": repeat,
        "ops": results,
    }
    with open(out_path, "w") as f:
        json.dump(profile, f, indent=1)
    print(json.dumps({"wrote": out_path, "n_ops": len(results)}))
    return profile


def compare(base_path, new_path, tol):
    """check_op_benchmark_result.py parity: relative regression gate."""
    with open(base_path) as f:
        base = json.load(f)
    with open(new_path) as f:
        new = json.load(f)
    if base.get("platform") != new.get("platform"):
        print(f"WARNING: platform mismatch ({base.get('platform')} vs "
              f"{new.get('platform')}); timings not comparable",
              file=sys.stderr)
    regressions = []
    for name, b in base["ops"].items():
        n = new["ops"].get(name)
        if n is None:
            print(f"MISSING  {name} (removed from suite?)", file=sys.stderr)
            continue
        ratio = n["mean_us"] / max(b["mean_us"], 1e-9)
        flag = " "
        if ratio > 1 + tol:
            flag = "R"  # regression
            regressions.append((name, ratio))
        elif ratio < 1 - tol:
            flag = "+"  # improvement
        print(f"{flag} {name:24s} {b['mean_us']:10.2f} -> {n['mean_us']:10.2f}"
              f" us  ({ratio - 1:+.1%})", file=sys.stderr)
    # ops only in the NEW profile are un-gated until the baseline is
    # regenerated — surface them so added hot-path kernels aren't silently
    # excluded from the regression gate
    new_only = sorted(set(new["ops"]) - set(base["ops"]))
    for name in new_only:
        print(f"N {name:24s} {'':>10s}    {new['ops'][name]['mean_us']:10.2f}"
              f" us  (NEW — no baseline; regenerate to gate)",
              file=sys.stderr)
    if regressions:
        print(json.dumps({"status": "FAIL", "regressions": [
            {"op": n, "slowdown": round(r, 3)} for n, r in regressions]}))
        return 1
    print(json.dumps({"status": "OK", "n_compared": len(base["ops"]),
                      "n_new_ungated": len(new_only)}))
    return 0


def main():
    ap = argparse.ArgumentParser(__doc__)
    sub = ap.add_subparsers(dest="cmd", required=True)
    p_run = sub.add_parser("run")
    p_run.add_argument("--out", default="ops_bench.json")
    p_run.add_argument("--repeat", type=int, default=50)
    p_run.add_argument("--cpu", action="store_true",
                       help="force the CPU backend")
    p_cmp = sub.add_parser("compare")
    p_cmp.add_argument("base")
    p_cmp.add_argument("new")
    p_cmp.add_argument("--tol", type=float, default=0.05)
    args = ap.parse_args()
    if args.cmd == "run":
        if args.cpu:
            import jax

            jax.config.update("jax_platforms", "cpu")
        run(args.out, args.repeat)
        return 0
    return compare(args.base, args.new, args.tol)


if __name__ == "__main__":
    sys.exit(main())
