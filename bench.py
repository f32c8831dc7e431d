"""Benchmark: GPT-2 small causal-LM training throughput on one TPU chip.

Prints ONE JSON line per completed leg: {"metric", "value", "unit",
"vs_baseline", "device"} (+ mfu/mbu); the LAST line is the most complete.

One process. It refuses to run without a TPU (exit 2, no metric line — a
number from a CPU is never printed under a device metric's name), runs the
legs it is asked for at their real sizes, and exits non-zero if one fails:
nothing is caught and carried on from.

Metric: tokens/sec/chip for a full jitted train step (fwd+bwd+AdamW) in bfloat16
matmuls — the BASELINE.md north-star family (ERNIE/BERT-class tokens/sec/chip).
vs_baseline: ratio against the reference-class target of 10_000 tokens/sec/device
(0.6 × a ~16.6k tok/s A100+NCCL BERT-base-class figure — BASELINE.json's ≥60% goal),
since the reference repo publishes no absolute numbers (BASELINE.md: "published: {}").

The only driver-recorded number so far is 61,879.8 tokens/s/chip for the default
leg (2026-07-29, shape and device kind not recorded); sweep locally with --sweep.

Other BASELINE.md milestone configs measure standalone via --config:
  --config resnet50      ResNet-50 @to_static-style jitted train step, imgs/s
  --config bert_dp       BERT-base pretrain step, tokens/s
  --config lenet         LeNet hapi Model train_batch loop, steps/s
  --config gpt2s_decode  KV-cache decode, pure new-tokens/s (prefill excluded)
  --config ppyolo        PP-YOLOE train step imgs/s (+ infer+NMS imgs/s extra)
  --config gpt2m         GPT-2-medium (~350M) train step, tokens/s (BASELINE #4 class)
  --config gpt2s_16k     GPT-2s train step at seq 16384 (flash long-context)
  --config gpt2s_serve   continuous-batching ServingEngine, aggregate new tok/s
The default (gpt2s) run also appends an "extra" dict with a quick ResNet-50
and decode measurement (disable with --no-extra).

Usage: python bench.py [--batch B] [--seq S] [--steps N] [--sweep]
                       [--config gpt2s|resnet50|bert_dp|lenet|gpt2s_decode|
                                 ppyolo|gpt2m|gpt2s_16k|gpt2s_serve]
                       [--no-extra] [--window W]
"""
import argparse
import json
import sys
import time

import numpy as np

BASELINE_TOKENS_PER_SEC = 10_000.0

#: the one chip these legs' MFU/MBU denominators describe (TPU v5e
#: datasheet: 197 TFLOP/s bf16, 819 GB/s HBM). Any other device kind is an
#: error in _v5e_peak, not a default. (One peaks table keyed by device kind
#: is ROADMAP S2.)
_V5E_PEAKS = {"flops": 197e12, "hbm_bw": 819e9}


def _device():
    """What every printed result names: the device as jax reports it."""
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def _v5e_peak(what):
    kind = _device()["kind"].lower()
    if "v5e" not in kind and "v5 lite" not in kind:
        raise RuntimeError(
            f"bench.py's MFU/MBU peaks are TPU v5e's; this is {kind!r}")
    return _V5E_PEAKS[what]


def _emit(line):
    """Print one metric line (flushed). Every line names the device it ran
    on and carries the runtime-telemetry snapshot ("monitor": compile
    counts, step/TTFT latencies, host syncs...) so the number is
    attributable."""
    from paddle_tpu import monitor

    print(json.dumps(dict(line, device=_device(),
                          monitor=monitor.flatten(monitor.snapshot()))),
          flush=True)


def _start_leg(leg):
    """Open the NEXT measurement leg's goodput run (FLAGS_goodput,
    docs/OBSERVABILITY.md "Goodput ledger"): ``start_run`` finalizes the
    previous leg's run — its bucket breakdown lands as one perf-ledger row
    at site=run/goodput — so each leg's wall time is accounted separately.
    Disarmed this is one flag lookup."""
    from paddle_tpu import flags

    if flags.get_flag("goodput", False):
        from paddle_tpu.monitor import goodput

        goodput.start_run("bench/" + leg)


def _goodput_close():
    """Finalize the LAST leg's goodput run (atexit: main has several exit
    paths and the final row must land on all of them)."""
    from paddle_tpu import flags

    if flags.get_flag("goodput", False):
        from paddle_tpu.monitor import goodput

        goodput.end_run()


def _ledger_row(leg, data):
    """With FLAGS_perf_ledger armed, a completed leg's numbers also land
    as one perf-ledger row (site=bench/<leg>)."""
    from paddle_tpu import flags

    if flags.get_flag("perf_ledger", False):
        from paddle_tpu.monitor import perfledger

        perfledger.record_leg(leg, data)


# cumulative compile-cache counts at the previous heartbeat, so each
# bench_phase line also carries the DELTA attributable to its phase
_LAST_CACHE_COUNTS = {}


def _compile_cache_counts():
    """Aggregate compile_cache_total by (event, source) across all sites —
    the per-phase attribution signal: a phase whose heartbeats show only
    miss_fresh deltas spent its time compiling; one showing hit_memory
    ran warmed programs and its time went to runtime."""
    from paddle_tpu import monitor

    out = {}
    metric = monitor.default_registry().get("compile_cache_total")
    if metric is None:
        return out
    for s in metric.series():
        key = (f"{s.labels.get('event', '?')}_"
               f"{s.labels.get('source', '?')}")
        out[key] = out.get(key, 0) + int(s.value)
    return out


def _heartbeat(phase, status="start", **fields):
    """Phase heartbeat into the monitor JSONL event log (where
    FLAGS_monitor_log_path names one) and the flight recorder (where
    FLAGS_blackbox is on): the log's last heartbeat names the phase a run
    died in. Each line carries the compile-cache hit/miss counts by source
    (memory|fresh) plus the delta since the previous heartbeat, so a
    phase is attributable to compile vs runtime from the artifact alone."""
    from paddle_tpu import monitor, trace

    counts = _compile_cache_counts()
    delta = {k: v - _LAST_CACHE_COUNTS.get(k, 0)
             for k, v in counts.items()
             if v != _LAST_CACHE_COUNTS.get(k, 0)}
    _LAST_CACHE_COUNTS.clear()
    _LAST_CACHE_COUNTS.update(counts)
    # trace summary (FLAGS_trace runs): span count + top-3 span totals, so
    # the heartbeat also names WHERE the traced time went
    tsum = trace.snapshot_summary(3)
    monitor.blackbox.beacon("bench/phase")
    monitor.blackbox.set_context("bench_phase", f"{phase}:{status}")
    monitor.blackbox.note("bench_phase", phase=phase, status=status)
    monitor.log_event("bench_phase", phase=phase, status=status,
                      compile_cache=counts, compile_cache_delta=delta,
                      trace_spans=tsum["spans"], trace_top=tsum["top"],
                      **fields)


def _n_params(cfg):
    """Parameter count for the GPT family: embedding + transformer blocks +
    lm head (tied-ish). Shared by MFU (all params matter for FLOPs) and
    MBU (which subtracts the gathered-not-streamed embedding)."""
    h, L, v = cfg.hidden_size, cfg.num_layers, cfg.vocab_size
    return v * h + L * (12 * h * h) + h * v


def _model_flops_per_token(cfg):
    """Approximate training FLOPs/token (fwd+bwd ~= 6*N params + attention).
    Sliding-window attention only computes an O(s*W) band — charge that,
    not O(s^2), or windowed MFU overstates by the skipped blocks."""
    h, L, s = cfg.hidden_size, cfg.num_layers, cfg.max_seq_len
    eff = min(getattr(cfg, "attention_window", None) or s, s)
    attn = L * 12 * eff * h  # 2 matmuls of [s,eff]x[eff,s-ish] per layer
    return 6 * _n_params(cfg) + attn


def _gpt2s_cfg(seq, window=None):
    """The benchmark's GPT-2-small config — single source for the train
    AND decode configs. window sets sliding-window attention (the flash
    kernels then skip out-of-band blocks: O(s*W) instead of O(s^2))."""
    from paddle_tpu.models import GPTConfig

    return GPTConfig(vocab_size=50304, hidden_size=768, num_layers=12,
                     num_heads=12, max_seq_len=seq, dropout=0.0,
                     attention_window=window)


def _gpt2m_cfg(seq, window=None):
    """GPT-2-medium (~350M params): the BASELINE #4 model class (ERNIE-1.0 /
    GPT-2 medium). Single-chip it exercises HBM pressure at real scale."""
    from paddle_tpu.models import GPTConfig

    return GPTConfig(vocab_size=50304, hidden_size=1024, num_layers=24,
                     num_heads=16, max_seq_len=seq, dropout=0.0,
                     attention_window=window)


def _gpt_train_setup(cfg, batch):
    """Model+trainer+data for a GPT train leg — shared with
    tools/profile_gpt.py so the profiled program IS the benchmarked one."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.distributed.mesh import build_mesh
    from paddle_tpu.distributed.spmd import SpmdTrainer
    from paddle_tpu.models import GPTForCausalLM, GPTPretrainLoss

    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    mesh = build_mesh((1,), ("dp",), devices=jax.devices()[:1])
    trainer = SpmdTrainer(model, opt, loss_fn=GPTPretrainLoss(), mesh=mesh)

    rng = np.random.RandomState(0)
    seq = cfg.max_seq_len
    ids = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32))
    labels = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32))
    return trainer, ids, labels


def run_config(batch, seq, steps, quiet=False, cfg_fn=_gpt2s_cfg,
               window=None):
    import paddle_tpu as paddle

    cfg = cfg_fn(seq, window)
    trainer, ids, labels = _gpt_train_setup(cfg, batch)

    with paddle.amp.auto_cast(True, dtype="bfloat16"):
        # warmup + compile (the host copy forces completion)
        np.asarray(trainer.train_step(ids, labels)._data)
        t0 = time.perf_counter()
        loss = None
        for _ in range(steps):
            loss = trainer.train_step(ids, labels)
        # trailing sync: last loss + a param leaf depend on every prior step
        np.asarray(loss._data)
        first = next(iter(trainer.params))
        np.asarray(trainer.params[first][(0,) * trainer.params[first].ndim])
        dt = time.perf_counter() - t0

    tokens_per_sec = batch * seq * steps / dt
    mfu = tokens_per_sec * _model_flops_per_token(cfg) / _v5e_peak("flops")
    if not quiet:
        print(f"  batch={batch} seq={seq}: {tokens_per_sec:,.0f} tok/s "
              f"(mfu~{mfu:.1%})", file=sys.stderr)
    return tokens_per_sec, mfu


def run_resnet50(batch, steps, quiet=False):
    """BASELINE config #2: ResNet-50 fwd+bwd+Momentum, imgs/s/chip."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.distributed.mesh import build_mesh
    from paddle_tpu.distributed.spmd import SpmdTrainer
    from paddle_tpu.vision.models import resnet50

    paddle.seed(0)
    model = resnet50(num_classes=1000)
    opt = paddle.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                    parameters=model.parameters())
    loss_layer = paddle.nn.CrossEntropyLoss()
    mesh = build_mesh((1,), ("dp",), devices=jax.devices()[:1])
    trainer = SpmdTrainer(model, opt, loss_fn=loss_layer, mesh=mesh)

    rng = np.random.RandomState(0)
    imgs = paddle.to_tensor(rng.rand(batch, 3, 224, 224).astype(np.float32))
    labels = paddle.to_tensor(rng.randint(0, 1000, (batch,)).astype(np.int32))

    with paddle.amp.auto_cast(True, dtype="bfloat16"):
        np.asarray(trainer.train_step(imgs, labels)._data)  # compile+sync
        t0 = time.perf_counter()
        loss = None
        for _ in range(steps):
            loss = trainer.train_step(imgs, labels)
        np.asarray(loss._data)
        dt = time.perf_counter() - t0
    ips = batch * steps / dt
    if not quiet:
        print(f"  resnet50 batch={batch}: {ips:,.1f} imgs/s", file=sys.stderr)
    return ips


def run_bert(batch, seq, steps, quiet=False):
    """BASELINE config #3: BERT-base pretrain step (MLM+NSP), tokens/s/chip."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.distributed.mesh import build_mesh
    from paddle_tpu.distributed.spmd import SpmdTrainer
    from paddle_tpu.models import BertConfig, BertForPretraining, \
        BertPretrainLoss

    cfg = BertConfig(dropout=0.0)  # base: 12L/768h/12heads, 512 pos

    paddle.seed(0)
    model = BertForPretraining(cfg)
    loss_layer = BertPretrainLoss()
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    mesh = build_mesh((1,), ("dp",), devices=jax.devices()[:1])
    trainer = SpmdTrainer(model, opt, loss_fn=loss_layer, mesh=mesh)

    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32))
    tok_type = paddle.to_tensor(np.zeros((batch, seq), np.int32))
    mlm_labels = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32))

    with paddle.amp.auto_cast(True, dtype="bfloat16"):
        np.asarray(trainer.train_step(ids, tok_type, mlm_labels)._data)
        t0 = time.perf_counter()
        loss = None
        for _ in range(steps):
            loss = trainer.train_step(ids, tok_type, mlm_labels)
        np.asarray(loss._data)
        dt = time.perf_counter() - t0
    tps = batch * seq * steps / dt
    if not quiet:
        print(f"  bert batch={batch} seq={seq}: {tps:,.0f} tok/s",
              file=sys.stderr)
    return tps


def run_lenet(batch, steps, quiet=False):
    """BASELINE config #1: LeNet hapi Model train_batch loop, steps/s."""
    import paddle_tpu as paddle
    from paddle_tpu.vision.models import LeNet

    paddle.seed(0)
    model = paddle.Model(LeNet())
    model.prepare(paddle.optimizer.Adam(learning_rate=1e-3,
                                        parameters=model.network.parameters()),
                  paddle.nn.CrossEntropyLoss())
    rng = np.random.RandomState(0)
    imgs = rng.rand(batch, 1, 28, 28).astype(np.float32)
    labels = rng.randint(0, 10, (batch, 1)).astype(np.int64)
    model.train_batch([imgs], [labels])  # compile
    t0 = time.perf_counter()
    for _ in range(steps):
        model.train_batch([imgs], [labels])
    dt = time.perf_counter() - t0
    sps = steps / dt
    if not quiet:
        print(f"  lenet batch={batch}: {sps:,.1f} steps/s", file=sys.stderr)
    return sps


def _ppyolo_setup(batch):
    """Shared model+data setup for the two ppyolo measurements."""
    import paddle_tpu as paddle
    from paddle_tpu.vision.models import PPYOLOE

    size, model = 640, PPYOLOE(num_classes=80, width=64, depth=2)
    paddle.seed(0)
    rng = np.random.RandomState(0)
    imgs = paddle.to_tensor(rng.rand(batch, 3, size, size).astype(np.float32))
    return size, model, imgs


def run_ppyolo_train(batch, steps, quiet=False, setup=None):
    """BASELINE config #5 (train half): PP-YOLOE jitted fwd+bwd+Momentum
    step via SpmdTrainer, imgs/s/chip. setup: see run_ppyolo_infer."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.distributed.mesh import build_mesh
    from paddle_tpu.distributed.spmd import SpmdTrainer
    from paddle_tpu.vision.models import PPYOLOELoss

    size, model, imgs = setup if setup is not None else _ppyolo_setup(batch)

    class TrainStep(nn.Layer):
        """Detector + loss fused so SpmdTrainer jits loss(decode(model(x)))."""

        def __init__(self, det, loss_fn):
            super().__init__()
            self.det = det
            self.det_loss = loss_fn

        def forward(self, x, gt_boxes, gt_labels):
            decoded = self.det.decode(self.det(x))
            return self.det_loss(decoded, (gt_boxes, gt_labels))

    step_layer = TrainStep(model, PPYOLOELoss(num_classes=80))
    opt = paddle.optimizer.Momentum(learning_rate=0.01, momentum=0.9,
                                    parameters=step_layer.parameters())
    mesh = build_mesh((1,), ("dp",), devices=jax.devices()[:1])
    trainer = SpmdTrainer(step_layer, opt, loss_fn=None, mesh=mesh)

    A = sum((size // s) ** 2 for s in model.strides)
    rng = np.random.RandomState(1)
    gt_boxes = paddle.to_tensor(
        (rng.rand(batch, A, 4) * size).astype(np.float32))
    gt_labels = paddle.to_tensor(
        rng.randint(0, 81, (batch, A)).astype(np.int64))  # 80 == background

    with paddle.amp.auto_cast(True, dtype="bfloat16"):
        np.asarray(trainer.train_step(imgs, gt_boxes, gt_labels)._data)
        t0 = time.perf_counter()
        loss = None
        for _ in range(steps):
            loss = trainer.train_step(imgs, gt_boxes, gt_labels)
        np.asarray(loss._data)
        train_ips = batch * steps / (time.perf_counter() - t0)
    if not quiet:
        print(f"  ppyolo batch={batch} size={size}: train {train_ips:,.1f} "
              f"imgs/s", file=sys.stderr)
    return train_ips


def run_ppyolo_infer(batch, steps, quiet=False, setup=None):
    """BASELINE config #5 (infer half): forward + decode + multiclass-NMS
    postprocess as ONE @to_static-compiled program (Pallas NMS) in bf16
    (the serving convention, matching gpt2s_decode), imgs/s/chip.
    Pass setup=(size, model, imgs) to reuse the train half's model and
    device-resident batch instead of rebuilding them."""
    import paddle_tpu as paddle

    size, model, imgs = setup if setup is not None else _ppyolo_setup(batch)
    model.eval()

    infer_fn = paddle.jit.to_static(
        lambda im: model.postprocess(model(im), score_threshold=0.3,
                                     keep_top_k=100))

    def infer_once():
        _, counts = infer_fn(imgs)
        np.asarray(counts._data)  # sync

    with paddle.amp.auto_cast(True, dtype="bfloat16"):
        infer_once()  # compile
        t0 = time.perf_counter()
        for _ in range(steps):
            infer_once()
        infer_ips = batch * steps / (time.perf_counter() - t0)
    if not quiet:
        print(f"  ppyolo batch={batch} size={size}: infer+nms "
              f"{infer_ips:,.1f} imgs/s", file=sys.stderr)
    return infer_ips


def run_decode(batch, steps, quiet=False, cache_dtype=None):
    """Serving-side metric: KV-cache decode, PURE new-tokens/s/chip (GPT-2
    small, prompt 128, greedy, bf16 cache). Prefill time is excluded by
    differencing a max_new_tokens=1 run against the full run at identical
    reps. cache_dtype='int8' measures the quantized-cache serving config.
    Returns (new_tokens/s, MBU) — MBU computed HERE, from the exact
    prompt/new_tokens/cfg this function measured (one source of truth)."""
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM

    cfg = _gpt2s_cfg(1024)
    new_tokens = 256

    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    model.eval()
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (batch, 128)).astype(np.int32))
    reps = max(1, steps // 4)

    def timed(n):
        np.asarray(model.generate(ids, max_new_tokens=n, temperature=0.0,
                                  dtype="bfloat16",
                                  cache_dtype=cache_dtype)._data)  # compile
        t0 = time.perf_counter()
        out = None
        for _ in range(reps):
            out = model.generate(ids, max_new_tokens=n, temperature=0.0,
                                 dtype="bfloat16", cache_dtype=cache_dtype)
        np.asarray(out._data)
        return time.perf_counter() - t0

    dt_full = timed(new_tokens)
    dt_prefill = timed(1)  # prefill + a single decode step
    decode_dt = max(dt_full - dt_prefill, 1e-9)
    tps = batch * (new_tokens - 1) * reps / decode_dt
    mbu = _decode_mbu(cfg, batch, tps, 128, new_tokens,
                      cache_dtype=cache_dtype)
    if not quiet:
        print(f"  decode batch={batch} cache={cache_dtype or 'dtype'}: "
              f"{tps:,.0f} new tok/s mbu~{mbu:.1%} (full {dt_full:.2f}s, "
              f"prefill {dt_prefill:.2f}s)", file=sys.stderr)
    return tps, mbu


def _decode_mbu(cfg, batch, tps, prompt, new_tokens, cache_dtype=None):
    """Model-bandwidth-utilization for the HBM-bound decode loop — the
    serving dual of training MFU. Bytes each decode step must move from
    HBM: every parameter (bf16 serving weights, read once per step,
    amortized over the batch) plus the KV cache at its average length
    over the run. MBU = tokens/s x bytes/token / HBM bandwidth, against
    the same v5e chip as the MFU peak.

    The input-embedding table is NOT charged: a decode step gathers only
    `batch` rows of it (negligible), unlike the lm-head matmul which
    streams its full [h, v] weight for the logits."""
    h, L, v = cfg.hidden_size, cfg.num_layers, cfg.vocab_size
    streamed_params = _n_params(cfg) - v * h  # minus the gathered embedding
    kv_heads = getattr(cfg, "num_kv_heads", None) or cfg.num_heads
    head_dim = h // cfg.num_heads
    # quantized caches stream 1-byte values PLUS the f32 per-row scale
    # (4 bytes per head_dim-element row) — omit it and quantized MBU reads
    # a few percent low vs the bf16 leg
    cache_el = 1 if cache_dtype in ("int8", "fp8") else 2
    avg_len = prompt + new_tokens / 2
    row_bytes = head_dim * cache_el + \
        (4 if cache_dtype in ("int8", "fp8") else 0)
    cache_bytes = batch * 2 * L * avg_len * kv_heads * row_bytes
    bytes_per_token = (2 * streamed_params + cache_bytes) / batch
    return tps * bytes_per_token / _v5e_peak("hbm_bw")


def _serve_model(cfg):
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM

    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    model.eval()
    return model


def run_serve(slots, n_requests, quiet=False):
    """Serving-engine metric: continuous batching over one fixed KV cache
    (bf16 params/cache, mixed prompt lengths, eos-free greedy), aggregate
    NEW tokens/s across all requests — the serving dual of gpt2s_decode's
    static-batch number."""
    from paddle_tpu.inference.serving import ServingEngine

    cfg = _gpt2s_cfg(1024)
    new_tokens = 128
    eng = ServingEngine(_serve_model(cfg), max_batch=slots, dtype="bfloat16")
    rng = np.random.RandomState(0)
    lens = [int(rng.randint(32, 128)) for _ in range(n_requests)]
    prompts = [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in lens]
    # warmup: compile EVERY prefill bucket the timed prompts will hit,
    # plus the decode step, off the clock
    seen_buckets = set()
    for p in prompts:
        b = eng._bucket(len(p))
        if b not in seen_buckets:
            seen_buckets.add(b)
            eng.submit(p, max_new_tokens=2)
    eng.run_until_complete()

    t0 = time.perf_counter()
    for p in prompts:
        eng.submit(p, max_new_tokens=new_tokens)
    res = eng.run_until_complete()
    dt = time.perf_counter() - t0
    # res accumulates across the engine's lifetime: count only the timed
    # requests (the warmups ran with max_new_tokens=2)
    total_new = sum(len(res[r].tokens) for r in res
                    if res[r].max_new_tokens == new_tokens)
    tps = total_new / dt
    if not quiet:
        print(f"  serve slots={slots} reqs={n_requests}: {tps:,.0f} "
              f"new tok/s aggregate", file=sys.stderr)
    return tps


def run_serve_mixed(slots, n_requests, quiet=False, cfg=None,
                    new_tokens=128, chunk=128, dtype="bfloat16"):
    """Serving realism scenario (the production shape, not an all-greedy
    drain): requests ARRIVE STAGGERED over the run, ~1/3 of them sample
    (temperature 0.8, top_k 50) while the rest stay greedy, and CHUNKED
    PREFILL is on so long prompts never stall running decodes. Reports
    (aggregate new tok/s, p50/p99 inter-token ms, p50/p99 time-to-first-
    token ms) — the latency percentiles are what the chunked-prefill
    design exists to protect. cfg/new_tokens/chunk/dtype default to the
    benchmark's GPT-2-small leg; a test hands in a small model of its own
    to exercise the scenario's bookkeeping."""
    from paddle_tpu.inference.serving import ServingEngine

    cfg = cfg or _gpt2s_cfg(1024)
    eng = ServingEngine(_serve_model(cfg), max_batch=slots, dtype=dtype,
                        prefill_chunk=chunk)
    rng = np.random.RandomState(1)
    lens = [int(rng.randint(32, 128)) for _ in range(n_requests)]
    prompts = [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in lens]
    kwargs = [({"temperature": 0.8, "top_k": 50, "seed": i}
               if i % 3 == 0 else {}) for i in range(n_requests)]

    # warmup off the clock: chunk program, greedy step AND sampling step
    eng.submit(prompts[0], max_new_tokens=2)
    eng.submit(prompts[-1], max_new_tokens=2, temperature=0.8, top_k=50,
               seed=0)
    eng.run_until_complete()

    tracked = {}      # rid -> (Request, submit_time)
    counts = {}       # rid -> tokens seen
    last_emit = {}    # rid -> timestamp of last emitted token
    inter_ms, ttft_ms = [], []
    pending = list(zip(prompts, kwargs))
    step_i = 0
    t0 = time.perf_counter()
    while pending or eng.has_work():
        if step_i % 3 == 0:    # staggered arrivals: 2 requests per 3 steps
            for _ in range(2):
                if pending:
                    p, kw = pending.pop(0)
                    rid = eng.submit(p, max_new_tokens=new_tokens, **kw)
                    tracked[rid] = (eng.get_request(rid),
                                    time.perf_counter())
                    counts[rid] = 0
        eng.step()
        now = time.perf_counter()
        for rid, (req, t_submit) in tracked.items():
            n = len(req.output_ids)
            if n > counts[rid]:
                if counts[rid] == 0:
                    ttft_ms.append((now - t_submit) * 1e3)
                else:
                    inter_ms.append((now - last_emit[rid]) * 1e3)
                last_emit[rid] = now
                counts[rid] = n
        step_i += 1
    dt = time.perf_counter() - t0
    total_new = sum(counts.values())
    tps = total_new / dt
    p50 = float(np.percentile(inter_ms, 50)) if inter_ms else 0.0
    p99 = float(np.percentile(inter_ms, 99)) if inter_ms else 0.0
    t50 = float(np.percentile(ttft_ms, 50)) if ttft_ms else 0.0
    t99 = float(np.percentile(ttft_ms, 99)) if ttft_ms else 0.0
    if not quiet:
        print(f"  serve-mixed slots={slots} reqs={n_requests}: {tps:,.0f} "
              f"tok/s, inter-token p50={p50:.1f}ms p99={p99:.1f}ms, "
              f"ttft p50={t50:.1f}ms p99={t99:.1f}ms", file=sys.stderr)
    return tps, p50, p99, t50, t99


def _train_line(metric, tps, mfu, window=None, **fields):
    return dict({"metric": metric + (f"_w{window}" if window else ""),
                 "value": round(tps, 1), "unit": "tokens/s",
                 "vs_baseline": round(tps / BASELINE_TOKENS_PER_SEC, 3),
                 "mfu": round(mfu, 4)}, **fields)


def _run_config_leg(args):
    """One --config leg: measure, return its final metric line."""
    cfg_name = args.config
    extra = {}
    fields = {}
    if cfg_name == "resnet50":
        v = run_resnet50(args.batch or 64, args.steps, quiet=True)
        metric, unit, base = "resnet50_train_imgs_per_sec_per_chip", \
            "imgs/s", 170.0  # ~0.6x a V100-class ResNet-50 fp16 figure
    elif cfg_name == "bert_dp":
        v = run_bert(args.batch or 16, args.seq or 512, args.steps,
                     quiet=True)
        metric, unit, base = "bert_base_train_tokens_per_sec_per_chip", \
            "tokens/s", BASELINE_TOKENS_PER_SEC
    elif cfg_name == "gpt2s_decode":
        b = args.batch or 8
        v, mbu = run_decode(b, args.steps, quiet=True)
        metric, unit, base = "gpt2s_decode_new_tokens_per_sec_per_chip", \
            "tokens/s", 1000.0  # ~A100-class HF GPT-2 batch decode proxy
        # one key, one location: the measured config's own MBU is always
        # top-level "mbu"; extras carry only the quantized-KV A/B pairs
        fields["mbu"] = round(mbu, 4)
        for kv in ("int8", "fp8"):
            tps_q, mbu_q = run_decode(b, args.steps, quiet=True,
                                      cache_dtype=kv)
            extra[f"gpt2s_decode_{kv}_kv_new_tokens_per_sec_per_chip"] = \
                round(tps_q, 1)
            extra[f"gpt2s_decode_{kv}_kv_mbu"] = round(mbu_q, 4)
    elif cfg_name == "gpt2s_serve":
        slots = args.batch or 8
        v = run_serve(slots, 3 * slots, quiet=True)
        metric, unit, base = \
            "gpt2s_serve_continuous_new_tokens_per_sec_per_chip", \
            "tokens/s", 1000.0  # same class target as gpt2s_decode
        # the REALISM scenario (staggered arrivals + sampling mix +
        # chunked prefill) enriches the drain number with percentiles
        mtps, p50, p99, t50, t99 = run_serve_mixed(slots, 3 * slots,
                                                   quiet=True)
        extra = {"mixed_new_tokens_per_sec": round(mtps, 1),
                 "mixed_inter_token_p50_ms": round(p50, 2),
                 "mixed_inter_token_p99_ms": round(p99, 2),
                 "mixed_ttft_p50_ms": round(t50, 2),
                 "mixed_ttft_p99_ms": round(t99, 2)}
    elif cfg_name == "gpt2s_16k":
        # long-context single chip: flash attention is what makes 16k
        # fit (VMEM-resident blocks; nothing scales with seq in VMEM)
        v, mfu = run_config(args.batch or 1, args.seq or 16384, args.steps,
                            quiet=True, window=args.window)
        return _train_line("gpt2s_16k_train_tokens_per_sec_per_chip", v,
                           mfu, args.window, config=cfg_name)
    elif cfg_name == "gpt2m":
        v, mfu = run_config(args.batch or 8, args.seq or 1024, args.steps,
                            quiet=True, cfg_fn=_gpt2m_cfg)
        # same 10k tok/s/device class target as the BERT/ERNIE row
        return _train_line("gpt2m_train_tokens_per_sec_per_chip", v, mfu,
                           config=cfg_name)
    elif cfg_name == "ppyolo":
        b = args.batch or 8
        setup = _ppyolo_setup(b)
        v = run_ppyolo_train(b, args.steps, quiet=True, setup=setup)
        metric, unit, base = "ppyoloe_train_imgs_per_sec_per_chip", \
            "imgs/s", 60.0  # ~0.6x a V100-class PP-YOLOE-s 640px figure
        if not args.no_extra:
            extra = {"ppyoloe_infer_nms_imgs_per_sec_per_chip": round(
                run_ppyolo_infer(b, args.steps, quiet=True, setup=setup),
                1)}
    else:
        v = run_lenet(args.batch or 64, args.steps, quiet=True)
        metric, unit, base = "lenet_fit_steps_per_sec", "steps/s", 100.0
    line = {"metric": metric, "value": round(v, 1), "unit": unit,
            "vs_baseline": round(v / base, 3), "config": cfg_name}
    line.update(fields)
    if extra:
        line["extra"] = extra
    return line


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--sweep", action="store_true",
                    help="sweep batch/seq configs, report the best")
    ap.add_argument("--config", default="gpt2s",
                    choices=["gpt2s", "resnet50", "bert_dp", "lenet",
                             "gpt2s_decode", "ppyolo", "gpt2m",
                             "gpt2s_16k", "gpt2s_serve"])
    ap.add_argument("--no-extra", action="store_true",
                    help="skip the appended quick ResNet-50/decode (or "
                         "ppyolo infer) measurements")
    ap.add_argument("--window", type=int, default=None,
                    help="sliding-window attention width for gpt2s/gpt2s_16k "
                         "(flash kernels skip out-of-band blocks)")
    args = ap.parse_args()

    import paddle_tpu as paddle

    dev = _device()
    if dev["platform"] != "tpu":
        # a measurement path that finds no chip fails; it never shrinks
        # the model and prints a CPU number under a device metric's name
        print(f"bench.py measures on a TPU; jax found {dev['platform']!r} "
              f"({dev['kind']}). Nothing was measured.", file=sys.stderr)
        return 2
    print(f"  device: {dev}; jax compile cache: "
          f"{paddle.enable_compile_cache()}", file=sys.stderr)
    _heartbeat("device_init", "done", **dev)

    # goodput accountant (FLAGS_goodput): every leg below opens its own
    # run via _start_leg; the atexit hook finalizes the last one
    import atexit

    atexit.register(_goodput_close)

    if args.config != "gpt2s":
        leg = "config:" + args.config
        _heartbeat(leg)
        _start_leg(leg)
        line = _run_config_leg(args)
        _emit(line)
        _ledger_row(leg, line)
        _heartbeat(leg, "done")
        return 0

    # batch 16 x seq 1024 is the shape of the one driver-recorded run
    batch = args.batch or 16
    seq = args.seq or 1024

    if args.sweep:
        _heartbeat("sweep")
        _start_leg("sweep")
        best = (0.0, 0.0, None)
        for b, s in ((8, 1024), (16, 1024), (24, 1024), (16, 2048),
                     (8, 2048), (4, 4096), (8, 4096)):
            tps, mfu = run_config(b, s, args.steps, window=args.window)
            if tps > best[0]:
                best = (tps, mfu, (b, s))
        tps, mfu, cfg = best
        _emit(_train_line("gpt2s_train_tokens_per_sec_per_chip", tps, mfu,
                          args.window, config=cfg))
        return 0

    _heartbeat("headline_gpt2s", batch=batch, seq=seq)
    _start_leg("headline")
    tps, mfu = run_config(batch, seq, args.steps, quiet=True,
                          window=args.window)
    _heartbeat("headline_gpt2s", "done")
    line = _train_line("gpt2s_train_tokens_per_sec_per_chip", tps, mfu,
                       args.window)
    _emit(line)
    _ledger_row("headline", line)
    if not args.no_extra:
        # the ResNet-50 milestone (BASELINE #2) and the serving decode
        # metric with MBU ride along; each re-emits the enriched line
        def _resnet_extra():
            return {"resnet50_train_imgs_per_sec_per_chip":
                    round(run_resnet50(64, 10, quiet=True), 1)}

        def _decode_extra():
            dtps, dmbu = run_decode(8, 20, quiet=True)
            return {"gpt2s_decode_new_tokens_per_sec_per_chip":
                    round(dtps, 1),
                    "gpt2s_decode_mbu": round(dmbu, 4)}

        extra = {}
        for extra_leg, measure in (("extra:resnet50", _resnet_extra),
                                   ("extra:gpt2s_decode", _decode_extra)):
            _heartbeat(extra_leg)
            _start_leg(extra_leg)
            got = measure()
            _ledger_row(extra_leg, got)
            extra.update(got)
            line["extra"] = extra
            _emit(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
