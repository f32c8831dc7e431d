"""chip_smoke.py — does the system still start on the chip?

Drives the main path once, through the entry points a user calls (`import
paddle_tpu as paddle`, `SpmdTrainer`, `ServingEngine`), at the full width of
GPT-2-small (hidden 768, 12 layers, 12 heads, vocab 50304, sequence 1024, bf16
autocast, dropout 0; random weights from --seed), in ONE process:

  train  batch 16, AdamW, a few train_steps on one repeated batch. Checks: the
         loss is finite and ends lower than it started; parameters and loss
         live on a TPU device; the compiled step contains the Pallas flash
         kernels (a step that took the naive softmax(QK^T)V path fails).
  serve  ServingEngine, max_batch 8, bf16, six seeded prompts of 64..512
         tokens, 32 new tokens each. Checks: every request finishes with
         reason "length", token ids are in range, and the first request's
         greedy output equals model.generate on the same prompt (on the
         chip: the decode steps read live cache tiles only, and the output
         may part from generate's at a tie of two logits, TIE_GAP).
  serve_hybrid  the solar_open2 family (3 KDA linear-attention layers to 1
         gated GQA layer, routed experts with a shared one) at the widths of
         the benchmark's cell, 16 slots, a dozen prompts of 32..255 tokens,
         16 new tokens each, through the same ServingEngine. Checks: the
         lookahead loop engaged, every request ran to its length, and every
         served token lies within the cell's own `token_gap` limit of the
         best logit of the benchmark's plain float32 reference.

With no arguments it needs one TPU chip and refuses to run without one (exit
2, nothing on stdout — never a smaller model on the CPU). Any phase that
raises ends the script non-zero; nothing is caught and carried on from. The
LAST line of stdout is exactly
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

--multichip  (four chips; the builder runs it, the driver never does) runs ONLY
             the sharded trainer and what it is compared with: SpmdTrainer on
             build_mesh((2, 2), ("dp", "mp")) with sharding_stage=2, same
             width, same batch, against the one-chip trainer's losses in this
             same process, step by step; then checks the parameters really
             are spread over four distinct devices. "count" is then 4.
--rehearse   the same control flow at tiny shapes on whatever backend jax has
             (the CPU, for rehearsals 1 and 2 of the on-chip-measurement
             guide). Its last line says "ok": false — a rehearsal can never
             be read as a pass.
"""
import argparse
import collections
import json
import sys
import time
from importlib import metadata

import jax
import numpy as np

import paddle_tpu as paddle
from paddle_tpu.distributed.mesh import build_mesh
from paddle_tpu.distributed.split import collect_spmd_specs
from paddle_tpu.distributed.spmd import SpmdTrainer
from paddle_tpu.inference.serving import ServingEngine
from paddle_tpu.models import GPTConfig, GPTForCausalLM, GPTPretrainLoss

FULL = dict(vocab_size=50304, hidden_size=768, num_layers=12, num_heads=12,
            max_seq_len=1024)
TINY = dict(vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
            max_seq_len=128)

#: --multichip: sharded vs one-chip loss, per step. Both run the same bf16
#: matmuls, but tensor parallelism splits every contraction in two and the
#: dp mean adds the halves in another order; bf16 carries 8 bits of mantissa
#: (2**-8 ~ 4e-3 per rounding), and a few such roundings compound over the
#: optimizer steps — 1e-2 relative holds that, and is two orders below what
#: a wrong sharding rule does to a loss of ~10.
MULTICHIP_RTOL = 1e-2

#: serve: how far apart, by the model's float32 logits, the engine's and
#: generate's picks may lie where the two greedy sequences first differ, when
#: the engine's decode step read the cache through the float32-softmax kernel
#: (ops/decode_attention.py) and generate through its bf16 einsums. On the
#: chip 16 first differences in 29 comparisons over 15 seeds read 0.00002 to
#: 0.00606 (PERF.md section 6, "PR 31"): four times the largest. Where both
#: ran the einsums the tokens are equal, and are held to that.
TIE_GAP = 0.025


def say(**fields):
    """One informational JSON line (every line but the last)."""
    print(json.dumps(fields), flush=True)


class CacheEvents:
    """jax's own persistent-compile-cache hit/miss events, per phase."""

    def __init__(self):
        self._seen = collections.Counter()
        jax.monitoring.register_event_listener(
            lambda name, **kw: self._seen.update([name]))

    def take(self):
        hits = self._seen.pop("/jax/compilation_cache/cache_hits", 0)
        misses = self._seen.pop("/jax/compilation_cache/cache_misses", 0)
        return {"compile_cache_hits": hits, "compile_cache_misses": misses}


def build_trainer(cfg_kw, seed, mesh, tp_layers=False, sharded=False):
    """A seeded GPT + AdamW + SpmdTrainer on `mesh`. tp_layers builds the
    model from the tensor-parallel layers (weights tagged with their 'mp'
    specs); sharded hands those specs to the trainer with ZeRO-2. The
    sharded trainer and its one-chip reference both use tp_layers, so the
    same seed gives them the same weights."""
    paddle.seed(seed)
    model = GPTForCausalLM(GPTConfig(dropout=0.0, tensor_parallel=tp_layers,
                                     **cfg_kw))
    opt = paddle.optimizer.AdamW(learning_rate=3e-4,
                                 parameters=model.parameters())
    kw = {}
    if sharded:
        kw = {"sharding_stage": 2,
              "extra_param_specs": collect_spmd_specs(model)}
    return SpmdTrainer(model, opt, loss_fn=GPTPretrainLoss(), mesh=mesh,
                       dp_axis="dp", **kw)


def make_batch(cfg_kw, batch, seed):
    rng = np.random.RandomState(seed)
    shape = (batch, cfg_kw["max_seq_len"])
    return [paddle.to_tensor(
        rng.randint(0, cfg_kw["vocab_size"], shape).astype(np.int32))
        for _ in range(2)]


def run_steps(trainer, batch, steps, events, label, expect_kernels):
    """Compile the step, take `steps` train_steps on the repeated batch,
    check the losses. Returns the per-step losses."""
    specs = [(tuple(t.shape), "int32") for t in batch]
    with paddle.amp.auto_cast(True, dtype="bfloat16"):
        t0 = time.perf_counter()
        trainer.aot_build(specs)
        compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        losses = []
        for _ in range(steps):
            loss = trainer.train_step(*batch)
            losses.append(loss)
        jax.block_until_ready([t._data for t in losses])
        run_s = time.perf_counter() - t0
    loss_devices = losses[-1]._data.devices()
    losses = [float(t._data) for t in losses]
    # what XLA says the compiled step costs (its own cost and memory
    # analysis, per device) — counts to set beside the seconds
    xla = [{k: e.get(k) for k in ("flops", "argument_bytes", "temp_bytes")}
           for e in paddle.trace.costs.table() if e["site"] == "trainer"][-1]
    say(phase=label, compile_s=round(compile_s, 2), run_s=round(run_s, 2),
        steps=steps, losses=[round(v, 4) for v in losses], xla=xla,
        **events.take())
    if not all(np.isfinite(losses)):
        raise AssertionError(f"{label}: non-finite loss in {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(
            f"{label}: loss did not fall on the repeated batch: {losses}")
    # read AFTER the steps: None means the compiled step was rejected at
    # call time and a different program ran
    text = trainer.compiled_text()
    if text is None:
        raise AssertionError(
            f"{label}: the compiled step did not run (the trainer fell "
            "back to a lazy jit)")
    if expect_kernels:
        platforms = {d.platform for d in loss_devices}
        for arr in trainer.params.values():
            platforms |= {d.platform for d in arr.devices()}
        if platforms != {"tpu"}:
            raise AssertionError(
                f"{label}: parameters/loss live on {sorted(platforms)}, "
                "not on the TPU")
        # fwd + dq + dkv Pallas calls per layer; the naive attention path
        # has none
        n_calls = text.count("tpu_custom_call")
        if n_calls < 3:
            raise AssertionError(
                f"{label}: the compiled train step holds {n_calls} "
                "tpu_custom_call(s) — attention went down the naive "
                "softmax(QK^T)V path, not the Pallas flash kernels")
        say(phase=label, tpu_custom_calls=n_calls)
    return losses


def phase_train(cfg_kw, batch, args, events, on_chip):
    mesh = build_mesh((1,), ("dp",), devices=jax.devices()[:1])
    trainer = build_trainer(cfg_kw, args.seed, mesh)
    batch = make_batch(cfg_kw, batch, args.seed)
    run_steps(trainer, batch, args.steps, events, "train",
              expect_kernels=on_chip)


def phase_serve(cfg_kw, args, events, on_chip):
    rehearse = args.rehearse
    paddle.seed(args.seed)
    model = GPTForCausalLM(GPTConfig(dropout=0.0, **cfg_kw))
    model.eval()
    vocab = cfg_kw["vocab_size"]
    lo, hi, new_tokens = (8, 32, 8) if rehearse else (64, 512, 32)
    rng = np.random.RandomState(args.seed)
    lens = [lo] + [int(rng.randint(lo, hi + 1)) for _ in range(5)]
    prompts = [rng.randint(0, vocab, (n,)).astype(np.int32) for n in lens]

    t0 = time.perf_counter()
    eng = ServingEngine(model, max_batch=4 if rehearse else 8,
                        dtype="bfloat16")
    rids = [eng.submit(p, max_new_tokens=new_tokens) for p in prompts]
    results = eng.run_until_complete()
    serve_s = time.perf_counter() - t0
    for rid, n in zip(rids, lens):
        req = results[rid]
        toks = req.tokens
        if req.finish_reason != "length" or len(toks) != new_tokens:
            raise AssertionError(
                f"serve: request {rid} (prompt {n}) ended "
                f"{req.finish_reason!r} after {len(toks)} tokens")
        if toks.min() < 0 or toks.max() >= vocab:
            raise AssertionError(f"serve: request {rid} token id out of range")

    t0 = time.perf_counter()
    ref = model.generate(paddle.to_tensor(prompts[0][None]),
                         max_new_tokens=new_tokens, temperature=0.0,
                         dtype="bfloat16")
    ref = np.asarray(ref._data)[0, lens[0]:]
    generate_s = time.perf_counter() - t0
    say(phase="serve", prompt_lens=lens, new_tokens=new_tokens,
        serve_s=round(serve_s, 2), generate_s=round(generate_s, 2),
        **events.take())
    st = eng.stats()
    live_only = st["kv_tiles_read"] < st["kv_tiles_held"]
    if on_chip and not live_only:
        raise AssertionError(
            "serve: the decode steps read every cache tile they hold "
            f"({st['kv_tiles_read']} of {st['kv_tiles_held']}): the "
            "attention went down the masked einsums, not the Pallas kernel")
    if live_only != bool(st["kv_tiles_written"]):
        raise AssertionError(
            "serve: the kernel that reads the live tiles stores the step's "
            f"column into them, and here {st['kv_tiles_written']} tiles "
            f"were written back with live_only {live_only}")
    first = first_difference(model, prompts[0], results[rids[0]].tokens, ref)
    if first is not None:
        # two near-equal logits of this untrained model may fall either way
        # between a float32 and a bf16 softmax, and from there the two
        # sequences part. A tie is allowed, a wrong token is not
        k, gap = first
        say(phase="serve", first_difference=k, logit_gap=round(gap, 5))
        allowed = TIE_GAP if live_only else 0.0
        if gap > allowed:
            raise AssertionError(
                "serve: the engine's greedy tokens differ from "
                f"model.generate on the same prompt at token {k} by a logit "
                f"gap of {gap:.4f} (allowed: {allowed}): "
                f"{results[rids[0]].tokens.tolist()} vs {ref.tolist()}")


def phase_serve_hybrid(args, events, on_chip):
    """A dozen requests through the solar_open2 family at the benchmark
    cell's widths (benchmark/configs/solar-open2-250b.json: one period of 3
    KDA layers to 1 GQA layer, experts 0-39 of 320, an eighth of the
    vocabulary; bf16, 16 slots, T 1024), by the normal path: the Layer's
    constructor, ServingEngine.submit/step through the lookahead loop. Every
    served token is held to the benchmark's plain reference by the cell's
    own limit (`token_gap`: how far its float32 reference logit lies below
    the reference's best)."""
    import gc
    import os

    import jax.numpy as jnp

    from benchmark import compare, hybrid_weights
    from benchmark.reference import solar_open2 as reference
    from benchmark.runners.serve_hybrid import program_config
    from paddle_tpu.models import SolarOpen2ForCausalLM

    here = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "benchmark")

    def load(*parts):
        with open(os.path.join(here, *parts)) as f:
            return json.load(f)

    rehearse = args.rehearse
    cfg = load("configs", "solar-open2-tiny-rehearsal.json" if rehearse
               else "solar-open2-250b.json")
    limit = load("workloads", ("rehearsal-serve-hybrid-tiny.json" if rehearse
                               else "solar-open2-250b.serve-backlog-2k.json")
                 )["limits"]["token_gap"]
    T, slots, buckets, new_tokens = (128, 4, (16, 32, 64), 6) if rehearse \
        else (1024, 16, (64, 128, 256), 16)
    t0 = time.perf_counter()
    model = SolarOpen2ForCausalLM(
        program_config(cfg, T), dtype="bfloat16",
        initializer=hybrid_weights.initializer(cfg, args.seed,
                                               round_to="bfloat16"))
    eng = ServingEngine(model, max_batch=slots, dtype="bfloat16",
                        prompt_buckets=buckets)
    rng = np.random.RandomState(args.seed)
    prompts = [rng.randint(0, cfg["vocab_size"],
                           (int(rng.randint(buckets[0] // 2, buckets[-1])),)
                           ).astype(np.int32) for _ in range(12)]
    rids = [eng.submit(p, max_new_tokens=new_tokens) for p in prompts]
    results = eng.run_until_complete()
    served = [np.asarray(results[r].tokens) for r in rids]
    st = eng.stats()
    serve_s = time.perf_counter() - t0
    if st["lookahead"]["rounds_overlapped"] < st["lookahead"]["rounds"] - 2:
        raise AssertionError("serve_hybrid: the lookahead loop did not "
                             f"engage: {st['lookahead']}")
    del eng, model, results
    gc.collect()
    t0 = time.perf_counter()
    P = hybrid_weights.flat(cfg, args.seed, round_to="bfloat16")
    D = reference.dims_of(cfg)
    worst = 0.0
    for prompt, toks in zip(prompts, served):
        ids = np.zeros((buckets[-1] + new_tokens,), np.int32)
        n = len(prompt) + len(toks)
        ids[:n] = np.concatenate([prompt, toks])
        logits = np.asarray(reference.sequence_logits(P, jnp.asarray(ids),
                                                      D))
        worst = max(worst, float(
            compare.token_gaps(logits, len(prompt), toks).max()))
    say(phase="serve_hybrid", prompt_lens=[len(p) for p in prompts],
        new_tokens=new_tokens, serve_s=round(serve_s, 2),
        reference_s=round(time.perf_counter() - t0, 2),
        token_gap=round(worst, 5), limit=limit,
        moe={k: st[k] for k in st if k.startswith("moe_")},
        state_bytes_held=st["state_bytes"]["held"], **events.take())
    if len(served) != 12 or any(len(t) != new_tokens for t in served):
        raise AssertionError("serve_hybrid: a request did not run to its "
                             f"length: {[len(t) for t in served]}")
    if not worst <= limit:
        raise AssertionError(
            f"serve_hybrid: a served token lies {worst:.4f} under the "
            f"reference's best logit (the cell's limit: {limit})")


def first_difference(model, prompt, got, ref):
    """Where two greedy continuations of `prompt` first differ, and how far
    apart the two picks lie by the model's own float32 forward over what was
    served so far: (index, logit gap), or None where they are equal."""
    if np.array_equal(got, ref):
        return None
    k = int(np.argmax(got != ref))
    ids = np.concatenate([prompt, got[:k]])[None]
    logits = np.asarray(model(paddle.to_tensor(ids))._data[0, -1], np.float32)
    return k, abs(float(logits[got[k]] - logits[ref[k]]))


def phase_multichip(cfg_kw, batch, args, events, on_chip):
    devices = jax.devices()[:4]
    batch = make_batch(cfg_kw, batch, args.seed)

    one = build_mesh((1,), ("dp",), devices=devices[:1])
    trainer = build_trainer(cfg_kw, args.seed, one, tp_layers=True)
    ref = run_steps(trainer, batch, args.steps, events,
                    "one_chip_reference", expect_kernels=on_chip)
    del trainer  # free chip 0 before the sharded trainer lands on it

    mesh = build_mesh((2, 2), ("dp", "mp"), devices=devices)
    trainer = build_trainer(cfg_kw, args.seed, mesh, tp_layers=True,
                            sharded=True)
    got = run_steps(trainer, batch, args.steps, events, "dp2_mp2_zero2",
                    expect_kernels=on_chip)

    rel = [abs(g - r) / abs(r) for g, r in zip(got, ref)]
    say(phase="multichip_compare", rtol=MULTICHIP_RTOL,
        rel_diff=[round(v, 6) for v in rel])
    if max(rel) > MULTICHIP_RTOL:
        raise AssertionError(
            f"multichip: sharded losses {got} leave the one-chip losses "
            f"{ref} by more than {MULTICHIP_RTOL} relative")

    # really sharded: together the parameters' shards sit on four distinct
    # devices, and an 'mp'-split weight holds half of itself on each
    holders, split = set(), 0
    for arr in trainer.params.values():
        shards = arr.addressable_shards
        holders |= {s.device for s in shards}
        split += any(s.data.shape != arr.shape for s in shards)
    say(phase="multichip_placement", devices_holding_shards=len(holders),
        params=len(trainer.params), params_split=split)
    if len(holders) != 4 or split == 0:
        raise AssertionError(
            f"multichip: parameters sit on {len(holders)} device(s) with "
            f"{split} split — not spread over four chips")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--multichip", action="store_true",
                    help="four chips: only the sharded trainer and the "
                         "one-chip trainer it is compared with")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny shapes on any backend; never prints "
                         '"ok": true')
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--steps", type=int, default=6)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    need = 4 if args.multichip else 1
    on_chip = device["platform"] == "tpu"
    if not args.rehearse and not on_chip:
        print(f"chip_smoke.py needs a TPU; jax found {device}. Nothing "
              "was run (use --rehearse for the tiny CPU rehearsal).",
              file=sys.stderr)
        return 2
    if device["count"] < need:
        print(f"chip_smoke.py --multichip needs 4 devices; jax found "
              f"{device}. Nothing was run.", file=sys.stderr)
        return 2

    cfg_kw, batch = (TINY, 4) if args.rehearse else (FULL, 16)
    # the persistent cache is for the chip: an XLA:CPU entry read back on
    # another host warns about machine features on every load
    cache_dir = None if args.rehearse else paddle.enable_compile_cache()
    events = CacheEvents()
    say(device=device, jax=jax.__version__,
        libtpu=_libtpu_version(), compile_cache_dir=cache_dir,
        config=cfg_kw, batch=batch, seed=args.seed,
        rehearse=args.rehearse, multichip=args.multichip)

    t0 = time.perf_counter()
    if args.multichip:
        phase_multichip(cfg_kw, batch, args, events, on_chip)
    else:
        phase_train(cfg_kw, batch, args, events, on_chip)
        phase_serve(cfg_kw, args, events, on_chip)
        phase_serve_hybrid(args, events, on_chip)
    stats = dev.memory_stats() or {}
    say(total_s=round(time.perf_counter() - t0, 2),
        peak_bytes_in_use=stats.get("peak_bytes_in_use"))

    if args.rehearse:
        print(json.dumps({"ok": False, "rehearsal": "passed",
                          "device": device}), flush=True)
    else:
        print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


def _libtpu_version():
    try:
        return metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        return None


if __name__ == "__main__":
    sys.exit(main())
