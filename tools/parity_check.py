"""A/B loss-parity gate CLI: run lockstep trainer pairs under reference vs
candidate flag-sets and fail loudly (exit 1, naming the diverging step and
stat) when a pair leaves its declared tolerance band.

    python tools/parity_check.py --ab check_nan_inf        # PR 4 guard: exact
    python tools/parity_check.py --ab amp_bf16             # bf16 amp: banded
    python tools/parity_check.py --ab quantized_allreduce  # int8 reduce: banded
    python tools/parity_check.py --ab shard_weight_update  # ZeRO-ish: EXACT
    python tools/parity_check.py --ab multi_lora           # pooled vs dedicated
    python tools/parity_check.py --ab paged_kv             # armed vs dense
    python tools/parity_check.py --ab reshard              # dp8 ckpt -> dp4/dp2xmp2
    python tools/parity_check.py --all
    python tools/parity_check.py --perturb-lr 5 --json     # negative control
    python tools/parity_check.py --ab quantized_allreduce --perturb-lr 6
    # ^ runs the target AND its in-band negative control (must exit 1)

The harness is paddle_tpu/testing/parity.py (docs/OBSERVABILITY.md
"Numerics telescope"): both sides train the SAME seeded tiny GPT over
IDENTICAL batches with the numerics telescope armed, and every step's
loss + per-layer grad stats are compared within each target's DECLARED
tolerance. ``--perturb-lr F`` runs the harness's own negative control — a
candidate whose learning rate is scaled by F must diverge, and the run
exits 1 naming where; CI uses it to prove the gate can actually fail.

This IS the acceptance gate ROADMAP item 2 named: `quantized_allreduce`
runs FLAGS_quantized_allreduce as the candidate inside its declared loss
band, `shard_weight_update` pins FLAGS_shard_weight_update EXACT, and
`--perturb-lr F` combined with `--ab NAME` re-runs each named target with
the candidate's lr scaled by F under the SAME band — which must diverge
(exit 1), proving the band is a gate and not a rubber stamp.

Report format: the tools/graph_lint.py schema ({"tool", "passes",
"targets": {name: {"name", "counts", "findings", "report"}}, "totals"})
so CI reads every audit tool through one loader.
"""
import argparse
import functools
import json
import os
import sys

# 8 host devices BEFORE jax loads — the MPMD pipeline targets need a
# real 2-device pp mesh (same forcing as tests/conftest.py)
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")
import jax

jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _build_trainer(lr=1e-2, amp_dtype=None):
    import paddle_tpu as paddle
    from paddle_tpu.distributed.mesh import build_mesh
    from paddle_tpu.distributed.spmd import SpmdTrainer
    from paddle_tpu.models import (GPTConfig, GPTForCausalLM,
                                   GPTPretrainLoss)

    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=1,
                    num_heads=2, max_seq_len=32, dropout=0.0)
    model = GPTForCausalLM(cfg)
    loss = GPTPretrainLoss()
    opt = paddle.optimizer.AdamW(learning_rate=lr,
                                 parameters=model.parameters())
    mesh = build_mesh((1,), ("dp",), devices=jax.devices()[:1])
    return SpmdTrainer(model, opt, loss_fn=loss, mesh=mesh,
                       amp_dtype=amp_dtype)


def _build_pipeline_trainer(lr=1e-2, compress=None):
    """2-stage pipeline twin of _build_trainer for the MPMD A/Bs: the
    armed/disarmed sides build the SAME seeded split model; only the
    scheduler differs. compress=8 quantizes the activation edges
    (meaningful only under FLAGS_mpmd — run_lockstep arms it via
    candidate_flags before build())."""
    import paddle_tpu as paddle
    from paddle_tpu.distributed.mesh import build_mesh
    from paddle_tpu.distributed.pipeline import PipelineTrainer
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                    num_heads=2, max_seq_len=32, dropout=0.0)
    model = GPTForCausalLM(cfg)
    pre, stages, post = model.pipeline_split(2)
    opt = paddle.optimizer.AdamW(learning_rate=lr,
                                 parameters=model.parameters())
    mesh = build_mesh((2,), ("pp",), devices=jax.devices()[:2])
    kw = {"compress": compress} if compress is not None else {}
    return PipelineTrainer(pre, stages, post, opt, mesh=mesh, n_micro=2,
                           schedule_mode="1F1B", **kw)


def _batches(steps, batch=2, seq=12):
    import numpy as np

    rng = np.random.RandomState(0)
    return [(rng.randint(0, 64, (batch, seq)).astype(np.int32),
             rng.randint(0, 64, (batch, seq)).astype(np.int32))
            for _ in range(steps)]


#: each target declares ITS tolerance — exact for program-identical or
#: bit-exact-by-contract A/Bs, a written band for genuinely lossy ones
AB_TARGETS = {
    # the PR 4 guard rebuilds the step with the fused finiteness verdict
    # + where-selects; on finite data its contract is BIT-exact
    "check_nan_inf": dict(
        reference_flags={},
        candidate_flags={"check_nan_inf": True},
        loss_rtol=0.0, loss_atol=0.0, stat_rtol=0.0, stat_atol=0.0),
    # bf16 autocast genuinely changes the numbers; the declared band is
    # the acceptance envelope (one part in 2^8 mantissa, headroom for
    # accumulation) — the shape every lossy candidate (ROADMAP item 2's
    # quantized all-reduce) will reuse
    "amp_bf16": dict(
        candidate_build=functools.partial(_build_trainer,
                                          amp_dtype="bfloat16"),
        reference_flags={}, candidate_flags={},
        loss_rtol=0.08, loss_atol=0.05, stat_rtol=0.6, stat_atol=0.1),
    # ROADMAP item 2's quantized all-reduce (distributed/compress.py):
    # int8 block-max quantization with stochastic rounding + error
    # feedback is a genuinely lossy reduce — the declared band matches
    # amp_bf16's (per-element error ~blockmax/127 ≈ bf16's 2^-8
    # mantissa step, residual feedback keeping the drift bounded). THIS
    # is the ship gate the flag must pass (docs/DISTRIBUTED.md)
    "quantized_allreduce": dict(
        reference_flags={},
        candidate_flags={"quantized_allreduce": True},
        loss_rtol=0.08, loss_atol=0.05, stat_rtol=0.6, stat_atol=0.1),
    # arXiv:2004.13336 update sharding re-distributes WHERE the
    # optimizer update is computed, not WHAT it computes: elementwise
    # rules on 1/dp shards are the same arithmetic — verified EXACT
    "shard_weight_update": dict(
        reference_flags={},
        candidate_flags={"shard_weight_update": True},
        loss_rtol=0.0, loss_atol=0.0, stat_rtol=0.0, stat_atol=0.0),
    # ISSUE 11 async dispatch changes NOTHING the device computes —
    # the compiled step is byte-identical; only the host's verdict
    # fetches move to window boundaries. Deferred fetches must not
    # change a single bit of the loss trajectory: EXACT
    "async_dispatch": dict(
        reference_flags={"check_nan_inf": True},
        candidate_flags={"check_nan_inf": True, "async_dispatch": True,
                         "async_window": 4},
        loss_rtol=0.0, loss_atol=0.0, stat_rtol=0.0, stat_atol=0.0),
    # ISSUE 11 TPP registry (ops/tpp.py): the ported fused-MLP /
    # ln->matmul kernels accumulate in fp32 with a blocked summation
    # order and a reference-math backward — a genuinely (minutely)
    # different float program. The band is tight: per-step loss within
    # 1e-3 relative, per-layer grad stats within 5%
    "tpp_kernels": dict(
        reference_flags={},
        candidate_flags={"tpp_kernels": True},
        loss_rtol=1e-3, loss_atol=1e-4, stat_rtol=0.05, stat_atol=1e-3),
    # ISSUE 15 MPMD runtime (distributed/stage.py): the same 2-stage
    # split model trained by the monolithic scanned schedule (reference)
    # vs per-stage programs + typed edges (candidate). The arithmetic is
    # the same matmuls, but grad accumulation is restructured (per-micro
    # vjp sums vs autodiff-of-scan) — a minutely different float
    # program, pinned in the tpp_kernels-class band
    "mpmd_pipeline": dict(
        reference_build=_build_pipeline_trainer,
        reference_flags={},
        candidate_flags={"mpmd": True},
        loss_rtol=1e-3, loss_atol=1e-4, stat_rtol=0.05, stat_atol=1e-3),
    # armed-vs-armed with the activation edges quantized (compress=8,
    # int8 row codec): genuinely lossy transfers — the declared band is
    # the quantized_allreduce envelope (per-element error ~rowmax/127)
    "mpmd_quantized_edge": dict(
        reference_build=_build_pipeline_trainer,
        candidate_build=functools.partial(_build_pipeline_trainer,
                                          compress=8),
        reference_flags={"mpmd": True},
        candidate_flags={"mpmd": True},
        loss_rtol=0.08, loss_atol=0.05, stat_rtol=0.6, stat_atol=0.1),
}


def _finding(name, severity, message, where=""):
    return {"pass": name, "severity": severity, "message": message,
            "where": where}


def _serving_fixture():
    """Seeded tiny GPT + two exported LoRA adapters shared by the
    serving-side parity targets (multi_lora / paged_kv)."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.incubate.lora import apply_lora, export_lora
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                    num_heads=2, max_seq_len=64, dropout=0.0)
    model = GPTForCausalLM(cfg)
    model.eval()

    def _adapter(seed):
        m2 = GPTForCausalLM(cfg)
        m2.load_dict(model.state_dict())
        apply_lora(m2, r=4, alpha=8)
        rng = np.random.RandomState(seed)
        for n_, p_ in m2.named_parameters():
            if "lora_B" in n_:
                p_.set_value(paddle.to_tensor(
                    rng.normal(0, 0.3, p_.shape).astype(np.float32)))
        return export_lora(m2)

    return model, {"alpha": _adapter(1), "beta": _adapter(2)}


def _drain(eng, jobs):
    """Submit [(prompt, kwargs)] jobs and return their outputs as
    int-token tuples, in job order."""
    rids = [eng.submit(list(p), **kw) for p, kw in jobs]
    res = eng.run_until_complete()
    return [tuple(int(t) for t in res[r].output_ids) for r in rids]


def run_multi_lora(steps=4):
    """ONE pooled multi-adapter engine vs a dedicated single-adapter
    engine per adapter (same batched-LoRA math, adapter alone in its
    pool): every session — greedy and seeded-sampled, base and
    adapter-routed — must be BYTE-identical. The acceptance bar for
    FLAGS_paged_kv batched multi-LoRA decode (docs/SERVING.md)."""
    import paddle_tpu as paddle
    from paddle_tpu import flags
    from paddle_tpu.inference.serving import ServingEngine

    old = {"paged_kv": flags.get_flag("paged_kv")}
    paddle.set_flags({"paged_kv": True})
    try:
        model, adapters = _serving_fixture()
        prompts = [[3, 14, 15, 9, 2, 6], [7, 1, 19], [21, 22, 23, 24]]
        n_new = 4 + steps

        def _jobs(adapter):
            out = []
            for i, p in enumerate(prompts):
                kw = dict(max_new_tokens=n_new, adapter=adapter)
                if i == 2:   # one seeded-sampled session per adapter
                    kw.update(temperature=0.8, top_k=16, seed=11)
                out.append((p, kw))
            return out

        pooled = ServingEngine(model, max_batch=4, max_adapters=4)
        for name, exp in adapters.items():
            pooled.load_adapter(name, exp)
        pooled_out = {name: _drain(pooled, _jobs(name))
                      for name in list(adapters) + [None]}

        findings, sessions = [], 0
        for name in list(adapters) + [None]:
            dedicated = ServingEngine(model, max_batch=4,
                                      max_adapters=4)
            if name is not None:
                dedicated.load_adapter(name, adapters[name])
            ded_out = _drain(dedicated, _jobs(name))
            for i, (a, b) in enumerate(zip(pooled_out[name], ded_out)):
                sessions += 1
                if a != b:
                    findings.append(_finding(
                        "multi_lora", "error",
                        f"adapter={name!r} session {i}: pooled engine "
                        f"diverged from its dedicated twin — pooled="
                        f"{list(a)} dedicated={list(b)}",
                        where=f"adapter={name}/session{i}"))
        if not findings:
            findings.append(_finding(
                "multi_lora", "info",
                f"{sessions} sessions ({len(adapters)} adapters + base, "
                "greedy + seeded-sampled) byte-identical between the "
                "pooled engine and dedicated per-adapter engines"))
        report = {"sessions": sessions, "adapters": sorted(adapters),
                  "diverged": any(f["severity"] == "error"
                                  for f in findings)}
        return report, findings
    finally:
        paddle.set_flags(old)


def run_paged_kv(steps=4):
    """FLAGS_paged_kv armed vs disarmed: the paged engine's dense decode
    must be BYTE-identical to the contiguous-cache engine (junk/null
    page columns are causally masked — exact by contract). Plus the int8
    cold-page band: a prefix block compressed cold and decompressed on
    touch must sit within the deterministic row codec's quantization
    step (|err| <= row absmax / 127)."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import flags
    from paddle_tpu.inference.serving import ServingEngine

    model, _ = _serving_fixture()
    prompts = [[3, 14, 15, 9, 2, 6], [7, 1, 19], [21, 22, 23, 24]]
    n_new = 4 + steps

    def _jobs():
        out = []
        for i, p in enumerate(prompts):
            kw = dict(max_new_tokens=n_new)
            if i == 2:
                kw.update(temperature=0.8, top_k=16, seed=11)
            out.append((p, kw))
        return out

    old = {"paged_kv": flags.get_flag("paged_kv")}
    findings = []
    try:
        paddle.set_flags({"paged_kv": False})
        dense_out = _drain(ServingEngine(model, max_batch=4), _jobs())
        paddle.set_flags({"paged_kv": True})
        paged_out = _drain(ServingEngine(model, max_batch=4), _jobs())
        for i, (a, b) in enumerate(zip(dense_out, paged_out)):
            if a != b:
                findings.append(_finding(
                    "paged_kv", "error",
                    f"session {i}: armed paged engine diverged from the "
                    f"disarmed dense engine — dense={list(a)} "
                    f"paged={list(b)}", where=f"session{i}"))

        # int8 cold band: hot frame -> sweep cold -> touch decompress
        eng = ServingEngine(model, max_batch=2, page_cold_steps=1)
        pool = eng._pool
        pid = eng.register_prefix(list(range(2, 34)))   # 2 full blocks
        frames = pool.prefix_frames(pid)
        hot_k = np.asarray(pool.kp[np.array(frames)])
        for _ in range(4):
            pool.sweep()
        if pool.stats()["cold_pages"] == 0:
            findings.append(_finding(
                "paged_kv", "error",
                "prefix blocks never compressed cold under "
                "page_cold_steps=1 idle sweeps", where="cold"))
        else:
            frames2 = pool.prefix_frames(pid)   # touch: decompress
            back_k = np.asarray(pool.kp[np.array(frames2)])
            err = np.abs(back_k.astype(np.float64)
                         - hot_k.astype(np.float64))
            # per-row band of the row codec: absmax/127 (+ float eps)
            band = np.abs(hot_k).max(axis=-1, keepdims=True) / 127.0 \
                + 1e-6
            worst = float((err - band).max())
            if worst > 0:
                findings.append(_finding(
                    "paged_kv", "error",
                    f"cold int8 round-trip left the row-codec band by "
                    f"{worst:.3g}", where="cold"))
            else:
                findings.append(_finding(
                    "paged_kv", "info",
                    f"{len(dense_out)} sessions byte-identical armed vs "
                    f"disarmed; int8 cold round-trip within the "
                    f"rowmax/127 band (max err {float(err.max()):.3g})"))
        report = {"sessions": len(dense_out),
                  "diverged": any(f["severity"] == "error"
                                  for f in findings)}
        return report, findings
    finally:
        paddle.set_flags(old)


#: serving-side parity targets — engine-vs-engine token comparisons, not
#: trainer lockstep A/Bs; they run through their own runners and skip
#: the --perturb-lr trainer companion machinery
SERVING_TARGETS = {"multi_lora": run_multi_lora, "paged_kv": run_paged_kv}


def _reshard_counts():
    """{action: value} of checkpoint_reshard_total right now (0-dict when
    the family hasn't been created yet)."""
    from paddle_tpu import monitor

    out = {}
    for m in monitor.snapshot()["metrics"]:
        if m["name"] != "checkpoint_reshard_total":
            continue
        for s in m["series"]:
            out[s["labels"]["action"]] = s["value"]
    return out


def run_reshard(steps=4, perturb_lr=None):
    """Topology-aware checkpoint reshard A/B (the FLAGS_elastic
    tentpole, docs/DISTRIBUTED.md "Elastic training"): a dp8 trainer
    with FLAGS_shard_weight_update ([dp, shard] moments) checkpoints at
    the midpoint, and the state_dict — carrying its ``shard_specs``
    topology leaf — restores onto a FRESH dp4 trainer AND a FRESH
    dp2x2 (dp x mp factorization of the same 4 devices) trainer. Each
    continuation must track the uninterrupted dp8 twin within the
    declared band (loss_rtol=1e-3, loss_atol=1e-4: re-layout changes
    psum order, the only float freedom — the moments themselves re-lay
    bit-exactly, pinned by tests/test_elastic_gate.py). The restore is
    also required to ATTRIBUTE itself: checkpoint_reshard_total
    {action=moment_reshard} must move, proving the topology-aware path
    engaged rather than a lucky same-layout load.

    ``perturb_lr`` scales the CONTINUATION trainers' lr — the
    ``--perturb-lr`` companion negative control, which must leave the
    band (exit 1), proving the band is a gate and not a rubber stamp."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import flags
    from paddle_tpu.distributed.mesh import build_mesh
    from paddle_tpu.distributed.spmd import SpmdTrainer
    from paddle_tpu.models import (GPTConfig, GPTForCausalLM,
                                   GPTPretrainLoss)

    name = "reshard" if perturb_lr is None else "reshard+perturb_lr"
    LOSS_RTOL, LOSS_ATOL = 1e-3, 1e-4
    if steps < 2:
        raise ValueError("the reshard A/B needs >= 2 steps (train, "
                         "checkpoint at the midpoint, continue)")
    split = steps // 2

    def _build(shape, axes, ndev, lr=1e-2):
        paddle.seed(0)
        cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=1,
                        num_heads=2, max_seq_len=32, dropout=0.0)
        model = GPTForCausalLM(cfg)
        opt = paddle.optimizer.AdamW(learning_rate=lr,
                                     parameters=model.parameters())
        return SpmdTrainer(model, opt, loss_fn=GPTPretrainLoss(),
                           mesh=build_mesh(shape, axes,
                                           devices=jax.devices()[:ndev]))

    old = {k: flags.get_flag(k)
           for k in ("elastic", "shard_weight_update")}
    paddle.set_flags({"elastic": True, "shard_weight_update": True})
    try:
        data = _batches(steps, batch=8)   # 8 divides dp8 / dp4 / dp2

        def _loss(tr, x, y):
            return float(np.asarray(tr.train_step(x, y)._data))

        twin = _build((8,), ("dp",), 8)
        twin_losses = [_loss(twin, x, y) for x, y in data]

        primary = _build((8,), ("dp",), 8)
        head = [_loss(primary, x, y) for x, y in data[:split]]

        lr = 1e-2 * (perturb_lr if perturb_lr is not None else 1.0)
        findings, worst = [], 0.0
        for label, shape, axes, ndev in (
                ("dp4", (4,), ("dp",), 4),
                ("dp2xmp2", (2, 2), ("dp", "mp"), 4)):
            before = _reshard_counts()
            cont = _build(shape, axes, ndev, lr=lr)
            # a fresh gather per continuation: restore re-lays the
            # [dp, shard] moments in place of the writer's layout
            cont.set_state_dict(primary.state_dict())
            relaid = _reshard_counts().get("moment_reshard", 0) \
                - before.get("moment_reshard", 0)
            if relaid <= 0:
                findings.append(_finding(
                    name, "error",
                    f"{label}: restore onto a different factorization "
                    "never re-laid a moment (checkpoint_reshard_total"
                    "{action=moment_reshard} did not move)",
                    where=label))
                continue
            losses = head + [_loss(cont, x, y) for x, y in data[split:]]
            for i, (a, b) in enumerate(zip(losses, twin_losses)):
                diff = abs(a - b)
                worst = max(worst, diff)
                if diff > LOSS_ATOL + LOSS_RTOL * abs(b):
                    findings.append(_finding(
                        name, "error",
                        f"{label}: continuation left the declared band "
                        f"at step {i}: twin={b:.6g} resumed={a:.6g} "
                        f"(|diff|={diff:.3g}, loss_rtol={LOSS_RTOL} "
                        f"loss_atol={LOSS_ATOL})",
                        where=f"{label}/step{i}"))
                    break
        if not findings:
            findings.append(_finding(
                name, "info",
                f"dp8 checkpoint at step {split} continued on dp4 and "
                f"dp2xmp2 within the declared band (max |loss diff| "
                f"{worst:.3g}; moments re-laid, attributed via "
                "checkpoint_reshard_total)"))
        report = {"steps": steps, "split": split,
                  "tolerances": {"loss_rtol": LOSS_RTOL,
                                 "loss_atol": LOSS_ATOL},
                  "max_abs_loss_diff": worst,
                  "reshard_actions": _reshard_counts(),
                  "diverged": any(f["severity"] == "error"
                                  for f in findings)}
        return report, findings
    finally:
        paddle.set_flags(old)


#: self-running trainer-side targets that manage their own twin AND
#: their own --perturb-lr companion (the factor reaches them as a
#: kwarg instead of riding the lockstep harness)
CUSTOM_TARGETS = {"reshard": run_reshard}


def run_target(name, steps=4, perturb_lr=None):
    """Run one A/B; returns (report, findings). `perturb_lr` builds a
    negative-control variant instead (candidate lr scaled — MUST
    diverge): standalone (`name == "perturb_lr"`) under zero tolerance,
    or — when `name` is a real target — under THAT target's own flags
    and declared band, proving the band itself can fail (the CI lane's
    companion run for the banded quantized_allreduce gate)."""
    from paddle_tpu.testing import parity

    if name in CUSTOM_TARGETS:
        return CUSTOM_TARGETS[name](steps=steps, perturb_lr=perturb_lr)
    if perturb_lr is None and name in SERVING_TARGETS:
        return SERVING_TARGETS[name](steps=steps)
    if perturb_lr is not None:
        if name in AB_TARGETS:
            spec = dict(AB_TARGETS[name])
            base = (spec.get("candidate_build")
                    or spec.get("reference_build", _build_trainer))
            base_fn = base.func if isinstance(base, functools.partial) \
                else base
            kw = dict(getattr(base, "keywords", None) or {})
            kw["lr"] = 1e-2 * perturb_lr
            spec["candidate_build"] = functools.partial(base_fn, **kw)
        else:
            spec = dict(
                candidate_build=functools.partial(_build_trainer,
                                                  lr=1e-2 * perturb_lr),
                reference_flags={}, candidate_flags={},
                loss_rtol=0.0, loss_atol=0.0, stat_rtol=0.0,
                stat_atol=0.0)
    else:
        spec = AB_TARGETS[name]
    report = parity.run_parity(
        spec.get("reference_build", _build_trainer), _batches(steps),
        build_candidate=spec.get("candidate_build"),
        reference_flags=spec["reference_flags"],
        candidate_flags=spec["candidate_flags"],
        loss_rtol=spec["loss_rtol"], loss_atol=spec["loss_atol"],
        stat_rtol=spec["stat_rtol"], stat_atol=spec["stat_atol"])
    findings = []
    if report["diverged"]:
        d = report["first_divergence"]
        where = d["stat"] + (f"[{d['layer']}]" if d.get("layer") else "")
        findings.append(_finding(
            name, "error",
            f"A/B diverged at step {d['step']} on {where}: "
            f"reference={d['reference']:.6g} "
            f"candidate={d['candidate']:.6g} "
            f"(|diff|={d['abs_diff']:.3g}, tolerances "
            f"{report['tolerances']})", where=where))
    else:
        findings.append(_finding(
            name, "info",
            f"{report['steps']} lockstep steps within declared "
            f"tolerance (max |loss diff| "
            f"{report['max_abs_loss_diff']:.3g})"))
    return report, findings


def build_report(targets, steps=4, perturb_lr=None):
    report = {"tool": "parity_check", "passes": list(targets), "targets": {},
              "totals": {"error": 0, "warning": 0, "info": 0}}
    jobs = [(t, t, None) for t in targets]
    if perturb_lr is not None:
        if targets:
            # negative control per named target, in ITS band — MUST
            # diverge (exit 1), proving each new gate can actually fail
            # (trainer A/Bs only: the serving targets have no lr to turn)
            for t in targets:
                if t in SERVING_TARGETS:
                    continue
                jobs.append((f"{t}+perturb_lr", t, perturb_lr))
                report["passes"].append(f"{t}+perturb_lr")
        else:
            jobs.append(("perturb_lr", "perturb_lr", perturb_lr))
            report["passes"].append("perturb_lr")
    for label, name, factor in jobs:
        try:
            ab_report, findings = run_target(name, steps=steps,
                                             perturb_lr=factor)
        except Exception as e:   # a crashed A/B is a failed gate
            ab_report = None
            findings = [_finding(label, "error",
                                 f"A/B crashed: {type(e).__name__}: {e}")]
        counts = {"error": 0, "warning": 0, "info": 0}
        for f in findings:
            f["pass"] = label
            counts[f["severity"]] += 1
        report["targets"][label] = {"name": label, "counts": counts,
                                    "findings": findings,
                                    "report": ab_report}
        for sev, n in counts.items():
            report["totals"][sev] += n
    return report


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--ab", action="append",
                    choices=(sorted(AB_TARGETS) + sorted(SERVING_TARGETS)
                             + sorted(CUSTOM_TARGETS)),
                    default=[], help="run one named A/B target "
                    "(repeatable)")
    ap.add_argument("--all", action="store_true",
                    help="run every named A/B target")
    ap.add_argument("--perturb-lr", type=float, default=None,
                    dest="perturb_lr", metavar="F",
                    help="negative control: candidate lr scaled by F "
                         "under zero tolerance — MUST diverge (exit 1 "
                         "naming the step/stat); proves the gate can "
                         "fail")
    ap.add_argument("--steps", type=int, default=4,
                    help="lockstep steps per side (default 4)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit the graph_lint-schema machine report")
    args = ap.parse_args(argv)

    targets = (sorted(AB_TARGETS) + sorted(SERVING_TARGETS)
               + sorted(CUSTOM_TARGETS)) if args.all else list(args.ab)
    if not targets and args.perturb_lr is None:
        ap.error("pick a target: --ab NAME, --all, or --perturb-lr F")

    report = build_report(targets, steps=args.steps,
                          perturb_lr=args.perturb_lr)
    if args.as_json:
        print(json.dumps(report, indent=1))
    else:
        for t in report["targets"].values():
            for f in t["findings"]:
                print(f"  [{f['severity']}] {f['pass']}: {f['message']}")
        t = report["totals"]
        print(f"total: {t['error']} divergence(s), {t['info']} A/B(s) "
              f"within tolerance")
    return 1 if report["totals"]["error"] else 0


if __name__ == "__main__":
    sys.exit(main())
