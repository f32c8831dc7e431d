"""Chaos gate CLI: drive short serving + trainer + checkpoint loops under a
canned fault schedule (paddle_tpu.testing.failpoints) and verify every
recovery path actually recovers.

    python tools/chaos_check.py           # human-readable
    python tools/chaos_check.py --json    # machine-readable report

Checks (one entry per name in `passes`):

  ckpt_atomic        a save killed between payload and commit leaves the
                     destination checkpoint untouched
  ckpt_fallback      a corrupt newest checkpoint is evicted and the
                     previous valid one restored
  serving_deadline   an overdue request finishes reason="deadline" while
                     its batch-mate decodes to exact greedy parity
  serving_slot_error an injected per-slot error evicts ONLY that slot;
                     the survivor stays bit-exact
  serving_shed       a full bounded queue raises QueueFullError and a
                     higher-priority arrival sheds the lowest
  router_failover    one of a Router's two engines is killed mid-stream
                     via the serving/step failpoint; every request —
                     including the dead engine's in-flight ones —
                     finishes on the survivor with exact greedy parity
  stall_dump         a serving/step=delay failpoint wedges an engine;
                     the blackbox stall sentinel fires DURING the wedge
                     and its dump bundle names site=serving/step, the
                     in-flight rids, and all-thread stacks — then the
                     engine drains to exact greedy parity
  stage_backpressure with FLAGS_mpmd armed the disagg pool's handoff
                     rides a typed StageEdge: a full edge rejects the
                     overflow put (EdgeFullError, counted, nothing
                     lost on drain), a stage/edge=delay failpoint
                     wedges one hand-off mid-run and the stall
                     sentinel fires DURING the wedge naming
                     site=stage/edge, then the drain keeps exact
                     greedy parity with edge puts==gets==prompts
  trainer_nonfinite  a NaN batch under FLAGS_check_nan_inf skips the
                     update, leaving params/moments bit-identical
  numerics_anomaly   a trainer/batch=scale failpoint injects a gradient
                     spike: the numerics telescope's drift detector
                     fires (naming the layer) BEFORE the non-finite
                     guard ever trips; a follow-up scale:nan step then
                     trips the guard AND the per-layer nonfinite
                     detector on the same step
  quantized_nonfinite a trainer/batch=scale:nan failpoint under the
                     FLAGS_quantized_allreduce path: the PR 4 guard
                     still trips through the int8 reduce (NaN poisons
                     the fp32 block scales, staying loud), params stay
                     bit-identical, AND the error-feedback residuals
                     are where-selected back bit-exactly — no
                     quantization poison carried into the next step,
                     which then trains normally
  adapter_evict_under_load the FLAGS_paged_kv engine's hot adapter is
                     evicted mid-stream: the live session requeues (not
                     reason='error'), re-admits after a hot-reload and
                     finishes bit-exact vs an undisturbed twin; a
                     serving/adapter=error:1 failpoint on a load leaves
                     the registry untouched
  page_pool_full     paged-KV pool exhaustion backpressures BEFORE any
                     work: a never-fits request is rejected at submit
                     with zero pool mutation, transient exhaustion
                     requeues to bit-exact completion, drain frees
                     every block
  elastic_resume     a dp8 run under the ElasticSupervisor is killed
                     mid-step (trainer/step failpoint) with the dp8
                     topology marked gone: the supervisor resumes on
                     dp4 through the topology-aware restore, the loss
                     trajectory stays within tolerance of an
                     uninterrupted dp8 twin, and the recovery is
                     attributed (blackbox crash bundle at
                     site=elastic/resume + elastic_resume_total
                     {reason=failpoint})
  goodput_attribution the elastic_resume kill re-run under FLAGS_goodput:
                     the finalized run's ledger row books nonzero
                     resume_backoff + ckpt_restore + reshard seconds,
                     its buckets sum to wall time within 10%, its
                     goodput lands below an uninterrupted twin's (which
                     books >= 95% of post-warmup wall as step+compile),
                     and the crash bundle's goodput provider names the
                     bucket active at kill time (step)
  stage_replace      one stage of a FLAGS_mpmd 2-stage pipeline is
                     killed via the stage/run failpoint; replace_stage
                     rebinds JUST that stage onto a replacement mesh
                     (the stage's own programs rebuilt, sibling
                     programs' compiled entries asserted untouched) and
                     training continues to loss parity with an
                     uninterrupted twin

Report format: the tools/graph_lint.py schema ({"tool", "passes",
"targets": {name: {"name", "counts", "findings"}}, "totals"}), so CI reads
graph_lint, op_coverage, metrics_dump, and chaos_check through
one loader. Exit code 1 when any recovery path fails (error-severity
finding), else 0. Wired into tier-1 by tests/test_failpoints_gate.py.
"""
import argparse
import json
import os
import sys
import tempfile
import time

# the elastic passes build dp8 meshes on the CPU backend (same forcing
# as tools/parity_check.py — must precede the jax import)
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import jax

jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

PASSES = ["ckpt_atomic", "ckpt_fallback", "serving_deadline",
          "serving_slot_error", "serving_shed", "router_failover",
          "stall_dump", "stage_backpressure", "trainer_nonfinite",
          "numerics_anomaly", "quantized_nonfinite", "async_nonfinite",
          "adapter_evict_under_load", "page_pool_full",
          "elastic_resume", "stage_replace", "goodput_attribution"]


def _finding(name, severity, message, where=""):
    return {"pass": name, "severity": severity, "message": message,
            "where": where}


def _ok(name, message):
    return _finding(name, "info", message)


def _check_ckpt_atomic():
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.testing import failpoints as fp

    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "state.pdparams")
        paddle.save({"w": paddle.to_tensor(np.ones(4))}, p)
        before = open(p, "rb").read()
        try:
            with fp.scoped("ckpt/write=error:1"):
                paddle.save({"w": paddle.to_tensor(np.zeros(4))}, p)
            return [_finding("ckpt_atomic", "error",
                             "armed ckpt/write failpoint did not fire")]
        except fp.FailpointError:
            pass
        if open(p, "rb").read() != before:
            return [_finding("ckpt_atomic", "error",
                             "destination changed after a failed save — "
                             "the commit is not atomic", where=p)]
        out = paddle.load(p)
        if not np.array_equal(np.asarray(out["w"]._data), np.ones(4)):
            return [_finding("ckpt_atomic", "error",
                             "surviving checkpoint does not load the "
                             "pre-fault state", where=p)]
    return [_ok("ckpt_atomic",
                "failed save left the committed checkpoint bit-intact")]


def _check_ckpt_fallback():
    import warnings

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.incubate.checkpoint.auto_checkpoint import \
        CheckpointSaver

    with tempfile.TemporaryDirectory() as d:
        saver = CheckpointSaver(d)
        saver.save_checkpoint({"v": paddle.to_tensor(np.zeros(2))},
                              meta={"epoch": 0})
        saver.save_checkpoint({"v": paddle.to_tensor(np.ones(2))},
                              meta={"epoch": 1})
        newest = os.path.join(d, "__paddle_checkpoint__.1",
                              "state.pdparams")
        blob = open(newest, "rb").read()
        open(newest, "wb").write(blob[: len(blob) // 2])   # truncate
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            state, meta = saver.load_checkpoint()
        if meta is None or meta.get("epoch") != 0:
            return [_finding("ckpt_fallback", "error",
                             "corrupt newest checkpoint did not fall back "
                             f"to the previous valid one (meta={meta})",
                             where=newest)]
        if saver.get_checkpoint_numbers() != [0]:
            return [_finding("ckpt_fallback", "error",
                             "corrupt checkpoint was not evicted: "
                             f"{saver.get_checkpoint_numbers()}")]
    return [_ok("ckpt_fallback",
                "corrupt newest checkpoint evicted; epoch-0 state restored")]


def _tiny_model():
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=1,
                    num_heads=2, max_seq_len=64, dropout=0.0)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


def _ref_tokens(m, p, n):
    import numpy as np

    import paddle_tpu as paddle

    out = m.generate(paddle.to_tensor(p[None]), max_new_tokens=n,
                     temperature=0.0)
    return np.asarray(out._data)[0, len(p):]


def _check_serving_deadline(m):
    import numpy as np

    from paddle_tpu.inference.serving import ServingEngine

    rng = np.random.RandomState(0)
    p1 = rng.randint(0, 64, (5,)).astype(np.int32)
    p2 = rng.randint(0, 64, (9,)).astype(np.int32)
    eng = ServingEngine(m, max_batch=2)
    r1 = eng.submit(p1, max_new_tokens=6)
    r2 = eng.submit(p2, max_new_tokens=6, deadline_ms=0.001)
    time.sleep(0.005)
    res = eng.run_until_complete()
    if res[r2].finish_reason != "deadline":
        return [_finding("serving_deadline", "error",
                         "overdue request finished with "
                         f"{res[r2].finish_reason!r}, not 'deadline'")]
    if not np.array_equal(res[r1].tokens, _ref_tokens(m, p1, 6)):
        return [_finding("serving_deadline", "error",
                         "batch-mate of an expired request lost greedy "
                         "parity")]
    return [_ok("serving_deadline",
                "overdue request expired; batch-mate stayed bit-exact")]


def _check_serving_slot_error(m):
    import numpy as np

    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.testing import failpoints as fp

    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, 64, (n,)).astype(np.int32) for n in (4, 7)]
    eng = ServingEngine(m, max_batch=2)
    rids = [eng.submit(p, max_new_tokens=6) for p in prompts]
    eng.step()
    with fp.scoped("serving/slot=error:1"):
        eng.step()
    res = eng.run_until_complete()
    reasons = {rid: res[rid].finish_reason for rid in rids}
    if sorted(reasons.values()) != ["error", "length"]:
        return [_finding("serving_slot_error", "error",
                         "injected slot error did not evict exactly one "
                         f"request (reasons={reasons})")]
    (surv,) = [rid for rid in rids if reasons[rid] == "length"]
    if not np.array_equal(res[surv].tokens,
                          _ref_tokens(m, prompts[rids.index(surv)], 6)):
        return [_finding("serving_slot_error", "error",
                         "the surviving slot lost greedy parity")]
    return [_ok("serving_slot_error",
                "injected slot error isolated; survivor bit-exact")]


def _check_serving_shed(m):
    import numpy as np

    from paddle_tpu.inference.serving import QueueFullError, ServingEngine

    rng = np.random.RandomState(2)
    p = rng.randint(0, 64, (5,)).astype(np.int32)
    eng = ServingEngine(m, max_batch=1, max_queue=1)
    low = eng.submit(p, max_new_tokens=2, priority=0)
    try:
        eng.submit(p, max_new_tokens=2, priority=0)
        return [_finding("serving_shed", "error",
                         "full queue accepted an equal-priority request")]
    except QueueFullError:
        pass
    eng.submit(p, max_new_tokens=2, priority=5)
    if eng.get_request(low).finish_reason != "shed":
        return [_finding("serving_shed", "error",
                         "higher-priority arrival did not shed the "
                         "lowest-priority queued request")]
    return [_ok("serving_shed",
                "queue bound enforced; priority shedding works")]


def _check_router_failover(m):
    import numpy as np

    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.serving.router import Router
    from paddle_tpu.testing import failpoints as fp

    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, 64, (n,)).astype(np.int32)
               for n in (4, 7, 9)]
    router = Router({"a": ServingEngine(m, max_batch=2),
                     "b": ServingEngine(m, max_batch=2)})
    rids = [router.submit(p, max_new_tokens=6, session_id=i)
            for i, p in enumerate(prompts)]
    for _ in range(2):
        router.step()   # tokens already streaming on both engines
    with fp.scoped("serving/step=error:1"):
        router.step()   # the first stepped engine dies mid-stream
    st = router.stats()["router"]
    if len(st["dead"]) != 1:
        return [_finding("router_failover", "error",
                         "killed engine was not marked dead "
                         f"(dead={st['dead']})")]
    res = router.run_until_complete()
    for rid, p in zip(rids, prompts):
        if res[rid].finish_reason != "length":
            return [_finding(
                "router_failover", "error",
                f"request {rid} finished with "
                f"{res[rid].finish_reason!r}, not 'length' — the finish "
                "reason was lost in the failover")]
        if not np.array_equal(res[rid].tokens, _ref_tokens(m, p, 6)):
            return [_finding("router_failover", "error",
                             f"request {rid} lost greedy parity after "
                             "re-routing to the survivor")]
    (survivor,) = st["alive"]
    stranded = [rid for rid in rids
                if router._reqs[rid].engine != survivor]
    if stranded:
        return [_finding("router_failover", "error",
                         f"requests {stranded} did not end on the "
                         f"surviving engine {survivor!r}")]
    return [_ok("router_failover",
                "engine killed mid-stream; all requests finished on the "
                "survivor, bit-exact, reasons recorded")]


def _check_stall_dump(m):
    """Chaos-injected stall: a serving/step=delay failpoint wedges one
    engine step; the sentinel (short timeout) must fire DURING the wedge
    and leave a bundle naming site=serving/step + the in-flight rids."""
    import glob

    import numpy as np

    from paddle_tpu import flags
    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.monitor import blackbox as bb
    from paddle_tpu.testing import failpoints as fp

    rng = np.random.RandomState(4)
    prompt = rng.randint(0, 64, (5,)).astype(np.int32)
    tmp_ctx = tempfile.TemporaryDirectory(
        prefix="paddle_tpu_chaos_blackbox_")
    d = tmp_ctx.name
    old_dir = flags.get_flag("blackbox_dir", "")
    was_enabled = bb.is_enabled()
    bb.enable(install=False)
    flags.set_flags({"blackbox_dir": d})
    try:
        eng = ServingEngine(m, max_batch=1)
        rid = eng.submit(prompt, max_new_tokens=6)
        eng.step()   # a healthy beat first: the stall is a TRANSITION
        bb.start_sentinel(timeout_s=0.15, poll_s=0.05)
        with fp.scoped("serving/step=delay:800"):
            eng.step()   # wedged inside the delay; the sentinel fires
        # the sentinel writes the bundle on ITS thread: poll briefly so a
        # loaded CI machine's slow write doesn't read as a missed fire
        deadline = time.time() + 3.0
        bundles = []
        while time.time() < deadline:
            bundles = sorted(glob.glob(os.path.join(d,
                                                    "blackbox-*.json")))
            if bundles:
                break
            time.sleep(0.05)
        if not bundles:
            return [_finding("stall_dump", "error",
                             "sentinel did not write a dump bundle while "
                             "the engine step was wedged")]
        bundle = bb.load_bundle(bundles[0])
        if bundle["reason"] != "stall" \
                or bundle.get("site") != "serving/step":
            return [_finding(
                "stall_dump", "error",
                f"bundle names reason={bundle['reason']!r} "
                f"site={bundle.get('site')!r}, expected a stall at "
                "serving/step")]
        tables = [t["table"] for t in bundle.get("requests", [])
                  if t.get("kind") == "serving_engine" and "table" in t]
        if not any(rid in t.get("in_flight", []) for t in tables):
            return [_finding("stall_dump", "error",
                             f"wedged request rid={rid} missing from the "
                             "bundle's in-flight request tables")]
        if not bundle.get("stacks"):
            return [_finding("stall_dump", "error",
                             "bundle carries no all-thread stacks")]
        res = eng.run_until_complete()
        if not np.array_equal(res[rid].tokens, _ref_tokens(m, prompt, 6)):
            return [_finding("stall_dump", "error",
                             "the wedged-then-released request lost "
                             "greedy parity")]
    finally:
        bb.stop_sentinel()
        flags.set_flags({"blackbox_dir": old_dir})
        bb.quiesce()
        bb.reset()
        if not was_enabled:
            bb.disable()
        tmp_ctx.cleanup()
    return [_ok("stall_dump",
                "sentinel fired during the wedge; bundle named "
                "site=serving/step + in-flight rids; drain stayed "
                "bit-exact")]


def _check_stage_backpressure(m):
    """Chaos-injected MPMD edge stall: with FLAGS_mpmd armed the disagg
    pool's prefill->decode hand-off travels a typed StageEdge. First a
    full edge must reject the overflow put (EdgeFullError, counted as
    backpressure) and still drain every accepted payload FIFO bit-exact;
    then a stage/edge=delay failpoint wedges one live hand-off inside the
    edge's beacon window — the stall sentinel must fire DURING the wedge
    naming site=stage/edge, and the post-stall drain must keep exact
    greedy parity with edge puts==gets==prompts (no payload lost)."""
    import glob

    import numpy as np

    from paddle_tpu import flags
    from paddle_tpu.monitor import blackbox as bb
    from paddle_tpu.serving.disagg import DisaggregatedPool
    from paddle_tpu.testing import failpoints as fp

    name = "stage_backpressure"
    old_mpmd = flags.get_flag("mpmd", False)
    flags.set_flags({"mpmd": True})
    try:
        from paddle_tpu.distributed import stage as stage_mod

        # 1) a FULL edge backpressures without loss: a capacity-2 queue
        # rejects the third put before doing any work, counts it, then
        # drains FIFO bit-exact and accepts the retried payload
        edge = stage_mod.StageEdge("chaos", stage_mod.HANDOFF_SCHEMA,
                                   capacity=2)
        rows = [np.full((1, 2, 4), float(i + 1), np.float32)
                for i in range(3)]
        for r in rows[:2]:
            edge.put({"activation": r})
        try:
            edge.put({"activation": rows[2]})
            return [_finding(name, "error",
                             "third put on a capacity-2 edge did not "
                             "raise EdgeFullError")]
        except stage_mod.EdgeFullError:
            pass
        if edge.stats["backpressured"] != 1 or edge.stats["puts"] != 2:
            return [_finding(name, "error",
                             "rejected put was not booked as pure "
                             f"backpressure: {edge.stats}")]
        drained = [edge.get()["activation"] for _ in range(2)]
        edge.put({"activation": rows[2]})   # the producer's retry lands
        drained.append(edge.get()["activation"])
        for want, got in zip(rows, drained):
            if not np.array_equal(np.asarray(got), want):
                return [_finding(name, "error",
                                 "backpressured edge lost or reordered a "
                                 "payload on drain")]

        # 2) the armed pool wedged INSIDE a live edge put: two waves of
        # prompts so the wedged step still has a free decode slot (and
        # therefore actually touches the edge), healthy beat first so
        # the stall is a transition the sentinel can see
        rng = np.random.RandomState(7)
        prompts = [rng.randint(0, 64, (n,)).astype(np.int32)
                   for n in (5, 7, 4, 6, 5, 8)]
        tmp_ctx = tempfile.TemporaryDirectory(
            prefix="paddle_tpu_chaos_stage_")
        d = tmp_ctx.name
        old_dir = flags.get_flag("blackbox_dir", "")
        was_enabled = bb.is_enabled()
        bb.enable(install=False)
        flags.set_flags({"blackbox_dir": d})
        try:
            pool = DisaggregatedPool(m, prefill_workers=1,
                                     decode_engines=1, max_batch=3)
            rids = [pool.submit(p, max_new_tokens=5) for p in prompts[:2]]
            pool.step()   # healthy hand-offs first
            rids += [pool.submit(p, max_new_tokens=5) for p in prompts[2:]]
            bb.start_sentinel(timeout_s=0.15, poll_s=0.05)
            with fp.scoped("stage/edge=delay:800"):
                pool.step()   # one free slot -> one wedged hand-off
            deadline = time.time() + 3.0
            bundles = []
            while time.time() < deadline:
                bundles = sorted(glob.glob(os.path.join(
                    d, "blackbox-*.json")))
                if bundles:
                    break
                time.sleep(0.05)
            if not bundles:
                return [_finding(name, "error",
                                 "sentinel wrote no dump bundle while a "
                                 "stage-edge hand-off was wedged")]
            bundle = bb.load_bundle(bundles[0])
            if bundle["reason"] != "stall" \
                    or bundle.get("site") != "stage/edge":
                return [_finding(
                    name, "error",
                    f"bundle names reason={bundle['reason']!r} "
                    f"site={bundle.get('site')!r}, expected a stall at "
                    "stage/edge")]
            res = pool.run_until_complete()
            for rid, p in zip(rids, prompts):
                if not np.array_equal(res[rid].tokens,
                                      _ref_tokens(m, p, 5)):
                    return [_finding(name, "error",
                                     "post-stall drain lost greedy "
                                     f"parity for rid={rid}")]
            st = pool.stats()["edge"]
            if st["puts"] != len(prompts) or st["gets"] != len(prompts):
                return [_finding(name, "error",
                                 "edge puts/gets do not match the prompt "
                                 f"count — a payload was lost: {st}")]
        finally:
            bb.stop_sentinel()
            flags.set_flags({"blackbox_dir": old_dir})
            bb.quiesce()
            bb.reset()
            if not was_enabled:
                bb.disable()
            tmp_ctx.cleanup()
    finally:
        flags.set_flags({"mpmd": old_mpmd})
    return [_ok(name,
                "full edge backpressured without loss; sentinel fired "
                "during the wedge naming site=stage/edge; post-stall "
                "drain stayed bit-exact with puts==gets==prompts")]


def _export_tiny_adapter(m, seed):
    """A LoRA export over the tiny chaos model, lora_B randomized so the
    adapter's delta actually moves tokens."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.incubate.lora import apply_lora, export_lora
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=1,
                    num_heads=2, max_seq_len=64, dropout=0.0)
    m2 = GPTForCausalLM(cfg)
    m2.load_dict(m.state_dict())
    apply_lora(m2, r=4, alpha=8)
    rng = np.random.RandomState(seed)
    for n_, p_ in m2.named_parameters():
        if "lora_B" in n_:
            p_.set_value(paddle.to_tensor(
                rng.normal(0, 0.3, p_.shape).astype(np.float32)))
    return export_lora(m2)


def _check_adapter_evict_under_load(m):
    """Chaos-injected adapter churn on the FLAGS_paged_kv engine: the hot
    adapter is evicted while its session is mid-stream — the session must
    be booted back to the queue (NOT finished reason='error'), re-admit
    after the adapter hot-reloads, and finish bit-exact against an
    undisturbed twin. A serving/adapter=error:1 failpoint on a load must
    additionally leave the registry and device factors exactly as they
    were, with in-flight sessions still decoding."""
    import numpy as np

    from paddle_tpu import flags
    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.testing import failpoints as fp

    name = "adapter_evict_under_load"
    old = {"paged_kv": flags.get_flag("paged_kv")}
    flags.set_flags({"paged_kv": True})
    try:
        expA = _export_tiny_adapter(m, 11)
        expB = _export_tiny_adapter(m, 12)
        rng = np.random.RandomState(5)
        prompt = rng.randint(0, 64, (5,)).astype(np.int32)

        ref_eng = ServingEngine(m, max_batch=2, max_adapters=2)
        ref_eng.load_adapter("hot", expA)
        rr = ref_eng.submit(prompt, max_new_tokens=8, adapter="hot")
        ref = tuple(int(t)
                    for t in ref_eng.run_until_complete()[rr].output_ids)

        eng = ServingEngine(m, max_batch=2, max_adapters=2)
        eng.load_adapter("hot", expA)
        rid = eng.submit(prompt, max_new_tokens=8, adapter="hot")
        for _ in range(3):
            eng.step()          # mid-stream: tokens already emitted
        if not eng.get_request(rid).output_ids:
            return [_finding(name, "error",
                             "scenario broken: no tokens streamed before "
                             "the eviction")]
        eng.evict_adapter("hot")   # under load: boots the live session
        req = eng.get_request(rid)
        if req.finish_reason is not None:
            return [_finding(name, "error",
                             "evicting the hot adapter finished its "
                             f"session (reason={req.finish_reason!r}) "
                             "instead of requeueing it")]
        with fp.scoped("serving/adapter=error:1"):
            try:
                eng.load_adapter("other", expB)
                return [_finding(name, "error",
                                 "armed serving/adapter failpoint did "
                                 "not fire on load_adapter")]
            except fp.FailpointError:
                pass
        if eng._adapters.lookup("other") is not None:
            return [_finding(name, "error",
                             "a load that died on the failpoint still "
                             "mutated the adapter registry")]
        eng.load_adapter("hot", expA)   # hot-reload: the session re-admits
        res = eng.run_until_complete()
        got = tuple(int(t) for t in res[rid].output_ids)
        if res[rid].finish_reason != "length" or got != ref:
            return [_finding(
                name, "error",
                "evicted-then-reloaded session lost bit-exactness vs the "
                f"undisturbed twin (reason={res[rid].finish_reason!r}, "
                f"got={list(got)}, want={list(ref)})")]
    finally:
        flags.set_flags(old)
    return [_ok(name,
                "hot adapter evicted mid-stream; session requeued (not "
                "errored), re-admitted after hot-reload, bit-exact vs "
                "the undisturbed twin; a failed load left the registry "
                "untouched")]


def _check_page_pool_full(m):
    """Paged-KV pool exhaustion: reservation-before-compute means a full
    pool backpressures BEFORE any prefill work — a permanently-oversized
    request is rejected at submit() (pool counters unmoved), and
    transient exhaustion requeues sessions until blocks free, every one
    finishing reason='length' bit-exact against a roomy-pool twin."""
    import numpy as np

    from paddle_tpu import flags
    from paddle_tpu.inference.serving import ServingEngine

    name = "page_pool_full"
    old = {"paged_kv": flags.get_flag("paged_kv")}
    flags.set_flags({"paged_kv": True})
    try:
        rng = np.random.RandomState(6)
        prompts = [rng.randint(0, 64, (n,)).astype(np.int32)
                   for n in (4, 6, 5)]

        # 3 usable frames (+ null): a 60-column budget needs 4 blocks —
        # never fits; the 3-block transient requests fit one at a time
        eng = ServingEngine(m, max_batch=4, page_blocks=4)
        free0 = eng._pool.stats()["free_blocks"]
        try:
            eng.submit(rng.randint(0, 64, (40,)).astype(np.int32),
                       max_new_tokens=20)
            return [_finding(name, "error",
                             "a request that can NEVER fit the pool was "
                             "accepted instead of rejected at submit()")]
        except ValueError:
            pass
        if eng._pool.stats()["free_blocks"] != free0:
            return [_finding(name, "error",
                             "the rejected oversized request leaked pool "
                             "blocks — work happened before the "
                             "reservation check")]
        rids = [eng.submit(p, max_new_tokens=30) for p in prompts]
        res = eng.run_until_complete()
        roomy = ServingEngine(m, max_batch=4)
        rids2 = [roomy.submit(p, max_new_tokens=30) for p in prompts]
        res2 = roomy.run_until_complete()
        for i, (a, b) in enumerate(zip(rids, rids2)):
            if res[a].finish_reason != "length":
                return [_finding(
                    name, "error",
                    f"request {i} under the tiny pool finished "
                    f"{res[a].finish_reason!r}, not 'length' — "
                    "backpressure turned into an error")]
            if [int(t) for t in res[a].output_ids] \
                    != [int(t) for t in res2[b].output_ids]:
                return [_finding(name, "error",
                                 f"request {i} lost bit-exactness under "
                                 "pool-full requeueing")]
        if eng._pool.stats()["live_blocks"] != 0:
            return [_finding(name, "error",
                             "drained engine still holds live pool "
                             f"blocks: {eng._pool.stats()}")]
    finally:
        flags.set_flags(old)
    return [_ok(name,
                "oversized request rejected before any work; transient "
                "pool exhaustion requeued sessions to bit-exact "
                "completion; all blocks freed on drain")]


def _check_trainer_nonfinite():
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.distributed.mesh import build_mesh
    from paddle_tpu.distributed.spmd import SpmdTrainer

    paddle.set_flags({"check_nan_inf": True})
    try:
        paddle.seed(0)
        model = paddle.nn.Linear(4, 1)
        opt = paddle.optimizer.Adam(learning_rate=0.1,
                                    parameters=model.parameters())
        mesh = build_mesh((1,), ("dp",), devices=jax.devices()[:1])
        tr = SpmdTrainer(model, opt, loss_fn=paddle.nn.MSELoss(),
                         mesh=mesh)
        x = np.ones((2, 4), np.float32)
        y = np.zeros((2, 1), np.float32)
        tr.train_step(x, y)
        snap = {k: np.asarray(v).copy() for k, v in tr.params.items()}
        count = opt._step_count
        xnan = x.copy()
        xnan[0, 0] = np.nan
        loss = tr.train_step(xnan, y)
        if not np.isnan(float(np.asarray(loss._data))):
            return [_finding("trainer_nonfinite", "error",
                             "poisoned batch did not produce a NaN loss — "
                             "the scenario itself is broken")]
        # ISSUE 11 deferred guard: the verdict is fetched at the next
        # step/stats boundary — force it so the skip is booked
        tr.guard_sync()
        drift = [k for k, v in tr.params.items()
                 if np.asarray(tr.params[k]).tobytes() != snap[k].tobytes()]
        if drift:
            return [_finding("trainer_nonfinite", "error",
                             "non-finite step leaked into parameters: "
                             f"{drift}")]
        if opt._step_count != count:
            return [_finding("trainer_nonfinite", "error",
                             "skipped step advanced the optimizer step "
                             "count")]
    finally:
        paddle.set_flags({"check_nan_inf": False})
    return [_ok("trainer_nonfinite",
                "NaN step skipped; parameters bit-identical")]


def _check_numerics_anomaly():
    """Chaos-injected drift: a trainer/batch=scale:1e4 failpoint blows
    one step's gradients up — finite, so the PR 4 guard stays silent,
    but the telescope's grad-spike detector must fire and NAME the
    layer. A scale:nan step afterwards trips the guard; the per-layer
    nonfinite detector must fire alongside it. Proves detection comes
    BEFORE the step is ruined."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.distributed.mesh import build_mesh
    from paddle_tpu.distributed.spmd import SpmdTrainer
    from paddle_tpu.testing import failpoints as fp

    name = "numerics_anomaly"
    old = {k: paddle.get_flags(["FLAGS_" + k])["FLAGS_" + k]
           for k in ("numerics", "numerics_interval", "check_nan_inf")}
    paddle.set_flags({"numerics": True, "numerics_interval": 1,
                      "check_nan_inf": True})
    try:
        paddle.seed(0)
        model = paddle.nn.Linear(8, 4)
        opt = paddle.optimizer.SGD(learning_rate=0.05,
                                   parameters=model.parameters())
        mesh = build_mesh((1,), ("dp",), devices=jax.devices()[:1])
        tr = SpmdTrainer(model, opt, loss_fn=paddle.nn.MSELoss(),
                         mesh=mesh)
        rng = np.random.RandomState(0)
        x = rng.randn(4, 8).astype(np.float32)
        y = rng.randn(4, 4).astype(np.float32)
        for _ in range(4):          # baseline: the EMA learns "normal"
            tr.train_step(x, y)
        if tr._numerics.anomalies:
            return [_finding(name, "error",
                             "detector cried wolf during baseline "
                             f"training: {list(tr._numerics.anomalies)}")]
        skipped = tr.stats()["breakdown"]["nonfinite_skipped_total"]
        with fp.scoped("trainer/batch=scale:10000"):
            tr.train_step(x, y)     # finite spike: detector territory
        spikes = [a for a in tr._numerics.anomalies
                  if a["kind"] == "grad_spike"]
        if not spikes:
            return [_finding(name, "error",
                             "injected gradient spike did not fire the "
                             "grad_spike detector")]
        if not spikes[0].get("layer"):
            return [_finding(name, "error",
                             "grad_spike anomaly does not name a layer")]
        after_spike = tr.stats()["breakdown"]["nonfinite_skipped_total"]
        if after_spike != skipped:
            return [_finding(name, "error",
                             "the finite spike tripped the non-finite "
                             "guard — the detector did not get there "
                             "first")]
        with fp.scoped("trainer/batch=scale:nan"):
            tr.train_step(x, y)     # poisoned step: guard territory
        if tr.stats()["breakdown"]["nonfinite_skipped_total"] \
                != skipped + 1:
            return [_finding(name, "error",
                             "scale:nan step did not trip the "
                             "FLAGS_check_nan_inf guard")]
        nonf = [a for a in tr._numerics.anomalies
                if a["kind"] == "nonfinite" and a.get("layer")]
        if not nonf:
            return [_finding(name, "error",
                             "poisoned step fired no per-layer "
                             "nonfinite anomaly — the guard knows the "
                             "step died but not WHERE")]
    finally:
        paddle.set_flags(old)
    return [_ok(name,
                f"grad_spike named layer {spikes[0]['layer']!r} before "
                "the non-finite guard tripped; the nan step then fired "
                f"nonfinite on {sorted({a['layer'] for a in nonf})}")]


def _check_quantized_nonfinite():
    """Chaos-injected poison under the quantized reduce: a scale:nan
    batch must trip the PR 4 guard THROUGH the int8 wire format (the NaN
    rides the fp32 block scales — the int8 payload never decides), and
    the where-select must restore params AND the error-feedback residuals
    bit-exactly, so no quantization poison leaks into the next step."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.distributed.mesh import build_mesh
    from paddle_tpu.distributed.spmd import SpmdTrainer
    from paddle_tpu.testing import failpoints as fp

    name = "quantized_nonfinite"
    old = {k: paddle.get_flags(["FLAGS_" + k])["FLAGS_" + k]
           for k in ("quantized_allreduce", "quantized_allreduce_min_size",
                     "check_nan_inf")}
    paddle.set_flags({"quantized_allreduce": True,
                      "quantized_allreduce_min_size": 1,
                      "check_nan_inf": True})
    try:
        paddle.seed(0)
        model = paddle.nn.Linear(8, 4)
        opt = paddle.optimizer.AdamW(learning_rate=0.05,
                                     parameters=model.parameters())
        mesh = build_mesh((1,), ("dp",), devices=jax.devices()[:1])
        tr = SpmdTrainer(model, opt, loss_fn=paddle.nn.MSELoss(),
                         mesh=mesh)
        if not tr._quantized or not tr._qar_eligible:
            return [_finding(name, "error",
                             "scenario broken: the trainer did not arm "
                             "the quantized reduce")]
        rng = np.random.RandomState(0)
        x = rng.randn(4, 8).astype(np.float32)
        y = rng.randn(4, 4).astype(np.float32)
        for _ in range(2):
            tr.train_step(x, y)
        snap_p = {k: np.asarray(v).copy() for k, v in tr.params.items()}
        snap_r = {k: np.asarray(v).copy()
                  for k, v in tr.opt_state["__qar_residual__"].items()}
        if not any(np.any(v != 0) for v in snap_r.values()):
            return [_finding(name, "error",
                             "scenario broken: error-feedback residuals "
                             "never became non-zero during baseline "
                             "training")]
        skipped = tr.stats()["breakdown"]["nonfinite_skipped_total"]
        with fp.scoped("trainer/batch=scale:nan"):
            loss = tr.train_step(x, y)
        if not np.isnan(float(np.asarray(loss._data))):
            return [_finding(name, "error",
                             "poisoned batch did not produce a NaN loss "
                             "through the quantized reduce — the int8 "
                             "path swallowed the poison")]
        if tr.stats()["breakdown"]["nonfinite_skipped_total"] \
                != skipped + 1:
            return [_finding(name, "error",
                             "scale:nan step did not trip the "
                             "FLAGS_check_nan_inf guard under the "
                             "quantized path")]
        drift = [k for k in snap_p
                 if np.asarray(tr.params[k]).tobytes()
                 != snap_p[k].tobytes()]
        if drift:
            return [_finding(name, "error",
                             "non-finite quantized step leaked into "
                             f"parameters: {drift}")]
        poisoned = [k for k in snap_r
                    if np.asarray(
                        tr.opt_state["__qar_residual__"][k]).tobytes()
                    != snap_r[k].tobytes()]
        if poisoned:
            return [_finding(name, "error",
                             "error-feedback residuals were not "
                             "where-selected back on the skipped step — "
                             f"poison carried forward in: {poisoned}")]
        after = tr.train_step(x, y)
        if not np.isfinite(float(np.asarray(after._data))):
            return [_finding(name, "error",
                             "the step AFTER the skip is non-finite — "
                             "residual state carried poison")]
    finally:
        paddle.set_flags(old)
    return [_ok(name,
                "NaN step skipped through the int8 reduce; params and "
                "EF residuals bit-identical; next step trained clean")]


def _check_async_nonfinite():
    """Chaos-injected poison under FLAGS_async_dispatch: a scale:nan
    batch's verdict is only FETCHED up to FLAGS_async_window steps
    later — the deferred drain must still book the skip (within the
    window), the device-side where-select must have left params and
    schedule bit-identical, the next step must train clean, and a
    blackbox dump bundle must record how deep the in-flight window
    was."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import flags
    from paddle_tpu.distributed.mesh import build_mesh
    from paddle_tpu.distributed.spmd import SpmdTrainer
    from paddle_tpu.monitor import blackbox as bb
    from paddle_tpu.testing import failpoints as fp

    name = "async_nonfinite"
    old = {k: paddle.get_flags(["FLAGS_" + k])["FLAGS_" + k]
           for k in ("async_dispatch", "async_window", "check_nan_inf")}
    paddle.set_flags({"async_dispatch": True, "async_window": 4,
                      "check_nan_inf": True})
    tmp_ctx = tempfile.TemporaryDirectory(
        prefix="paddle_tpu_chaos_async_blackbox_")
    old_dir = flags.get_flag("blackbox_dir", "")
    was_enabled = bb.is_enabled()
    bb.enable(install=False)
    flags.set_flags({"blackbox_dir": tmp_ctx.name})
    try:
        paddle.seed(0)
        model = paddle.nn.Linear(8, 4)
        opt = paddle.optimizer.AdamW(learning_rate=0.05,
                                     parameters=model.parameters())
        mesh = build_mesh((1,), ("dp",), devices=jax.devices()[:1])
        tr = SpmdTrainer(model, opt, loss_fn=paddle.nn.MSELoss(),
                         mesh=mesh)
        rng = np.random.RandomState(0)
        x = rng.randn(4, 8).astype(np.float32)
        y = rng.randn(4, 4).astype(np.float32)
        for _ in range(2):
            tr.train_step(x, y)
        tr.guard_sync()
        snap = {k: np.asarray(v).copy() for k, v in tr.params.items()}
        count = opt._step_count
        skipped = tr._nonfinite_total
        with fp.scoped("trainer/batch=scale:nan"):
            tr.train_step(x, y)
        if tr._nonfinite_total != skipped:
            return [_finding(name, "error",
                             "the verdict was fetched eagerly — the "
                             "async path did not defer it")]
        if len(tr._pending_verdicts) != 1:
            return [_finding(name, "error",
                             "poisoned step's verdict is not in the "
                             "deferred window")]
        dump_path = bb.dump("stall", site="trainer/step",
                            extra={"trigger": "chaos async_nonfinite"})
        tr.guard_sync()   # within the window: the host now learns
        if tr._nonfinite_total != skipped + 1:
            return [_finding(name, "error",
                             "deferred drain did not book the skipped "
                             "step within the window")]
        if opt._step_count != count:
            return [_finding(name, "error",
                             "skipped step left the optimizer schedule "
                             f"moved ({opt._step_count} != {count})")]
        drift = [k for k in snap
                 if np.asarray(tr.params[k]).tobytes()
                 != snap[k].tobytes()]
        if drift:
            return [_finding(name, "error",
                             "non-finite step leaked into parameters "
                             f"under async dispatch: {drift}")]
        if dump_path is None:
            return [_finding(name, "error",
                             "blackbox dump failed to write")]
        bundle = bb.load_bundle(dump_path)
        tables = [t["table"] for t in bundle.get("requests", [])
                  if t.get("kind") == "trainer_async" and "table" in t]
        if not tables:
            return [_finding(name, "error",
                             "dump bundle carries no trainer_async "
                             "in-flight window table")]
        tbl = tables[-1]
        if tbl.get("window") != 4 or tbl.get("pending") != 1:
            return [_finding(name, "error",
                             "bundle's window table does not record the "
                             f"in-flight depth (got {tbl})")]
        after = tr.train_step(x, y)
        tr.guard_sync()
        if not np.isfinite(float(np.asarray(after._data))):
            return [_finding(name, "error",
                             "the step AFTER the deferred skip is "
                             "non-finite")]
    finally:
        paddle.set_flags(old)
        flags.set_flags({"blackbox_dir": old_dir})
        bb.quiesce()
        bb.reset()
        if not was_enabled:
            bb.disable()
        tmp_ctx.cleanup()
    return [_ok(name,
                "nan step's verdict deferred 1-in-window, drain booked "
                "the skip, params/schedule bit-identical, bundle "
                "recorded window depth, next step trained clean")]


def _check_elastic_resume():
    """Chaos-injected preemption: kill a dp8 supervised run mid-step and
    mark the dp8 topology gone — the ElasticSupervisor must resume on
    dp4 (topology-aware restore: [dp, shard] moments re-laid), keep the
    loss trajectory within tolerance of an uninterrupted dp8 twin, and
    leave the recovery attributable (blackbox crash bundle at
    site=elastic/resume, elastic_resume_total{reason=failpoint})."""
    import glob

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import flags, monitor
    from paddle_tpu.distributed.mesh import build_mesh
    from paddle_tpu.distributed.spmd import SpmdTrainer
    from paddle_tpu.monitor import blackbox as bb
    from paddle_tpu.testing import failpoints as fp

    name = "elastic_resume"
    old = {k: flags.get_flag(k)
           for k in ("elastic", "shard_weight_update", "blackbox_dir")}
    tmp_ctx = tempfile.TemporaryDirectory(prefix="paddle_tpu_chaos_elastic_")
    was_enabled = bb.is_enabled()
    bb.enable(install=False)
    paddle.set_flags({"elastic": True, "shard_weight_update": True,
                      "blackbox_dir": os.path.join(tmp_ctx.name, "bb")})
    try:
        class MLP(paddle.nn.Layer):
            def __init__(self):
                super().__init__()
                self.l1 = paddle.nn.Linear(64, 64)
                self.l2 = paddle.nn.Linear(64, 1)

            def forward(self, x):
                return self.l2(paddle.nn.functional.relu(self.l1(x)))

        def build(mesh):
            paddle.seed(0)
            m = MLP()
            opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                         parameters=m.parameters())
            return SpmdTrainer(
                m, opt, loss_fn=lambda p, y: ((p - y) ** 2).mean(),
                mesh=mesh)

        rng = np.random.RandomState(0)
        data = [(rng.randn(8, 64).astype(np.float32),
                 rng.randn(8, 1).astype(np.float32)) for _ in range(6)]

        # the uninterrupted dp8 twin
        twin = build(build_mesh((8,), ("dp",), devices=jax.devices()[:8]))
        twin_losses = [float(np.asarray(twin.train_step(x, y)._data))
                       for x, y in data]

        from paddle_tpu.distributed.elastic import ElasticSupervisor
        from paddle_tpu.incubate.checkpoint.auto_checkpoint import \
            CheckpointSaver

        alive = {"dp8": True}

        def dp8():
            return build_mesh((8,), ("dp",), devices=jax.devices()[:8]) \
                if alive["dp8"] else None

        def dp4():
            return build_mesh((4,), ("dp",), devices=jax.devices()[:4])

        class KillAt(list):
            def __init__(self, items, at):
                super().__init__(items)
                self.at, self.fired = at, False

            def __getitem__(self, i):
                if i == self.at and not self.fired:
                    self.fired = True
                    alive["dp8"] = False
                    fp.arm("trainer/step", "error:1")
                return super().__getitem__(i)

        sup = ElasticSupervisor(
            build, CheckpointSaver(os.path.join(tmp_ctx.name, "ckpt")),
            [dp8, dp4], checkpoint_interval=1)
        losses = sup.run(KillAt(data, 3))

        if not sup.recoveries:
            return [_finding(name, "error",
                             "the killed step produced no recovery")]
        rec = sup.recoveries[0]
        if rec["reason"] != "failpoint":
            return [_finding(name, "error",
                             f"recovery reason {rec['reason']!r}, "
                             "expected 'failpoint' (the injected kill)")]
        if int(sup.trainer.mesh.shape["dp"]) != 4:
            return [_finding(name, "error",
                             "supervisor did not resume on the shrunken "
                             "dp4 mesh")]
        drift = max(abs(a - b) for a, b in zip(losses, twin_losses))
        if not np.allclose(losses, twin_losses, rtol=1e-4, atol=5e-4):
            return [_finding(
                name, "error",
                f"resumed dp4 loss trajectory diverged from the "
                f"uninterrupted dp8 twin (max |diff|={drift:.3e}, "
                "band rtol=1e-4 atol=5e-4)")]
        # attribution: the crash bundle names the recovery site
        bundles = sorted(glob.glob(os.path.join(
            tmp_ctx.name, "bb", "blackbox-*.json")))
        if not bundles:
            return [_finding(name, "error",
                             "recovery wrote no blackbox crash bundle")]
        bundle = bb.load_bundle(bundles[0])
        if bundle.get("site") != "elastic/resume" \
                or bundle.get("reason") != "crash":
            return [_finding(
                name, "error",
                f"bundle names reason={bundle.get('reason')!r} "
                f"site={bundle.get('site')!r}, expected a crash bundle "
                "at elastic/resume")]
        # ...and the lazy counter carries the reason
        snap = monitor.snapshot()
        moved = [s for m in snap["metrics"]
                 if m["name"] == "elastic_resume_total"
                 for s in m["series"]
                 if s["labels"].get("reason") == "failpoint"
                 and s["value"] > 0]
        if not moved:
            return [_finding(name, "error",
                             "elastic_resume_total{reason=failpoint} "
                             "did not move")]
    finally:
        fp.reset()
        paddle.set_flags(old)
        bb.quiesce()
        bb.reset()
        if not was_enabled:
            bb.disable()
        tmp_ctx.cleanup()
    return [_ok(name,
                f"dp8 kill at step {rec['step'] - 1} resumed on dp4 "
                f"(reason={rec['reason']}, max loss drift "
                f"{drift:.1e}); bundle at site=elastic/resume + "
                "elastic_resume_total attribute the recovery")]


def _check_goodput_attribution():
    """Chaos-injected preemption under the goodput ledger: the dp8 kill +
    dp4 resume of elastic_resume re-run with FLAGS_goodput armed. The
    finalized run's ledger row must book NONZERO resume_backoff +
    ckpt_restore + reshard seconds, its buckets must sum to the run's
    wall time within 10% (exclusive attribution), its goodput must land
    BELOW an uninterrupted twin's, the twin must book >= 95% of its
    post-warmup wall as step+compile, and the recovery's crash bundle
    must carry the goodput provider naming the bucket active at kill
    time."""
    import glob

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import flags, monitor
    from paddle_tpu.distributed.mesh import build_mesh
    from paddle_tpu.distributed.spmd import SpmdTrainer
    from paddle_tpu.monitor import blackbox as bb
    from paddle_tpu.monitor import goodput, perfledger
    from paddle_tpu.testing import failpoints as fp

    name = "goodput_attribution"
    old = {k: flags.get_flag(k)
           for k in ("goodput", "elastic", "shard_weight_update",
                     "blackbox_dir", "perf_ledger", "perf_ledger_path",
                     "perf_ledger_warmup", "perf_ledger_interval")}
    tmp_ctx = tempfile.TemporaryDirectory(prefix="paddle_tpu_chaos_goodput_")
    ledger_path = os.path.join(tmp_ctx.name, "perf.jsonl")
    was_enabled = bb.is_enabled()
    bb.enable(install=False)
    paddle.set_flags({"goodput": True, "elastic": True,
                      "shard_weight_update": True,
                      "blackbox_dir": os.path.join(tmp_ctx.name, "bb"),
                      "perf_ledger": True,
                      "perf_ledger_path": ledger_path,
                      "perf_ledger_warmup": 1, "perf_ledger_interval": 1})
    perfledger.reset_ledger()
    goodput.reset()
    try:
        class MLP(paddle.nn.Layer):
            def __init__(self):
                super().__init__()
                self.l1 = paddle.nn.Linear(64, 64)
                self.l2 = paddle.nn.Linear(64, 1)

            def forward(self, x):
                return self.l2(paddle.nn.functional.relu(self.l1(x)))

        def build(mesh):
            paddle.seed(0)
            m = MLP()
            opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                         parameters=m.parameters())
            return SpmdTrainer(
                m, opt, loss_fn=lambda p, y: ((p - y) ** 2).mean(),
                mesh=mesh)

        rng = np.random.RandomState(0)
        data = [(rng.randn(8, 64).astype(np.float32),
                 rng.randn(8, 1).astype(np.float32)) for _ in range(6)]

        # uninterrupted dp8 twin, post-warmup: one step outside its run
        # absorbs trainer build + first compile, the accounted window is
        # pure steady-state stepping
        twin = build(build_mesh((8,), ("dp",), devices=jax.devices()[:8]))
        twin.train_step(*data[0])
        goodput.start_run("chaos/goodput-twin")
        for x, y in data[1:]:
            twin.train_step(x, y)
        twin_row = goodput.end_run()
        productive = (twin_row["buckets"]["step"]
                      + twin_row["buckets"]["compile"])
        if productive < 0.95 * twin_row["wall_s"]:
            return [_finding(
                name, "error",
                f"uninterrupted twin booked only {productive:.3f}s of "
                f"{twin_row['wall_s']:.3f}s post-warmup wall as "
                "step+compile (< 95%)")]

        from paddle_tpu.distributed.elastic import ElasticSupervisor
        from paddle_tpu.incubate.checkpoint.auto_checkpoint import \
            CheckpointSaver

        alive = {"dp8": True}

        def dp8():
            return build_mesh((8,), ("dp",), devices=jax.devices()[:8]) \
                if alive["dp8"] else None

        def dp4():
            return build_mesh((4,), ("dp",), devices=jax.devices()[:4])

        class KillAt(list):
            def __init__(self, items, at):
                super().__init__(items)
                self.at, self.fired = at, False

            def __getitem__(self, i):
                if i == self.at and not self.fired:
                    self.fired = True
                    alive["dp8"] = False
                    fp.arm("trainer/step", "error:1")
                return super().__getitem__(i)

        goodput.start_run("chaos/goodput")
        sup = ElasticSupervisor(
            build, CheckpointSaver(os.path.join(tmp_ctx.name, "ckpt")),
            [dp8, dp4], checkpoint_interval=1)
        sup.run(KillAt(data, 3))
        row = goodput.end_run()
        if not sup.recoveries:
            return [_finding(name, "error",
                             "the killed step produced no recovery")]
        if int(sup.trainer.mesh.shape["dp"]) != 4:
            return [_finding(name, "error",
                             "supervisor did not resume on the shrunken "
                             "dp4 mesh")]
        # the recovery legs must be attributed, not lumped into step/other
        zero = [b for b in ("resume_backoff", "ckpt_restore", "reshard")
                if not row["buckets"].get(b, 0.0) > 0.0]
        if zero:
            return [_finding(
                name, "error",
                f"killed+resumed run booked no seconds in {zero} — "
                f"buckets: { {k: round(v, 4) for k, v in row['buckets'].items()} }")]
        booked = sum(row["buckets"].values())
        if abs(booked - row["wall_s"]) > 0.1 * row["wall_s"]:
            return [_finding(
                name, "error",
                f"buckets sum to {booked:.3f}s but the run walled "
                f"{row['wall_s']:.3f}s — outside the 10% band")]
        if not row["goodput"] < twin_row["goodput"]:
            return [_finding(
                name, "error",
                f"interrupted run's goodput {row['goodput']:.3f} is not "
                f"below the uninterrupted twin's "
                f"{twin_row['goodput']:.3f}")]
        # the ledger row landed at site=run/goodput with the breakdown
        rows = perfledger.load_rows(ledger_path)
        grows = [r for r in rows if r.get("site") == "run/goodput"
                 and r.get("sig") == "chaos/goodput"]
        if not grows:
            return [_finding(name, "error",
                             "finalized run appended no run/goodput "
                             "perf-ledger row")]
        # the crash bundle's goodput provider names the kill-time bucket
        bundles = sorted(glob.glob(os.path.join(
            tmp_ctx.name, "bb", "blackbox-*.json")))
        if not bundles:
            return [_finding(name, "error",
                             "recovery wrote no blackbox crash bundle")]
        bundle = bb.load_bundle(bundles[0])
        tables = [p for p in bundle.get("requests", [])
                  if p.get("kind") == "goodput"]
        if not tables:
            return [_finding(name, "error",
                             "crash bundle carries no goodput provider "
                             "table")]
        gp = tables[0].get("table", {})
        at_kill = gp.get("active_bucket") or gp.get("last_bucket")
        if at_kill != "step":
            return [_finding(
                name, "error",
                f"crash bundle's goodput table names {at_kill!r} at kill "
                "time, expected 'step' (the failpoint fired mid-step)")]
        if not gp.get("buckets", {}).get("step", 0.0) > 0.0:
            return [_finding(name, "error",
                             "crash bundle's goodput breakdown books no "
                             "step seconds before the kill")]
    finally:
        fp.reset()
        paddle.set_flags(old)
        perfledger.reset_ledger()
        goodput.reset()
        bb.quiesce()
        bb.reset()
        if not was_enabled:
            bb.disable()
        tmp_ctx.cleanup()
    return [_ok(name,
                f"killed dp8 run booked its recovery "
                f"(resume_backoff={row['buckets']['resume_backoff']:.3f}s,"
                f" ckpt_restore={row['buckets']['ckpt_restore']:.3f}s, "
                f"reshard={row['buckets']['reshard']:.3f}s; buckets sum "
                f"within 10% of {row['wall_s']:.3f}s wall); goodput "
                f"{row['goodput']:.3f} < twin {twin_row['goodput']:.3f}, "
                "crash bundle names bucket 'step' at kill time")]


def _check_stage_replace():
    """Chaos-injected stage death: kill one stage of a FLAGS_mpmd
    2-stage pipeline via stage/run, rebind JUST that stage onto a
    replacement mesh (replace_stage), and keep training — the stage's
    own programs must be rebuilt, siblings' compiled programs untouched
    (object identity); losses stay at parity with an uninterrupted
    twin."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import flags, monitor
    from paddle_tpu.distributed.mesh import build_mesh
    from paddle_tpu.distributed.pipeline import PipelineTrainer
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.testing import failpoints as fp

    name = "stage_replace"
    old = {k: flags.get_flag(k) for k in ("mpmd", "elastic")}
    paddle.set_flags({"mpmd": True, "elastic": True})
    try:
        cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                        num_heads=2, max_seq_len=16, dropout=0.0)
        rng = np.random.RandomState(0)
        batches = [[rng.randint(0, 64, (2, 16)).astype(np.int32)
                    for _ in range(2)] for _ in range(4)]

        def build():
            paddle.seed(0)
            model = GPTForCausalLM(cfg)
            pre, stages, post = model.pipeline_split(2)
            opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                         parameters=model.parameters())
            mesh = build_mesh((2,), ("pp",), devices=jax.devices()[:2])
            return PipelineTrainer(pre, stages, post, opt, mesh=mesh,
                                   n_micro=2, schedule_mode="1F1B")

        twin = build()
        twin_losses = [float(np.asarray(twin.train_step(*b)._data))
                       for b in batches]

        tr = build()
        losses = [float(np.asarray(tr.train_step(*b)._data))
                  for b in batches[:2]]
        runner = tr._mpmd_runner
        jits = {n: p._jit for n, p in runner.programs.items()}
        fp.arm("stage/run", "error:1")
        try:
            tr.train_step(*batches[2])
            return [_finding(name, "error",
                             "armed stage/run failpoint did not fire")]
        except fp.FailpointError:
            pass
        # stage 0's slice died: rebind fwd0/bwd0 onto a replacement
        # device
        replacement = build_mesh((1,), ("stage",),
                                 devices=[jax.devices()[2]])
        runner.replace_stage(0, replacement)
        losses += [float(np.asarray(tr.train_step(*b)._data))
                   for b in batches[2:]]

        drift = max(abs(a - b) for a, b in zip(losses, twin_losses))
        if not np.allclose(losses, twin_losses, rtol=1e-5, atol=1e-5):
            return [_finding(
                name, "error",
                f"post-replace loss trajectory diverged from the "
                f"uninterrupted twin (max |diff|={drift:.3e})")]
        rebuilt = sorted(n for n, j in jits.items()
                         if runner.programs[n]._jit is not j)
        if rebuilt != ["bwd0", "fwd0"]:
            return [_finding(name, "error",
                             "replace_stage must rebuild stage 0's "
                             "programs (bwd0, fwd0) and no sibling's; "
                             f"rebuilt: {rebuilt}")]
        if runner.stage_meshes[0] is not replacement:
            return [_finding(name, "error",
                             "replace_stage did not record the "
                             "replacement mesh")]
        snap = monitor.snapshot()
        moved = [s for m in snap["metrics"]
                 if m["name"] == "elastic_resume_total"
                 for s in m["series"]
                 if s["labels"].get("reason") == "stage_replace"
                 and s["value"] > 0]
        if not moved:
            return [_finding(name, "error",
                             "elastic_resume_total{reason=stage_replace} "
                             "did not move")]
    finally:
        fp.reset()
        paddle.set_flags(old)
    return [_ok(name,
                f"killed stage 0 rebound onto a replacement mesh "
                f"(its programs rebuilt, siblings untouched); loss "
                f"parity with the twin (max drift {drift:.1e})")]


def build_report(only=None):
    """Run the fault schedule; `only` restricts to a subset of PASSES
    (the model is only built when a serving check is selected)."""
    selected = set(only) if only else set(PASSES)
    unknown = selected - set(PASSES)
    if unknown:
        raise ValueError(f"unknown chaos pass(es) {sorted(unknown)}; "
                         f"known: {PASSES}")
    findings = []
    checks = [
        ("ckpt_atomic", _check_ckpt_atomic),
        ("ckpt_fallback", _check_ckpt_fallback),
        ("trainer_nonfinite", _check_trainer_nonfinite),
        ("numerics_anomaly", _check_numerics_anomaly),
        ("quantized_nonfinite", _check_quantized_nonfinite),
        ("async_nonfinite", _check_async_nonfinite),
        ("elastic_resume", _check_elastic_resume),
        ("stage_replace", _check_stage_replace),
        ("goodput_attribution", _check_goodput_attribution),
    ]
    if selected & {"serving_deadline", "serving_slot_error",
                   "serving_shed", "router_failover", "stall_dump",
                   "stage_backpressure", "adapter_evict_under_load",
                   "page_pool_full"}:
        m = _tiny_model()
        checks += [
            ("serving_deadline", lambda: _check_serving_deadline(m)),
            ("serving_slot_error", lambda: _check_serving_slot_error(m)),
            ("serving_shed", lambda: _check_serving_shed(m)),
            ("router_failover", lambda: _check_router_failover(m)),
            ("stall_dump", lambda: _check_stall_dump(m)),
            ("stage_backpressure",
             lambda: _check_stage_backpressure(m)),
            ("adapter_evict_under_load",
             lambda: _check_adapter_evict_under_load(m)),
            ("page_pool_full", lambda: _check_page_pool_full(m)),
        ]
    for name, fn in checks:
        if name not in selected:
            continue
        try:
            findings.extend(fn())
        except Exception as e:   # a crashed check IS a failed recovery path
            findings.append(_finding(
                name, "error",
                f"check crashed: {type(e).__name__}: {e}"))
    counts = {"error": 0, "warning": 0, "info": 0}
    for f in findings:
        counts[f["severity"]] = counts.get(f["severity"], 0) + 1
    return {
        "tool": "chaos_check",
        "passes": PASSES,
        "targets": {"chaos": {"name": "chaos", "counts": counts,
                              "findings": findings}},
        "totals": dict(counts),
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit the machine-readable report")
    ap.add_argument("--only", action="append", choices=PASSES,
                    help="run only this check (repeatable)")
    args = ap.parse_args(argv)

    from paddle_tpu.testing import failpoints as fp

    fp.reset()   # a canned schedule must start from a clean slate
    try:
        report = build_report(only=args.only)
    finally:
        fp.reset()
    if args.as_json:
        print(json.dumps(report, indent=1))
    else:
        for f in report["targets"]["chaos"]["findings"]:
            print(f"  [{f['severity']}] {f['pass']}: {f['message']}")
        t = report["totals"]
        print(f"total: {t['error']} error(s), {t['info']} recovery "
              f"path(s) verified")
    return 1 if report["totals"]["error"] else 0


if __name__ == "__main__":
    sys.exit(main())
