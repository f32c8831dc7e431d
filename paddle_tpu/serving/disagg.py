"""Prefill/decode disaggregation: dedicated prefill workers feed decode
engines through the DecodeModel cache-pytree handoff.

The monolithic ``ServingEngine`` runs admission prefill and the decode
loop on the same program family; at scale the two want DIFFERENT
placement — prefill is compute-bound and bursty, decode is HBM-bound and
steady (the per-stage multi-program split MPMD pipeline parallelism
argues for, PAPERS.md arXiv:2412.14374). ``DisaggregatedPool`` is that
split in-process:

- ``PrefillWorker`` builds ONLY the bucketed whole-prompt prefill program
  from a model's :class:`~paddle_tpu.serving.decode_model.DecodeModel`
  adapter and turns a prompt into ``((kc1, vc1), last_logits)`` — one
  single-row KV cache in the adapter's documented cache-pytree layout;
- the pool hands that row to the least-loaded decode engine via
  ``ServingEngine.admit_prefilled`` — a ``kv_handoff`` span and the
  ``kv_handoff_bytes_total`` metric meter every transfer;
- the decode engine picks the first token through the SAME pick program
  monolithic admission uses, so pool completions are **bit-identical** to
  a single engine serving the same prompts (tests/test_serving_disagg.py).

Workers and engines must share adapter/config/dtype/cache_dtype — the
pool constructor builds both sides from one model so the contract holds
by construction.
"""
import time

import numpy as np

from .. import flags as _flags
from .. import monitor as _monitor
from ..monitor import blackbox_lazy as _blackbox  # import-free recorder facade (ISSUE 12)
from .. import trace as _trace
from ..core.tensor import Tensor
from ..framework import aot as _aot
from . import decode_model as _dm_registry

__all__ = ["PrefillWorker", "DisaggregatedPool", "HANDOFF_SCHEMA"]

#: The prefill->decode KV transfer edge, declared (ISSUE 13; docs/
#: ANALYSIS.md "Declaring a transfer edge"). This literal is the ONE
#: source of truth for the handoff payload: the static auditor
#: (analysis/handoff_schema.py) AST-extracts it and pins its fingerprint
#: in tests/handoff_baseline.json, and ``ServingEngine.admit_prefilled``
#: validates every incoming row against it at runtime — a silent
#: KV-layout drift fails lint AND raises at the door, never corrupts a
#: decode. Symbolic dims bind to the consuming engine's config (L =
#: num_layers, KVh = compact kv heads, T = max_seq_len, hd = head_dim,
#: V = vocab); ``$cache`` binds to the engine's cache dtype;
#: ``quantizable`` sides accept the int8/fp8 (values, scales) pair.
HANDOFF_SCHEMA = {
    "edge": "disagg_kv",
    "producer": "paddle_tpu/serving/disagg.py::PrefillWorker.prefill",
    "consumer": ("paddle_tpu/inference/serving.py::"
                 "ServingEngine.admit_prefilled"),
    "runtime_checked": True,
    "doc": "one prefilled single-row KV cache pair + the prompt's "
           "last-position vocab logits, in the DecodeModel adapter's "
           "documented cache-pytree layout",
    "payload": {
        "kc": {"shape": ("L", 1, "KVh", "T", "hd"), "dtype": "$cache",
               "layout": "[L, B, KVh, T, hd]", "quantizable": True},
        "vc": {"shape": ("L", 1, "KVh", "T", "hd"), "dtype": "$cache",
               "layout": "[L, B, KVh, T, hd]", "quantizable": True},
        "logits": {"shape": ("V",), "dtype": "float32"},
    },
}

_KV_BYTES = _monitor.counter(
    "kv_handoff_bytes_total",
    "bytes of prefilled KV rows handed from prefill workers to decode "
    "engines (disaggregated serving)")
_KV_HANDOFFS = _monitor.counter(
    "kv_handoff_total",
    "prefill->decode handoffs, by outcome",
    labelnames=("event",))


class PrefillWorker:
    """The prefill half of a disaggregated pair: owns the model params
    and ONE program — bucketed whole-prompt prefill — built through the
    DecodeModel adapter exactly like ``ServingEngine``'s, so the row it
    produces is the row the engine would have produced itself."""

    def __init__(self, model, dtype=None, cache_dtype=None,
                 prompt_buckets=(32, 64, 128, 256, 512, 1024),
                 decode_model=None):
        import jax
        import jax.numpy as jnp

        dm = _dm_registry.resolve(model, decode_model)
        self._dm = dm
        cfg = model.cfg
        dm.check_config(cfg)
        self.cfg = cfg
        self.T = cfg.max_seq_len
        self._buckets = tuple(sorted(b for b in prompt_buckets
                                     if b <= self.T))
        if not self._buckets:
            raise ValueError("no prompt bucket fits max_seq_len")
        params, aux = dm.extract_params(model, "the model")
        self._compute_dtype = dm.compute_dtype(dtype)
        if self._compute_dtype is not None:
            params = {k: (v.astype(self._compute_dtype)
                          if jnp.issubdtype(v.dtype, jnp.floating) else v)
                      for k, v in params.items()}
        self._params = params
        fwd, logits_of, cache_init = dm.decode_fns(cfg, aux,
                                                   cache_dtype=cache_dtype)
        cache_dt = self._compute_dtype or jnp.float32
        # fixed-size state comes back as it stood at true_len, as the
        # engine's own prefill returns it (docs/SERVING.md "State kinds")
        fixed_state = any(leaf["kind"] != "kv" for leaf in
                          _dm_registry.state_leaves(dm.cache_spec(cfg)))

        def prefill(p, ids_padded, true_len):
            kc1, vc1 = cache_init(1, self.T, cache_dt)
            x, kc1, vc1 = fwd(p, ids_padded, 0, kc1, vc1, **(
                {"valid_len": true_len} if fixed_state else {}))
            x_last = jax.lax.dynamic_slice_in_dim(
                x, true_len - 1, 1, axis=1)[:, 0]
            return kc1, vc1, logits_of(p, x_last).astype(jnp.float32)[0]

        # the same site/label family as the engine's prefill: one
        # telemetry series for both sides of the split
        self._prefill = _aot.cached_jit(
            prefill, site="serving", label="prefill",
            record_event="serving/compile")
        self._m = {"prefills": 0, "prefill_ms": 0.0}

    def _bucket(self, n):
        for b in self._buckets:
            if n <= b:
                return b
        return self.T

    def prefill(self, prompt_ids):
        """Prefill one prompt; returns ``((kc1, vc1), logits)`` — the
        handoff unit ``ServingEngine.admit_prefilled`` consumes."""
        import jax.numpy as jnp

        ids = prompt_ids._data if isinstance(prompt_ids, Tensor) \
            else np.asarray(prompt_ids)
        ids = np.asarray(ids, np.int32).ravel()
        if len(ids) == 0:
            raise ValueError("empty prompt")
        if len(ids) + 1 > self.T:
            raise ValueError(
                f"prompt ({len(ids)}) too long for max_seq_len {self.T}")
        n = len(ids)
        pb = self._bucket(n)
        padded = np.zeros((1, pb), np.int32)
        padded[0, :n] = ids
        t0 = time.perf_counter()
        with _blackbox.progress("disagg/prefill"):
            kc1, vc1, logits = self._prefill(self._params,
                                             jnp.asarray(padded),
                                             np.int32(n))
        self._m["prefills"] += 1
        self._m["prefill_ms"] += (time.perf_counter() - t0) * 1e3
        return (kc1, vc1), logits

    def stats(self):
        return dict(self._m)


class DisaggregatedPool:
    """N prefill workers + M decode engines behind one submit()/step()
    surface with the monolithic engine's semantics (and bit-identical
    outputs on the same prompts)."""

    def __init__(self, model, prefill_workers=1, decode_engines=2,
                 max_batch=4, dtype=None, cache_dtype=None,
                 eos_token_id=None,
                 prompt_buckets=(32, 64, 128, 256, 512, 1024),
                 max_queue=None, decode_model=None, compress=None):
        from ..inference.serving import ServingEngine

        if int(prefill_workers) < 1 or int(decode_engines) < 1:
            raise ValueError("the pool needs >= 1 prefill worker and "
                             ">= 1 decode engine")
        # MPMD stage edge (distributed/stage.py): FLAGS_mpmd is consumed
        # HERE — armed, the prefill->decode hand-off travels a typed
        # StageEdge validating this module's HANDOFF_SCHEMA (compress=8
        # rides the int8 row codec); a post-construction toggle raises
        # (_mpmd_active). Unset, the module is never imported and the
        # hand-off below is byte-identical to the pre-PR pool.
        self._mpmd = bool(_flags.get_flag("mpmd", False))
        self._edge = None
        self._backpressure_excs = ()
        if compress is not None and not self._mpmd:
            raise ValueError(
                "compress quantizes the prefill->decode stage edge "
                "(distributed/stage.py) — set FLAGS_mpmd before "
                "constructing the pool")
        if self._mpmd:
            from ..distributed import stage as _stage_mod

            self._edge = _stage_mod.StageEdge(
                "disagg_kv", HANDOFF_SCHEMA,
                capacity=int(decode_engines) * int(max_batch),
                compress=compress)
            self._backpressure_excs = (_stage_mod.EdgeFullError,)
        shared = dict(dtype=dtype, cache_dtype=cache_dtype,
                      prompt_buckets=prompt_buckets,
                      decode_model=decode_model)
        self.workers = [PrefillWorker(model, **shared)
                        for _ in range(int(prefill_workers))]
        self.engines = {
            f"decode{i}": ServingEngine(model, max_batch=max_batch,
                                        eos_token_id=eos_token_id,
                                        max_queue=max_queue, **shared)
            for i in range(int(decode_engines))}
        self.T = model.cfg.max_seq_len
        self._pending = []   # (rid, ids, kwargs, t0) awaiting prefill
        self._placed = {}        # rid -> (engine_name, erid)
        self._by_erid = {}       # (engine_name, erid) -> rid, LIVE only
        self._results = {}       # rid -> finished Request
        self._next_rid = 0
        self._next_worker = 0
        self._m = {"submitted": 0, "handoffs": 0, "handoff_bytes": 0,
                   "per_engine": {}}

    def _mpmd_active(self):
        """FLAGS_mpmd was consumed at construction (the stage edge is
        built then); a post-construction toggle is loud instead of
        silently re-routing the hand-off. One get_flag + compare when
        disarmed."""
        m = bool(_flags.get_flag("mpmd", False))
        if m != self._mpmd:
            raise RuntimeError(
                "FLAGS_mpmd changed after this DisaggregatedPool was "
                "constructed; the prefill->decode stage edge is built at "
                "__init__ — build a new pool under the new flag value")
        return self._mpmd

    def submit(self, prompt_ids, max_new_tokens=32, **kwargs):
        """Queue one prompt; returns the pool request id. kwargs pass
        through to ``ServingEngine.admit_prefilled`` (temperature, top_k,
        top_p, seed, deadline_ms, priority)."""
        ids = prompt_ids._data if isinstance(prompt_ids, Tensor) \
            else np.asarray(prompt_ids)
        ids = np.asarray(ids, np.int32).ravel()
        if len(ids) == 0:
            raise ValueError("empty prompt")
        if len(ids) + 1 > self.T:
            raise ValueError(
                f"prompt ({len(ids)}) too long for max_seq_len {self.T}")
        # fail-fast with the ENGINE's own validation: a bad argument that
        # only surfaced at handoff time would re-raise from every step()
        # and head-of-line block the prefill queue forever
        next(iter(self.engines.values()))._validate_decode_args(
            ids, max_new_tokens, kwargs.get("temperature", 0.0),
            kwargs.get("deadline_ms"), kwargs.get("top_k"),
            kwargs.get("top_p"), kwargs.get("seed"))
        rid = self._next_rid
        self._next_rid += 1
        # t0 anchors deadline_ms at POOL submit: time spent waiting in
        # the prefill backlog counts against the budget, matching the
        # monolithic engine's submit-to-finish deadline semantics
        self._pending.append((rid, ids,
                              dict(kwargs, max_new_tokens=max_new_tokens),
                              time.perf_counter()))
        self._m["submitted"] += 1
        return rid

    def _free_slots(self, name):
        """Admission room on a decode engine: free decode slots minus the
        handoff backlog, capped by the engine's bounded-queue headroom —
        prefilling a prompt the engine would reject (QueueFullError)
        wastes the whole forward."""
        eng = self.engines[name]
        h = eng.health()
        free = eng.B - h["active_slots"] - len(eng._handoff)
        if eng._max_queue is not None:
            free = min(free, eng._max_queue - len(eng._queue)
                       - len(eng._handoff))
        return free

    def _target_engine(self):
        """Least-loaded decode engine by free slot count (ties broken by
        name order — deterministic placement)."""
        return max(sorted(self.engines),
                   key=lambda n: (self._free_slots(n),))

    def _advance_prefill(self):
        """Prefill pending prompts (round-robin over workers) while any
        decode engine has room, handing each finished row off."""
        if not self._pending:
            return
        # window beacon: the site is watched only while handoffs are in
        # flight (per-iteration beats inside keep the counter advancing)
        with _blackbox.progress("disagg/handoff"):
            self._advance_prefill_inner()

    def _advance_prefill_inner(self):
        while self._pending:
            _blackbox.beacon("disagg/handoff")
            name = self._target_engine()
            if self._free_slots(name) <= 0:
                return   # decode tier full: natural backpressure
            rid, ids, kwargs, t0 = self._pending.pop(0)
            eng_kwargs = kwargs
            if kwargs.get("deadline_ms") is not None:
                # hand the engine the REMAINING budget: prefill-backlog
                # wait already spent it (an exhausted budget still
                # submits with an epsilon — the engine's own deadline
                # machinery expires it with reason="deadline"). The
                # UN-adjusted kwargs go back on the queue if the handoff
                # fails, so a retry re-derives from the original budget.
                elapsed_ms = (time.perf_counter() - t0) * 1e3
                eng_kwargs = dict(kwargs, deadline_ms=max(
                    1e-3, kwargs["deadline_ms"] - elapsed_ms))
            worker = self.workers[self._next_worker % len(self.workers)]
            self._next_worker += 1
            eng = self.engines[name]
            tid = _trace.new_trace_id() if _trace.is_enabled() else None
            sp = None if tid is None else _trace.start_span(
                "kv_handoff", subsystem="serving", trace_id=tid,
                rid=rid, engine=name, prompt_tokens=int(len(ids)))
            try:
                kv_row, logits = worker.prefill(ids)
                if self._edge is not None:
                    # MPMD routing: the row crosses a typed StageEdge —
                    # validated against HANDOFF_SCHEMA, quantized when
                    # the edge compresses, metered (wire bytes) at the
                    # edge's own kv_handoff_bytes_total chokepoint
                    kc1, vc1 = kv_row
                    nbytes = self._edge.put(
                        {"kc": kc1, "vc": vc1, "logits": logits},
                        dtypes={"cache": str(kc1.dtype)})
                    payload = self._edge.get()
                    kv_row = (payload["kc"], payload["vc"])
                    logits = payload["logits"]
                else:
                    nbytes = _dm_registry.cache_row_bytes(kv_row)
                erid = eng.admit_prefilled(ids, kv_row, logits,
                                           trace_id=tid, parent_span=sp,
                                           **eng_kwargs)
            except BaseException as exc:
                # the popped request must not vanish with the failed
                # handoff: put it back at the head
                self._pending.insert(0, (rid, ids, kwargs, t0))
                if sp is not None:
                    sp.end(error=True)
                from ..inference.serving import QueueFullError

                if isinstance(exc,
                              (QueueFullError,) + self._backpressure_excs):
                    # a bounded decode engine (or a full stage edge) at
                    # capacity is BACKPRESSURE (same as no free slots),
                    # not a pool failure — retry the handoff later
                    return
                _KV_HANDOFFS.labels(event="error").inc()
                raise
            if sp is not None:
                sp.end(bytes=nbytes)
            if self._edge is None:
                _KV_BYTES.inc(nbytes)   # armed: the edge already metered
            _KV_HANDOFFS.labels(event="ok").inc()
            self._m["handoffs"] += 1
            self._m["handoff_bytes"] += nbytes
            self._m["per_engine"][name] = \
                self._m["per_engine"].get(name, 0) + 1
            self._placed[rid] = (name, erid)
            self._by_erid[(name, erid)] = rid

    def step(self):
        """Advance prefill handoffs, then one decode step per engine.
        Returns the pool requests finished this step as {rid: Request}."""
        self._mpmd_active()
        self._advance_prefill()
        done = {}
        for name, eng in self.engines.items():
            if not eng.has_work():
                continue
            for ereq in eng.step():
                # pop: _by_erid holds LIVE placements only, so per-step
                # cost tracks in-flight work, not pool lifetime
                rid = self._by_erid.pop((name, ereq.rid), None)
                if rid is not None:
                    self._results[rid] = ereq
                    done[rid] = ereq
        return done

    def get_request(self, rid):
        if rid in self._results:
            return self._results[rid]
        if rid in self._placed:
            name, erid = self._placed[rid]
            return self.engines[name].get_request(erid)
        for p_rid, ids, kwargs, t0 in self._pending:
            if p_rid == rid:
                raise KeyError(
                    f"request {rid} is still awaiting prefill (no Request "
                    "object exists until handoff)")
        raise KeyError(f"unknown pool request id {rid}")

    def has_work(self):
        return bool(self._pending) or any(e.has_work()
                                          for e in self.engines.values())

    def run_until_complete(self, max_steps=100_000):
        """Drain the pool; returns {rid: finished Request}."""
        steps = 0
        while self.has_work():
            self.step()
            steps += 1
            if steps > max_steps:
                msg = (f"disaggregated pool did not converge within "
                       f"{max_steps} steps")
                if _blackbox.is_enabled():
                    path = _blackbox.dump(
                        "stall", site="disagg/handoff",
                        extra={"trigger": "run_until_complete",
                               "max_steps": max_steps,
                               "pending": len(self._pending)})
                    if path:
                        msg += f"; blackbox dump bundle: {path}"
                raise RuntimeError(msg)
        return dict(self._results)

    def stats(self):
        """Pool-level handoff accounting + each side's own stats."""
        out = {
            "pool": dict(self._m, pending=len(self._pending)),
            "workers": [w.stats() for w in self.workers],
            "engines": {n: e.stats() for n, e in self.engines.items()},
        }
        if self._edge is not None:
            out["edge"] = dict(self._edge.stats)
        return out
