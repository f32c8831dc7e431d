"""Continuous-batching serving engine (beyond the reference).

The reference serves LMs request-at-a-time through its predictor; modern
LLM serving interleaves requests so a long generation never blocks a short
one. This engine is that recipe, TPU-shaped:

- a FIXED [max_batch, max_seq] KV cache (static shapes — one compiled
  decode program, ever);
- each slot carries its own sequence position: the decode step runs the
  whole batch with PER-ROW positions and per-row cache columns
  (models/gpt.py _decode_fns grew a vectorized-pos path for this);
- admission prefills a new prompt into a fresh single-row cache (prompt
  right-padded to a length bucket, so prefill compiles once per bucket)
  and copies that row into the big cache — one row copy per admission,
  nothing per step;
- right-pad junk in the prefill is never read: it sits at columns the
  causal mask hides until the decode loop OVERWRITES them (the store runs
  before attention each step);
- finished slots (eos / max_new_tokens / capacity) free immediately and
  the next queued request takes the slot on the following step() —
  continuous batching, not static batching;
- one decode step is kept IN FLIGHT (dense engines without a draft
  model; docs/SERVING.md "The decode loop"): the token vector lives on
  the device, step N+1 is dispatched from what step N leaves there, and
  the host reads step N's tokens while N+1 runs. Token streams do not
  change by it; a token may show one step() call later.

Per-request decoding knobs: temperature=0 (default) is greedy with EXACT
parity vs a solo `model.generate(temperature=0)` (asserted in tests);
temperature>0 samples from the (optionally top_k-truncated) distribution
with a deterministic per-request PRNG stream, without disturbing greedy
neighbors — an all-greedy batch dispatches to a lean argmax-only compiled
step. Composes with bf16 serving params/cache (dtype="bfloat16") and the
int8 KV cache (cache_dtype="int8").

`prefill_chunk=C` enables CHUNKED prefill: a long prompt is consumed C
tokens per step() with decode steps for active slots running in between,
so an arriving 1024-token prompt stalls inter-token latency by one chunk's
compute, not one full prefill (the whole-prompt path remains the default;
outputs are identical either way — asserted in tests). ONE slot prefills at
a time and a round carries ONE chunk: the slot holds a one-row side cache
until its last chunk, and the queue's head waits for it, so a burst of
arrivals neither multiplies the stall nor holds a side row an arrival.

`register_prefix(ids)` caches a shared prefix's KV ONCE (system prompts):
requests submitted with `prefix_id=` start from a copy of that cache and
prefill only their suffix — identical outputs to resending the full
prompt, without recomputing the prefix per request.

Robustness (docs/ROBUSTNESS.md): per-request `deadline_ms` finishes an
overdue request with reason="deadline" while batch-mates continue;
`cancel(rid)` evicts a queued or in-flight request; `max_queue=` bounds
the admission queue — a full queue rejects (`QueueFullError`) or, when the
incoming request outranks a queued one, load-sheds the lowest-priority
entry (reason="shed", `request_shed_total{reason}`); per-slot host-side
failures are ISOLATED (the failing slot finishes with reason="error" and
is evicted, the rest of the batch continues); `health()` reports
ok/degraded/draining and `drain()` stops admission for graceful shutdown.
A non-converging `run_until_complete` fails its in-flight requests with
reason="engine_stalled" instead of leaving them dangling.

`draft_model=` turns on SPECULATIVE continuous batching (the batched form
of `generate_speculative`): each round a small draft proposes `spec_k`
tokens per slot and the target verifies all slots in ONE (spec_k+1)-token
forward at per-slot positions, emitting 1..spec_k+1 tokens per slot per
round — output bit-identical to plain greedy. Rounds run while every
active slot is greedy with cache headroom; sampling neighbors or
near-capacity slots fall back to exact single-token steps. Composes with
chunked prefill, shared prefixes, bf16/int8 caches, and tp_mesh (the
draft stays replicated; the target verify shares the head-sharded cache).

Multi-engine tier (docs/SERVING.md): the engine is MODEL-AGNOSTIC — all
model-specific decode math arrives through the DecodeModel adapter
resolved from `paddle_tpu.serving.decode_model` (gpt registers itself;
`decode_model=` picks explicitly). `submit(trace_id=, parent_span=)`
lets a fronting `serving.Router` thread its placement span into the
request's trace, and `admit_prefilled()` accepts a KV row prefilled by a
`serving.PrefillWorker` — the prefill/decode disaggregation handoff,
bit-identical to local admission.
"""
import collections
import functools
import time

import numpy as np

from .. import flags as _flags
from .. import monitor as _monitor
from ..monitor import blackbox_lazy as _blackbox  # import-free recorder facade (ISSUE 12)
from ..trace import costs as _costs
from .. import trace as _trace
from ..core.tensor import Tensor
from ..framework import aot as _aot
from ..framework import lineage as _lineage
from ..serving import decode_model as _dm_registry
from ..testing import failpoints as _fp

__all__ = ["ServingEngine", "Request", "QueueFullError"]


class QueueFullError(RuntimeError):
    """submit() rejected: the bounded admission queue is full and the
    request's priority does not outrank any queued entry."""


class _AdapterUnavailable(RuntimeError):
    """Paged admission found the request's adapter not loaded (evicted
    mid-flight): requeue-at-head backpressure, exactly like
    ``PagePoolFullError`` — never a reason='error' finish. The request
    re-admits, and regenerates bit-identically, once the adapter is
    loaded again."""

# engine metrics in the default registry (every engine in the process
# shares them; per-engine views live on ServingEngine.stats())
_REQ_SUBMITTED = _monitor.counter(
    "serving_requests_submitted_total", "requests accepted by submit()")
_REQ_FINISHED = _monitor.counter(
    "serving_requests_finished_total",
    "finished requests by reason (eos|length|capacity)",
    labelnames=("reason",))
_TOKENS = _monitor.counter(
    "serving_tokens_total", "generated tokens across all requests")
_QUEUE_WAIT_MS = _monitor.histogram(
    "serving_queue_wait_ms", "submit() -> admission start wait")
_TTFT_MS = _monitor.histogram(
    "serving_ttft_ms", "submit() -> first generated token")
_ITL_MS = _monitor.histogram(
    "serving_inter_token_ms",
    "gap between consecutive generated tokens of one request (a "
    "speculative round lands its accepted run at once: near-zero gaps)")
_STEPS = _monitor.counter(
    "serving_steps_total",
    "engine step slices by kind "
    "(decode_greedy|decode_sample|prefill_chunk|speculative)",
    labelnames=("kind",))
_OCCUPANCY = _monitor.gauge(
    "serving_batch_occupancy", "active decode slots at the last step()")
_PREFIX = _monitor.counter(
    "serving_prefix_cache_total",
    "prefix-reuse admissions: hit = suffix-only prefill from cached KV, "
    "miss = a prefix_id request that fell back to whole-prompt prefill",
    labelnames=("event",))
_SPEC = _monitor.counter(
    "serving_spec_tokens_total",
    "speculative decoding draft tokens (proposed vs accepted)",
    labelnames=("event",))
_SHED = _monitor.counter(
    "request_shed_total",
    "load-shedding on the bounded admission queue (queue_full = incoming "
    "request rejected with QueueFullError; preempted = a lower-priority "
    "queued request was finished with reason='shed' to admit a higher-"
    "priority one)",
    labelnames=("reason",))
_DEADLINE = _monitor.counter(
    "request_deadline_exceeded_total",
    "requests finished with reason='deadline' (per-request deadline_ms "
    "elapsed before completion)")


# the lookahead loop's decode step dispatched and not read yet: its device
# tokens, the [(slot, request)] it was dispatched for, its kind, and the
# `serve/decode_dispatch` phase it was dispatched in
_Flight = collections.namedtuple("_Flight", "toks rows kind disp counts")


class _MsSummary:
    """O(1) per-request/per-engine latency accumulator for stats()."""

    __slots__ = ("count", "sum", "min", "max")

    def __init__(self):
        self.count = 0
        self.sum = 0.0
        self.min = None
        self.max = None

    def add(self, v):
        self.count += 1
        self.sum += v
        self.min = v if self.min is None else min(self.min, v)
        self.max = v if self.max is None else max(self.max, v)

    def to_dict(self):
        return {"count": self.count, "sum_ms": self.sum,
                "avg_ms": self.sum / self.count if self.count else 0.0,
                "min_ms": self.min or 0.0, "max_ms": self.max or 0.0}


class Request:
    """One submitted prompt and, when finished, its generated tokens.
    Lifecycle timestamps (perf_counter seconds) are stamped by the engine;
    ``stats()`` is the per-request observability view."""

    def __init__(self, rid, prompt_ids, max_new_tokens, temperature=0.0,
                 top_k=None, top_p=None, seed=None, prefix_id=None,
                 prefix_len=0, deadline_ms=None, priority=0, adapter=None):
        self.rid = rid
        self.prompt_ids = np.asarray(prompt_ids, np.int32).ravel()
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = float(temperature)
        self.top_k = top_k
        self.top_p = top_p
        self.seed = rid if seed is None else int(seed)
        self.prefix_id = prefix_id          # registered shared prefix, or
        self.prefix_len = int(prefix_len)   # 0 = no prefix reuse
        self.adapter = adapter    # loaded LoRA adapter name (paged engines)
        self.deadline_ms = deadline_ms      # None = no deadline
        self.priority = int(priority)       # higher outranks on a full queue
        self.output_ids = []          # generated tokens (no prompt echo)
        # tracing (FLAGS_trace): one trace_id per request; the root span
        # lives submit() -> finish reason, queue_wait is its first child
        self.trace_id = None
        self._span = None
        self._qspan = None
        self.finished = False
        # "eos" | "length" | "capacity" | "deadline" | "error" |
        # "cancelled" | "shed" | "engine_stalled"
        self.finish_reason = None
        self.submit_time = None       # stamped by ServingEngine.submit
        self.admit_time = None        # admission start (queue wait ends)
        self.first_token_time = None
        self.last_token_time = None
        self.finish_time = None
        self._inter_token = _MsSummary()
        # weight lineage (framework/lineage.py, ISSUE 20): the engine
        # stamps at submission which weight (and adapter) version this
        # session decodes under — a hot_swap mid-stream leaves the
        # session on its pre-swap stamp, which _finish_req counts as a
        # stale finish (serving_stale_sessions_total, FLAGS_goodput)
        self.weight_version = None
        self.adapter_version = None

    @property
    def tokens(self):
        return np.asarray(self.output_ids, np.int32)

    def _note_token(self, now):
        """Record one emitted token; returns the inter-token gap in ms
        (None for the first token)."""
        gap = None
        if self.first_token_time is None:
            self.first_token_time = now
        else:
            gap = (now - self.last_token_time) * 1e3
            self._inter_token.add(gap)
        self.last_token_time = now
        return gap

    def stats(self):
        """Per-request latency/throughput stats (ms), live at any point of
        the lifecycle — the latency-tracker surface get_request promises."""
        out = {"rid": self.rid, "finished": self.finished,
               "trace_id": self.trace_id,   # joins req stats to its spans
               "finish_reason": self.finish_reason,
               "prompt_tokens": int(len(self.prompt_ids)),
               "prefix_tokens": self.prefix_len,
               "new_tokens": len(self.output_ids)}
        if self.weight_version is not None:
            out["weight_version"] = str(self.weight_version)
        if self.adapter_version is not None:
            out["adapter_version"] = str(self.adapter_version)
        if self.submit_time is not None and self.admit_time is not None:
            out["queue_wait_ms"] = (self.admit_time - self.submit_time) * 1e3
        if self.submit_time is not None \
                and self.first_token_time is not None:
            out["ttft_ms"] = (self.first_token_time
                              - self.submit_time) * 1e3
        out["inter_token"] = self._inter_token.to_dict()
        if self.first_token_time is not None \
                and self.last_token_time is not None \
                and len(self.output_ids) > 1:
            dt = self.last_token_time - self.first_token_time
            if dt > 0:
                out["decode_tokens_per_sec"] = \
                    (len(self.output_ids) - 1) / dt
        return out


def _blackbox_request_table(eng):
    """One engine's in-flight request table for a blackbox dump bundle:
    where every unfinished request lives and how far it got — the
    'which rids were mid-flight when it wedged' evidence."""
    running = [{"rid": r.rid, "slot": s, "pos": int(eng._pos[s]),
                "new_tokens": len(r.output_ids)}
               for s, r in enumerate(eng._slot_req)
               if r is not None and s not in eng._prefilling]
    table = {
        "slots": eng.B,
        "step_no": eng._step_no,
        "draining": eng._draining,
        "queued": [r.rid for r in eng._queue],
        "handoff": [e[0].rid for e in eng._handoff],
        "prefilling": {s: e[0].rid for s, e in eng._prefilling.items()},
        "running": running,
        # slot already free, last token on its way with the step in flight
        "unread": [r.rid for r in eng._unread()],
        "finished": len(eng._finished),
    }
    table["in_flight"] = sorted(
        set(table["queued"]) | set(table["handoff"])
        | set(table["prefilling"].values())
        | {r["rid"] for r in running} | set(table["unread"]))
    return table


class ServingEngine:
    def __init__(self, model, max_batch=4, dtype=None, cache_dtype=None,
                 eos_token_id=None, prompt_buckets=(32, 64, 128, 256, 512,
                                                    1024), tp_mesh=None,
                 prefill_chunk=None, draft_model=None, spec_k=4,
                 max_queue=None, decode_model=None, page_block=None,
                 page_blocks=None, max_adapters=None, lora_rank=None,
                 page_cold_steps=None):
        import jax
        import jax.numpy as jnp

        # the engine is model-agnostic: every model-specific decode entry
        # point (config check, param extraction, decode math, tp recipe)
        # comes through the DecodeModel adapter resolved here — never from
        # a model module's privates (docs/SERVING.md; lint-enforced by
        # analysis/source_lint.py private-model-import-in-serving)
        dm = _dm_registry.resolve(model, decode_model)
        self._dm = dm
        cfg = model.cfg
        dm.check_config(cfg)
        # the cache as the adapter describes it: every leaf's kind (`kv`
        # grows with the context and is written at `pos`; `recurrent` and
        # `conv` have a fixed size and are replaced every step), its slot
        # axis and its layers
        spec = dm.cache_spec(cfg)
        self._state_leaves = _dm_registry.state_leaves(spec)
        #: the adapter's cache is a tree of its own and no K/V pair of
        #: heads: a hand-off row is held to that tree, not to the pair's
        #: declared schema
        self._described = spec.get("kind") == "state_tree"
        self._fixed_state = any(leaf["kind"] != "kv"
                                for leaf in self._state_leaves)
        #: names of the counts a decode step of this family returns
        #: beside its tokens (device values, read with the tokens)
        self._count_names = tuple(getattr(dm, "step_counts", ()))
        # an engine other than the dense one is refused here, by name,
        # where the adapter declares it does not serve it (`not_served`:
        # a family whose `kv` leaves are all the paged pool could hold may
        # still have no pages of K/V heads to give it)
        not_served = getattr(dm, "not_served", None) or {}
        asked = [f"{what} ({not_served[key]})" for key, what, on in (
            ("paged_kv", "FLAGS_paged_kv",
             _flags.get_flag("paged_kv", False)),
            ("draft_model", "draft_model=", draft_model is not None),
            ("tp_mesh", "tp_mesh=", tp_mesh is not None),
            ("lora", "max_adapters= / lora_rank=",
             max_adapters is not None or lora_rank is not None),
            ("cache_dtype", "cache_dtype=", cache_dtype is not None))
            if on and key in not_served]
        if asked:
            raise ValueError(
                f"decode model {dm.name!r} is served by the dense engine "
                "with its lookahead loop; it does not compose with "
                + "; ".join(asked))
        self.cfg = cfg
        self.B = int(max_batch)
        self.T = cfg.max_seq_len
        self.eos = eos_token_id
        # argument validation FIRST — before any device allocation/compile
        # (cache_dtype is validated centrally by _decode_fns' _QUANT table)
        if prefill_chunk is not None:
            if not 1 <= int(prefill_chunk) <= self.T:
                raise ValueError(
                    f"prefill_chunk must be in [1, max_seq_len={self.T}], "
                    f"got {prefill_chunk}")
        if max_queue is not None and int(max_queue) < 1:
            raise ValueError(f"max_queue must be >= 1, got {max_queue}")
        self._max_queue = None if max_queue is None else int(max_queue)
        # paged KV + batched multi-LoRA serving (FLAGS_paged_kv, ISSUE 18).
        # STRUCTURAL and construction-consumed: _paged_active() raises on a
        # post-construction disarm.
        # Armed, the dense [max_batch, max_seq] cache is replaced by a
        # physical block pool + per-slot block tables (serving/paging.py)
        # with whole-budget reservation at admission, refcounted prefix
        # sharing, int8 cold pages, and per-request adapter deltas batched
        # inside the one jitted decode step.
        _paged = bool(_flags.get_flag("paged_kv", False))
        self._paged = _paged
        _pg_set = sorted(k for k, v in (
            ("page_block", page_block), ("page_blocks", page_blocks),
            ("max_adapters", max_adapters), ("lora_rank", lora_rank),
            ("page_cold_steps", page_cold_steps)) if v is not None)
        if not _paged and _pg_set:
            raise ValueError(
                f"{', '.join(_pg_set)}= need FLAGS_paged_kv=1 — the paged "
                "engine is flag-gated (structural; consumed at engine "
                "construction)")
        if _paged:
            if tp_mesh is not None:
                raise ValueError(
                    "FLAGS_paged_kv does not compose with tp_mesh= serving:"
                    " the block pool is single-host state — serve tensor-"
                    "parallel engines dense")
            if draft_model is not None:
                raise ValueError(
                    "FLAGS_paged_kv does not compose with draft_model= "
                    "(speculative rounds write multi-token columns; the "
                    "paged scatter writes one frontier column per step)")
            if cache_dtype is not None:
                raise ValueError(
                    "FLAGS_paged_kv does not compose with cache_dtype=: "
                    "hot pages live at the compute dtype; the cold tier is "
                    "the pool's int8 page codec (page_cold_steps=)")
            if prefill_chunk is not None:
                raise ValueError(
                    "FLAGS_paged_kv does not compose with prefill_chunk= "
                    "(paged admission prefills whole prompts into blocks "
                    "reserved up front)")
        dm_d = None
        if draft_model is not None:
            dm_d = _dm_registry.resolve(draft_model, None)
            if draft_model.cfg.vocab_size != cfg.vocab_size:
                raise ValueError("draft and target must share a vocabulary")
            if not (1 <= int(spec_k) <= 16):
                raise ValueError(f"spec_k must be in [1, 16], got {spec_k}")
            if draft_model.cfg.max_seq_len < self.T:
                raise ValueError(
                    f"draft max_seq_len ({draft_model.cfg.max_seq_len}) "
                    f"must cover the target's ({self.T})")
            dm_d.check_config(draft_model.cfg)
        self._buckets = tuple(sorted(b for b in prompt_buckets
                                     if b <= self.T))
        if not self._buckets:
            raise ValueError("no prompt bucket fits max_seq_len")
        params, dm_aux = dm.extract_params(model, "the model")
        self._compute_dtype = dm.compute_dtype(dtype)
        if self._compute_dtype is not None:
            params = {k: (v.astype(self._compute_dtype)
                          if jnp.issubdtype(v.dtype, jnp.floating) else v)
                      for k, v in params.items()}
        # tensor-parallel serving: dense checkpoint Megatron-split over an
        # 'mp' mesh (same recipe as generate(tp_mesh=...)); the engine's
        # PERSISTENT KV cache lives head-sharded across the mesh
        tp_axis, tp_size, tp_specs = None, 1, None
        if tp_mesh is not None:
            tp_axis, tp_size, params, tp_specs = dm.tp_setup(tp_mesh, cfg,
                                                             params)
        self._tp_mesh = tp_mesh
        self._params = params
        fwd, logits_of, cache_init = dm.decode_fns(cfg, dm_aux,
                                                   cache_dtype=cache_dtype,
                                                   tp_axis=tp_axis,
                                                   tp_size=tp_size)
        cache_dt = self._compute_dtype or jnp.float32

        if _paged:
            # no dense [B, T] cache: physical K/V lives in the block pool;
            # each decode step gathers it through the block tables into the
            # exact dense layout fwd consumes, then scatters the frontier
            # column back (paged programs below)
            from ..serving import paging as _paging

            self._paging = _paging
            side = jax.eval_shape(lambda: cache_init(1, self.T, cache_dt))
            L, _, KVh, _, hd = side[0].shape
            bs_pg = 16 if page_block is None else int(page_block)
            if bs_pg < 1 or self.T % bs_pg:
                raise ValueError(
                    f"page_block must divide max_seq_len={self.T}, "
                    f"got {page_block}")
            maxb = self.T // bs_pg
            # default pool: every slot can hold a full-length session,
            # plus the permanent NULL frame — a ceiling, not a win; the
            # memory win comes from page_blocks= sized to the real
            # shared-prefix workload (tools/parity_check.py paged_kv)
            n_blocks = (self.B * maxb + 1 if page_blocks is None
                        else int(page_blocks))
            self._pool = _paging.PagePool(
                (int(L), int(KVh), int(hd)), cache_dt, bs_pg, n_blocks,
                self.B, self.T, cold_after=page_cold_steps)
            self._kc = self._vc = None
            n_ad = 8 if max_adapters is None else int(max_adapters)
            self._lora_rank = 8 if lora_rank is None else int(lora_rank)
            self._adapters = None
            self._lora = None
            if n_ad > 0:
                try:
                    # slot 0 is the permanent all-zero BASE adapter: base
                    # requests take the lora path with an exact-zero delta
                    self._lora = dm.lora_init(cfg, n_ad + 1,
                                              self._lora_rank,
                                              dtype=self._compute_dtype)
                    self._adapters = _paging.AdapterRegistry(n_ad)
                except NotImplementedError:
                    pass   # pool serves base-only; adapter APIs raise
            self._adapter_slot = np.zeros(self.B, np.int32)
        elif tp_mesh is None:
            self._kc, self._vc = cache_init(self.B, self.T, cache_dt)
        else:
            # allocate the GLOBAL cache (full KV heads) sharded on the
            # head axis, DIRECTLY into its sharding (no transient
            # single-device copy). The global layout comes from the DENSE
            # cache_init via eval_shape — one source of truth, so a cache
            # layout change in _decode_fns can't silently diverge here.
            from jax.sharding import NamedSharding, PartitionSpec as P

            dense_cache_init = dm.decode_fns(cfg, dm_aux,
                                             cache_dtype=cache_dtype)[2]
            tpl = jax.eval_shape(
                lambda: dense_cache_init(self.B, self.T, cache_dt))
            cache_spec = P(None, None, "mp", None, None)
            shard = NamedSharding(tp_mesh, cache_spec)
            alloc = jax.jit(
                lambda: jax.tree_util.tree_map(
                    lambda s: jnp.zeros(s.shape, s.dtype), tpl),
                out_shardings=jax.tree_util.tree_map(lambda s: shard, tpl))
            self._kc, self._vc = alloc()
            self._cache_spec = cache_spec
            # single-row SIDE caches (chunked prefill staging, shared
            # prefixes) use the same global-layout + head-sharded
            # allocation recipe as the big cache
            side_tpl = jax.eval_shape(
                lambda: dense_cache_init(1, self.T, cache_dt))
            side_alloc = jax.jit(
                jax.named_scope("cache/admit")(
                    lambda: jax.tree_util.tree_map(
                        lambda s: jnp.zeros(s.shape, s.dtype), side_tpl)),
                out_shardings=jax.tree_util.tree_map(
                    lambda s: shard, side_tpl))

        # bytes of state the engine holds, by kind, and what one decode
        # step has to move of them: a `kv` leaf's live columns read and
        # one column a row written, a fixed-size leaf read and written
        # whole (stats()["state_bytes"]; the `state_bytes_*` counts of
        # `serve/decode_dispatch`). A paged engine's pool keeps its own.
        self._state_held = {} if _paged else _dm_registry.bytes_by_kind(
            self._state_leaves, (self._kc, self._vc))
        self._kv_col_bytes = self._state_held.get("kv", 0) // (self.B
                                                               * self.T)

        # the width of the tiles a one-token decode step reads each row's
        # cache in (stats()["kv_tiles_read"] / ["kv_tiles_held"]): the
        # model's, where its step stops at each row's position, else the
        # whole row
        k_side = jax.eval_shape(
            lambda: cache_init(self.B, self.T, cache_dt))[0]
        self._kv_tile = dm.kv_read_tile(cfg, k_side, cache_dt,
                                        tp_size) or self.T
        # ... and how many of them, over all rows, such a step's attention
        # writes back with the step's column in them
        # (stats()["kv_tiles_written"]); 0 where the store is its own
        self._kv_tiles_written = self.B * dm.kv_tiles_written(
            cfg, k_side, cache_dt, tp_size)
        # the key tiles a prefill chunk's attention walks through a kernel
        # over its layers (stats()["chunk_kv_tiles"]), by chunk width
        self._chunk_kv_tile = functools.lru_cache(maxsize=None)(
            lambda width: dm.chunk_kv_tile(cfg, k_side, cache_dt, width))

        fixed_state, count_names = self._fixed_state, self._count_names

        def valid(n):
            """A whole-sequence call of a family with fixed-size state is
            told how many of its positions are the sequence's own: the
            padded ones leave that state exactly as it was (a K/V column
            past the end is junk nobody sees; a recurrent state changed
            by padding is changed for good)."""
            return {"valid_len": n} if fixed_state else {}

        def decode(p, toks, pos_vec, kc, vc):
            """One token a row through the stack: (x, kc, vc, extra), extra
            the family's own counts of the step as a 1-tuple, or ()."""
            if count_names:
                x, kc, vc, counts = fwd(p, toks, pos_vec, kc, vc,
                                        counts=True)
                return x, kc, vc, (counts,)
            return fwd(p, toks, pos_vec, kc, vc) + ((),)

        def prefill(p, ids_padded, true_len):
            """ids_padded [1, Pb] right-padded; returns (kc1, vc1,
            last_logits [vocab]). Junk beyond true_len is causally
            invisible and later overwritten by the decode loop; fixed-size
            state comes back as it stood AT true_len (`valid`)."""
            with jax.named_scope("cache/admit"):
                kc1, vc1 = cache_init(1, self.T, cache_dt)
            x, kc1, vc1 = fwd(p, ids_padded, 0, kc1, vc1,
                              **valid(true_len))
            x_last = jax.lax.dynamic_slice_in_dim(
                x, true_len - 1, 1, axis=1)[:, 0]
            return kc1, vc1, logits_of(p, x_last).astype(jnp.float32)[0]

        def prefill_start():
            return cache_init(1, self.T, cache_dt)

        def prefill_chunk_fn(p, chunk_ids, offset, kc1, vc1, last_in_chunk):
            """Consume ONE fixed-size chunk at column `offset` of the slot's
            side cache; returns updated cache + the logits at
            last_in_chunk (only meaningful on the final chunk — junk
            columns beyond it are causally invisible/overwritten)."""
            x, kc1, vc1 = fwd(p, chunk_ids, offset, kc1, vc1,
                              **valid(last_in_chunk + 1))
            x_last = jax.lax.dynamic_slice_in_dim(
                x, last_in_chunk, 1, axis=1)[:, 0]
            return kc1, vc1, logits_of(p, x_last).astype(jnp.float32)[0]

        def make_admit(slot_axes):
            """`slot_axes`: one half of the cache pair's tree with every
            leaf's slot axis in the leaf's place."""

            def admit(big, row, r):
                """Copy a 1-row cache into row r of the big cache (r
                traced — one compile covers every slot): every leaf's
                slot, whole, along the axis its description names. For a
                `kv` leaf that may leave junk past the new request's
                columns; a fixed-size leaf is replaced whole, which is
                what frees a slot of its last request's state."""

                def put(b_leaf, r_leaf, axis):
                    return jax.lax.dynamic_update_slice(
                        b_leaf, r_leaf, tuple(
                            r if i == axis else 0
                            for i in range(b_leaf.ndim)))

                with jax.named_scope("cache/admit"):
                    return jax.tree_util.tree_map(put, big, row, slot_axes)

            return admit

        row_tpl = jax.eval_shape(lambda: cache_init(1, self.T, cache_dt))
        self._row_template = row_tpl
        axes_k, axes_v = (_dm_registry.slot_axes(self._state_leaves, i, t)
                          for i, t in enumerate(row_tpl))

        vocab = cfg.vocab_size

        @jax.named_scope("pick")
        def _pick(logits, temps, kvec, pvec, seeds, pos_vec):
            """Per-row pick: temperature 0 = exact greedy (the argmax path
            is untouched); temperature > 0 samples from the (optionally
            per-row top-k and/or top-p truncated) distribution with a PRNG
            key derived from (request seed, position) — deterministic per
            request, independent across slots. Nucleus filtering runs on
            the temperature-scaled logits, exactly like generate()'s
            single-request pick (models/gpt.py _gpt_generate)."""
            greedy = jnp.argmax(logits, -1).astype(jnp.int32)

            # per-row top-k cutoff (kvec = vocab means no truncation)
            srt = jnp.sort(logits, axis=-1)[:, ::-1]
            cut = jnp.take_along_axis(
                srt, jnp.clip(kvec - 1, 0, vocab - 1)[:, None], axis=-1)
            lg = jnp.where(logits < cut, -jnp.inf, logits)
            safe_t = jnp.where(temps > 0, temps, 1.0)[:, None]
            lgt = lg / safe_t
            # per-row nucleus (pvec = 1.0 means no truncation): smallest
            # sorted prefix reaching mass p; the top token always survives
            srt_t = jnp.sort(lgt, axis=-1)[:, ::-1]
            probs = jax.nn.softmax(srt_t, axis=-1)
            cum = jnp.cumsum(probs, axis=-1)
            k_keep = jnp.sum(cum - probs < pvec[:, None], axis=-1)
            cutoff = jnp.take_along_axis(
                srt_t, jnp.maximum(k_keep - 1, 0)[:, None], axis=-1)
            lgt = jnp.where(lgt < cutoff, -jnp.inf, lgt)

            def draw(row_logits, seed, p_):
                key = jax.random.fold_in(
                    jax.random.fold_in(jax.random.PRNGKey(0), seed), p_)
                return jax.random.categorical(key, row_logits)

            sampled = jax.vmap(draw)(lgt, seeds,
                                     pos_vec).astype(jnp.int32)
            return jnp.where(temps > 0, sampled, greedy)

        def step_greedy(p, kc, vc, last_toks, pos_vec):
            """One decode step for ALL slots at their own positions —
            argmax only (the default workload keeps its lean hot loop:
            no sort/categorical machinery compiled in)."""
            x, kc, vc, extra = decode(p, last_toks[:, None], pos_vec, kc,
                                      vc)
            logits = logits_of(p, x[:, 0]).astype(jnp.float32)
            with jax.named_scope("pick"):
                toks = jnp.argmax(logits, -1).astype(jnp.int32)
            return (toks, kc, vc) + extra

        def step_sample(p, kc, vc, last_toks, pos_vec, temps, kvec,
                        pvec, seeds):
            """Decode step with per-request sampling knobs [B] (used only
            while at least one active request has temperature > 0)."""
            x, kc, vc, extra = decode(p, last_toks[:, None], pos_vec, kc,
                                      vc)
            logits = logits_of(p, x[:, 0]).astype(jnp.float32)
            return (_pick(logits, temps, kvec, pvec, seeds, pos_vec), kc,
                    vc) + extra

        if _paged:
            _paging_mod = self._paging
            _has_lora = self._lora is not None

            def _fwd_pg(p, toks, pos, kc, vc, lora, aids):
                if _has_lora:
                    return fwd(p, toks, pos, kc, vc, lora=lora,
                               adapter_ids=aids)
                return fwd(p, toks, pos, kc, vc)

            def prefill_paged(p, ids_padded, true_len, lora, aid):
                """Whole-prompt prefill with the request's adapter delta
                applied (aid [1]; slot 0 = base = exact-zero add): the
                prefilled row and first-token logits match a dedicated
                engine serving that adapter byte-for-byte."""
                with jax.named_scope("cache/admit"):
                    kc1, vc1 = cache_init(1, self.T, cache_dt)
                x, kc1, vc1 = _fwd_pg(p, ids_padded, 0, kc1, vc1, lora, aid)
                x_last = jax.lax.dynamic_slice_in_dim(
                    x, true_len - 1, 1, axis=1)[:, 0]
                return kc1, vc1, logits_of(p, x_last).astype(jnp.float32)[0]

            def step_greedy_paged(p, kp, vp, tables, last_toks, pos_vec,
                                  lora, aids):
                """Paged decode step: gather pool frames -> the dense
                [L, B, KVh, T, hd] layout, run the UNCHANGED decode math
                (per-row adapter deltas included), scatter each row's
                frontier column back into its frame. Junk in null/free
                columns sits strictly above every row's position, so
                causal masking makes tokens bit-identical to the dense
                engine's."""
                kc, vc = _paging_mod.gather_dense(kp, vp, tables)
                x, kc, vc = _fwd_pg(p, last_toks[:, None], pos_vec, kc, vc,
                                    lora, aids)
                with jax.named_scope("cache/store"):
                    kp, vp = _paging_mod.scatter_cols(kp, vp, kc, vc, tables,
                                                      pos_vec)
                logits = logits_of(p, x[:, 0]).astype(jnp.float32)
                with jax.named_scope("pick"):
                    toks = jnp.argmax(logits, -1).astype(jnp.int32)
                return toks, kp, vp

            def step_sample_paged(p, kp, vp, tables, last_toks, pos_vec,
                                  temps, kvec, pvec, seeds, lora, aids):
                kc, vc = _paging_mod.gather_dense(kp, vp, tables)
                x, kc, vc = _fwd_pg(p, last_toks[:, None], pos_vec, kc, vc,
                                    lora, aids)
                with jax.named_scope("cache/store"):
                    kp, vp = _paging_mod.scatter_cols(kp, vp, kc, vc, tables,
                                                      pos_vec)
                logits = logits_of(p, x[:, 0]).astype(jnp.float32)
                return (_pick(logits, temps, kvec, pvec, seeds, pos_vec),
                        kp, vp)

        # every program in the family is a CachedJit (framework/aot.py):
        # warmup() compiles them from shape specs before traffic; never
        # warmed = plain jax.jit behavior.
        def _cj(fn=None, label=None, jit=None, donate=()):
            return _aot.cached_jit(fn, jit=jit, site="serving", label=label,
                                   donate_argnums=donate,
                                   record_event="serving/compile")

        # donate the big cache through admit/step: XLA aliases it in place
        # instead of copying GBs of K/V per token (the loop this engine
        # exists to make fast); CPU backends that can't donate just warn
        if tp_mesh is None:
            self._prefill = _cj(prefill, "prefill")
            self._step_greedy = _cj(step_greedy, "step_greedy",
                                    donate=(1, 2))
            self._step_sample = _cj(step_sample, "step_sample",
                                    donate=(1, 2))
            if _paged:
                # pool sides donate through the step exactly like the
                # dense big cache: the scatter updates them in place
                self._prefill_pg = _cj(prefill_paged, "prefill_paged")
                self._step_greedy_pg = _cj(step_greedy_paged,
                                           "step_greedy_paged",
                                           donate=(1, 2))
                self._step_sample_pg = _cj(step_sample_paged,
                                           "step_sample_paged",
                                           donate=(1, 2))
        else:
            from jax.sharding import PartitionSpec as P

            _tp_wrap = dm.tp_wrap
            cs = self._cache_spec   # pytree-prefix: covers int8 tuples too
            self._prefill = _cj(jit=_tp_wrap(
                prefill, tp_mesh, tp_specs, 0, (cs, cs, P()),
                in_specs=(tp_specs, P(), P())), label="prefill")
            self._step_greedy = _cj(jit=_tp_wrap(
                step_greedy, tp_mesh, tp_specs, 0, (P(), cs, cs),
                in_specs=(tp_specs, cs, cs, P(), P()), donate=(1, 2)),
                label="step_greedy")
            self._step_sample = _cj(jit=_tp_wrap(
                step_sample, tp_mesh, tp_specs, 0, (P(), cs, cs),
                in_specs=(tp_specs, cs, cs, P(), P(), P(), P(), P(), P()),
                donate=(1, 2)), label="step_sample")
            # chunked prefill composes with tp: the chunk side-cache
            # allocates head-sharded (side_alloc above) and the chunk
            # program runs inside the same shard_map recipe
            self._prefill_start = side_alloc
            self._prefill_chunk = _cj(jit=_tp_wrap(
                prefill_chunk_fn, tp_mesh, tp_specs, 0, (cs, cs, P()),
                in_specs=(tp_specs, P(), P(), cs, cs, P()),
                donate=(3, 4)), label="prefill_chunk")
        # admit slices only the batch axis: a plain jit partitions it
        # fine over the head-sharded cache
        self._admit = _cj(make_admit(axes_k), "admit", donate=(0,))
        # the pair's second half: the same program where it is described
        # as the first is (a K/V pair), its own where it holds other kinds
        self._admit_second = self._admit if axes_k == axes_v else _cj(
            make_admit(axes_v), "admit_fixed", donate=(0,))
        # the prefill token goes through the SAME pick as decode steps
        def pick1(lg, t, k, tp, s, p_):
            return _pick(lg[None], t[None], k[None], tp[None], s[None],
                         p_[None])[0]

        def pick1_put(toks, slot, lg, t, k, tp, s, p_):
            """The admission's first token, picked on the device and laid
            into row `slot` of the token vector the next decode step
            reads; the token alone comes back too, for the host to read
            when it reads the round's others."""
            tok = pick1(lg, t, k, tp, s, p_)
            with jax.named_scope("pick"):
                return tok, toks.at[slot].set(tok)

        # one decode step of lookahead (_step_inner_lookahead): dense
        # engines without a draft keep a step in flight and read its
        # tokens one step() call behind. Paged engines (admission mutates
        # the pool whose tables the dispatched step snapshotted) and
        # speculative engines (the draft round's host orchestration IS the
        # dispatch) keep the serial loop (_step_inner_sync).
        self._lookahead = draft_model is None and not _paged
        if not self._lookahead:
            self._pick1 = _cj(pick1, "pick1")
        elif tp_mesh is None:
            self._pick1_put = _cj(pick1_put, "pick1_put")
        else:
            # the token vector stays what the tp step returns and takes:
            # replicated over the mesh
            from jax.sharding import NamedSharding, PartitionSpec as P

            rep = NamedSharding(tp_mesh, P())
            self._pick1_put = _cj(jit=jax.jit(
                pick1_put, out_shardings=(rep, rep)), label="pick1_put")

        self._chunk = None if prefill_chunk is None else int(prefill_chunk)
        if tp_mesh is None:
            self._prefill_start = prefill_start
            self._prefill_chunk = _cj(prefill_chunk_fn, "prefill_chunk",
                                      donate=(3, 4))
        # slot -> [req, kc1, vc1, consumed_offset, chunk_width]
        self._prefilling = {}
        # registered shared prefixes: pid -> (ids, kc1, vc1). The chunk fn
        # DONATES its cache args, so admissions consume a fresh COPY
        self._prefixes = {}
        self._next_pid = 0
        # (its device work is the copy XLA makes of an undonated argument:
        # no operation of the program's own to carry a scope; the module's
        # name, serving.copy_cache, says what it is)
        self._copy_cache = _cj(
            lambda c: jax.tree_util.tree_map(jnp.array, c), "copy_cache")

        # --- speculative decoding: a draft model proposes spec_k tokens
        # per round, the target verifies them in ONE multi-token forward
        # at PER-SLOT positions and accepts the longest matching prefix
        # plus its own fix-up token — 1..spec_k+1 tokens per round, output
        # bit-identical to plain greedy (same scheme as
        # generate_speculative, batched over slots; the cache invariant —
        # junk columns past the accepted frontier are causally invisible
        # and overwritten — is the one admission prefill already relies
        # on). Rounds run only while EVERY active slot is greedy with
        # spec_k+1 columns of cache headroom; otherwise the engine falls
        # back to single-token steps (still exact).
        self._draft = None
        if draft_model is not None:
            self._spec_k = K = int(spec_k)
            params_d, dm_d_aux = dm_d.extract_params(
                draft_model, "the draft model")
            if self._compute_dtype is not None:
                params_d = {n: (v.astype(self._compute_dtype)
                                if jnp.issubdtype(v.dtype, jnp.floating)
                                else v) for n, v in params_d.items()}
            # the draft is small by design: it stays replicated (dense
            # fns) even when the target serves tensor-parallel
            fwd_d, logits_d, cache_init_d = dm_d.decode_fns(
                draft_model.cfg, dm_d_aux, cache_dtype=cache_dtype)
            self._params_d = params_d
            self._kc_d, self._vc_d = cache_init_d(self.B, self.T, cache_dt)

            def draft_row():
                return cache_init_d(1, self.T, cache_dt)

            def draft_feed(pd, ids_padded, offset, kc1, vc1):
                """Write a token block's draft KV at `offset` (whole-prompt
                prefill at 0, or one chunk of a chunked admission)."""
                _, kc1, vc1 = fwd_d(pd, ids_padded, offset, kc1, vc1)
                return kc1, vc1

            def draft_propose(pd, kc_d, vc_d, last, pos_vec):
                """K sequential draft steps at per-row positions; also
                writes the K-th proposal's KV (an all-accepted round
                continues PAST that column — an unwritten column inside
                the accepted prefix would poison later attention)."""
                d_cur = last
                props = []
                for j in range(K):
                    xd, kc_d, vc_d = fwd_d(pd, d_cur[:, None], pos_vec + j,
                                           kc_d, vc_d)
                    d_cur = jnp.argmax(
                        logits_d(pd, xd[:, 0]).astype(jnp.float32),
                        -1).astype(jnp.int32)
                    props.append(d_cur)
                _, kc_d, vc_d = fwd_d(pd, d_cur[:, None], pos_vec + K,
                                      kc_d, vc_d)
                return jnp.stack(props, axis=1), kc_d, vc_d

            def verify(p, kc, vc, last, pos_vec, props):
                """One (K+1)-token target forward per slot row: accept the
                longest prefix where each proposal equals the target's own
                argmax after the same context, emit it plus the target's
                fix-up token. emit[s, j] is meaningful for j <= m[s]."""
                seq = jnp.concatenate([last[:, None], props], axis=1)
                x, kc, vc = fwd(p, seq, pos_vec, kc, vc)
                preds = jnp.argmax(
                    logits_of(p, x).astype(jnp.float32),
                    -1).astype(jnp.int32)                     # [B, K+1]
                matches = (props == preds[:, :K]).astype(jnp.int32)
                m = jnp.cumprod(matches, axis=1).sum(axis=1)  # [B] 0..K
                fix = jnp.take_along_axis(preds, m[:, None], axis=1)
                j_idx = jnp.arange(K + 1)[None]
                padded = jnp.pad(props, ((0, 0), (0, 1)))
                emit = jnp.where(j_idx < m[:, None], padded, fix)
                return emit, m, kc, vc

            def draft_sync(pd, kc_d, vc_d, last, pos_vec):
                """One 1-token draft forward at per-row positions: keeps
                the draft KV cache in lockstep during single-token
                FALLBACK steps (sampling neighbors / near-capacity), so a
                slot that lives through a fallback resumes speculative
                rounds with an intact draft context instead of a
                permanently cold one."""
                _, kc_d, vc_d = fwd_d(pd, last[:, None], pos_vec,
                                      kc_d, vc_d)
                return kc_d, vc_d

            self._draft = draft_model
            self._draft_row = draft_row
            self._draft_sync = _cj(draft_sync, "draft_sync", donate=(1, 2))
            self._draft_feed = _cj(draft_feed, "draft_feed", donate=(3, 4))
            self._draft_propose = _cj(draft_propose, "draft_propose",
                                      donate=(1, 2))
            if tp_mesh is None:
                self._verify = _cj(verify, "verify", donate=(1, 2))
            else:
                from jax.sharding import PartitionSpec as P

                cs = self._cache_spec
                self._verify = _cj(jit=dm.tp_wrap(
                    verify, tp_mesh, tp_specs, 0, (P(), P(), cs, cs),
                    in_specs=(tp_specs, cs, cs, P(), P(), P()),
                    donate=(1, 2)), label="verify")

        # engine-local observability accumulators (the module-level monitor
        # metrics aggregate across engines; stats() reports THIS engine)
        self._m = {"submitted": 0, "finished": {}, "tokens": 0,
                   "steps": {}, "step_ms": {}, "spec_proposed": 0,
                   "spec_accepted": 0,
                   "prefix_hit": 0, "prefix_miss": 0,
                   "occupancy_sum": 0, "occupancy_steps": 0,
                   "kv_tiles_read": 0, "kv_tiles_held": 0,
                   "kv_tiles_written": 0, "chunk_kv_tiles": 0,
                   "state_bytes_moved": {k: 0 for k in self._state_held},
                   "step_counts": {n: 0 for n in self._count_names},
                   "lookahead": {"rounds": 0, "rounds_overlapped": 0,
                                 "tokens_discarded": 0},
                   "queue_wait_ms": _MsSummary(), "ttft_ms": _MsSummary(),
                   "inter_token_ms": _MsSummary()}
        # admissions so far: [requests, prompt tokens] — the serve/step and
        # serve/admit phases' `admitted`/`prompt_tokens` counts are deltas
        self._admitted = [0, 0]

        # host-side slot state
        self._slot_req = [None] * self.B        # Request or None
        # next write column; the lookahead loop advances it when a step
        # is DISPATCHED, the serial loop when its tokens are read
        self._pos = np.zeros(self.B, np.int32)
        # each slot's last token: ON THE DEVICE under the lookahead loop (a
        # decode step's output is the next step's input; admissions lay
        # their first token over it there, pick1_put), on the host under
        # the serial one
        if self._lookahead:
            self._toks = jnp.zeros(self.B, jnp.int32)
        else:
            self._last = np.zeros(self.B, np.int32)
        # the lookahead loop's step dispatched and not yet read (_Flight),
        # and this round's admissions whose first token is not read yet:
        # (slot, request, device token)
        self._flight = None
        self._firsts = []
        self._step_counts = None    # the newest dispatched step's own
        self._temps = np.zeros(self.B, np.float32)   # 0 = greedy
        self._topk = np.full(self.B, self.cfg.vocab_size, np.int32)
        self._topp = np.ones(self.B, np.float32)     # 1.0 = no nucleus
        self._seeds = np.zeros(self.B, np.int32)
        self._queue = []
        # disaggregated prefill->decode handoff (admit_prefilled): rows
        # whose prompt KV arrived already prefilled, waiting for a slot.
        # Plain engines never touch it beyond an empty-list truthiness
        # check per step (gate-pinned in tests/test_router_gate.py).
        self._handoff = []
        self._next_rid = 0
        self._finished = {}
        # robustness state: draining stops admission; step/error counters
        # feed health()'s ok|degraded|draining verdict
        self._draining = False
        self._deadline_live = 0   # unfinished requests carrying deadline_ms
        self._step_no = 0
        self._last_error_step = None
        # perf ledger (FLAGS_perf_ledger, docs/OBSERVABILITY.md):
        # consumed at ENGINE CONSTRUCTION like the trainer's copy.
        # Non-structural — host-side accounting only; disarmed, step()
        # pays one `is not None`
        self._perf_ledger = None
        self._perf_rounds = 0
        if _flags.get_flag("perf_ledger", False):
            from ..monitor import perfledger as _perfledger

            self._perf_ledger = _perfledger.get_ledger()
        # weight-version lineage (framework/lineage.py, ISSUE 20):
        # always-on host metadata — the engine mints a version for the
        # params it was built with, bumps it on hot_swap(), and stamps
        # every accepted request with the version it will decode under.
        # Adapter slots carry their own load-time stamps. METRIC
        # publication (serving_weight_version gauge, stale-session
        # counter) rides the goodput accountant, consumed here like the
        # perf ledger: disarmed costs one `is not None` per finish.
        self._weight_version = _lineage.WeightVersion(
            _lineage.new_run_id(), 0, "init")
        self._adapter_versions = {}   # adapter name -> WeightVersion
        self._goodput = None
        if _flags.get_flag("goodput", False):
            from ..monitor import goodput as _goodput

            self._goodput = _goodput
            _goodput.note_serving_version(self._weight_version.counter)

        # blackbox dump bundles carry every live engine's in-flight
        # request table (weakly held; only read at dump time)
        _blackbox.register_provider("serving_engine", self,
                                    _blackbox_request_table)

    # -- API -----------------------------------------------------------------
    def register_prefix(self, prefix_ids, adapter=None):
        """Prefill a shared prefix (e.g. a system prompt) ONCE and cache
        its KV; returns a prefix id for submit(prefix_id=...). Requests
        using it prefill only their suffix.

        Paged engines (FLAGS_paged_kv): the prefix's full blocks land in
        the pool ONCE and every session submitting with this prefix_id
        maps them SHARED (refcounted; a partial boundary block is copied
        private at admission — copy-on-write). ``adapter=`` prefills the
        prefix under that loaded adapter's delta; sessions share the
        frames only when their adapter matches."""
        import jax.numpy as jnp

        if adapter is not None and not self._paged:
            raise ValueError(
                "register_prefix(adapter=) needs FLAGS_paged_kv=1")
        ids = prefix_ids._data if isinstance(prefix_ids, Tensor) \
            else np.asarray(prefix_ids)
        ids = np.asarray(ids, np.int32).ravel()
        if len(ids) == 0:
            raise ValueError("empty prefix")
        if len(ids) + 2 > self.T:
            raise ValueError(
                f"prefix ({len(ids)}) too long for max_seq_len {self.T}")
        n = len(ids)
        pb = self._bucket(n)
        padded = np.zeros((1, pb), np.int32)
        padded[0, :n] = ids
        if self._paged:
            aid = self._resolve_adapter_slot(adapter)
            with _trace.phase("serve/prefill", tokens=n, bucket=pb) as ph:
                kc1, vc1, _ = self._prefill_pg(
                    self._params, jnp.asarray(padded), np.int32(n),
                    self._lora, jnp.asarray([aid], np.int32))
            self._acc_phase("prefill", ph)
            pid = self._next_pid
            self._next_pid += 1
            # full blocks land in the pool once (put_prefix may raise
            # PagePoolFullError — nothing is registered then); the dense
            # row is dropped, sessions re-block only their suffix
            self._pool.put_prefix(pid, kc1[:, 0], vc1[:, 0], n)
            self._prefixes[pid] = (ids, "paged", adapter, None, None)
            return pid
        # accounted as a "prefill" slice: the prefill PROGRAM runs here,
        # so its wall time must land in the same breakdown kind its
        # executed-flops counters feed — otherwise stats()['breakdown']
        # reports registration FLOPs with zero matching wall time
        with _trace.phase("serve/prefill", tokens=n, bucket=pb) as ph:
            kc1, vc1, _ = self._prefill(self._params, jnp.asarray(padded),
                                        np.int32(n))
            kc1d = vc1d = None
            if self._draft is not None:  # the draft replays suffixes from
                # its own cached prefix KV, like the target
                kc1d, vc1d = self._draft_feed(
                    self._params_d, jnp.asarray(padded), np.int32(0),
                    *self._draft_row())
        self._acc_phase("prefill", ph)
        pid = self._next_pid
        self._next_pid += 1
        self._prefixes[pid] = (ids, kc1, vc1, kc1d, vc1d)
        return pid

    def warmup(self, batch_shapes=None, sampling=True):
        """Compile the engine's whole jitted program family BEFORE traffic,
        from shape specs only — no real prompts, nothing executed, the KV
        cache untouched: the programs are compiled in memory, so
        submit/step then pay no compile. With jax's persistent cache on
        (paddle.enable_compile_cache()) a fresh server process finds
        every one of them there and compiles nothing.

        batch_shapes: iterable of prompt lengths to warm prefill buckets
        for (bucketed exactly like submit(); default: every configured
        bucket). sampling=False skips the sampling decode step for
        all-greedy deployments. Returns {program: warmed-signature count}.
        """
        import jax
        import jax.numpy as jnp

        def aval(t):
            return jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(
                    a.shape, a.dtype, sharding=getattr(a, "sharding", None)),
                t)

        def f32(shape=()):
            return jax.ShapeDtypeStruct(shape, jnp.float32)

        def i32(shape=()):
            return jax.ShapeDtypeStruct(shape, jnp.int32)

        counts = {}

        def warm(cj, *specs):
            counts[cj._label] = counts.get(cj._label, 0) + \
                (1 if cj.warm(*specs) else 0)

        B, V = self.B, self.cfg.vocab_size
        p = aval(self._params)
        if self._paged:
            lens = (list(batch_shapes) if batch_shapes is not None
                    else list(self._buckets))
            lora = aval(self._lora)
            kp, vp = aval(self._pool.kp), aval(self._pool.vp)
            tb = i32((B, self._pool.maxb))
            for pb in sorted({self._bucket(int(n)) for n in lens}):
                warm(self._prefill_pg, p, i32((1, pb)), i32(), lora,
                     i32((1,)))
            warm(self._step_greedy_pg, p, kp, vp, tb, i32((B,)),
                 i32((B,)), lora, i32((B,)))
            if sampling:
                warm(self._step_sample_pg, p, kp, vp, tb, i32((B,)),
                     i32((B,)), f32((B,)), i32((B,)), f32((B,)),
                     i32((B,)), lora, i32((B,)))
            warm(self._pick1, f32((V,)), f32(), i32(), f32(), i32(),
                 i32())
            return counts
        kc, vc = aval(self._kc), aval(self._vc)
        kc1, vc1 = jax.eval_shape(lambda: self._prefill_start())
        lg_spec, toks_spec = f32((V,)), i32((B,))
        if self._tp_mesh is not None:
            # eval_shape drops out_shardings: re-attach the head-sharded
            # side-cache placement (same every-leaf recipe as the ctor's
            # side_alloc) or the warmed executables would be compiled for
            # unsharded rows and rejected at first admission. The prefill
            # logits likewise arrive mesh-replicated, so pick1's spec
            # must carry that placement too.
            from jax.sharding import NamedSharding, PartitionSpec as P

            sh = NamedSharding(self._tp_mesh, self._cache_spec)
            reshard = lambda t: jax.tree_util.tree_map(  # noqa: E731
                lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                               sharding=sh), t)
            kc1, vc1 = reshard(kc1), reshard(vc1)
            rep = NamedSharding(self._tp_mesh, P())
            lg_spec = jax.ShapeDtypeStruct((V,), jnp.float32, sharding=rep)
            toks_spec = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=rep)
        lens = (list(batch_shapes) if batch_shapes is not None
                else list(self._buckets))
        buckets = sorted({self._bucket(int(n)) for n in lens})
        for pb in buckets:
            warm(self._prefill, p, i32((1, pb)), i32())
        warm(self._step_greedy, p, kc, vc, i32((B,)), i32((B,)))
        if sampling:
            warm(self._step_sample, p, kc, vc, i32((B,)), i32((B,)),
                 f32((B,)), i32((B,)), f32((B,)), i32((B,)))
        if self._lookahead:
            warm(self._pick1_put, toks_spec, i32(), lg_spec, f32(), i32(),
                 f32(), i32(), i32())
        else:
            warm(self._pick1, lg_spec, f32(), i32(), f32(), i32(), i32())
        # slot index rides as a weakly-typed python int, exactly as the
        # live _activate call passes it
        warm(self._admit, kc, kc1, 0)
        warm(self._copy_cache, kc1)
        if self._admit_second is not self._admit:
            warm(self._admit_second, vc, vc1, 0)
            warm(self._copy_cache, vc1)
        if self._chunk is not None:
            warm(self._prefill_chunk, p, i32((1, self._chunk)), i32(),
                 kc1, vc1, i32())
        if self._draft is not None:
            pd = aval(self._params_d)
            kcd, vcd = aval(self._kc_d), aval(self._vc_d)
            kc1d, vc1d = jax.eval_shape(self._draft_row)
            for pb in buckets:
                warm(self._draft_feed, pd, i32((1, pb)), i32(), kc1d, vc1d)
            if self._chunk is not None:
                warm(self._draft_feed, pd, i32((1, self._chunk)), i32(),
                     kc1d, vc1d)
            warm(self._draft_propose, pd, kcd, vcd, i32((B,)), i32((B,)))
            warm(self._verify, p, kc, vc, i32((B,)), i32((B,)),
                 i32((B, self._spec_k)))
            warm(self._draft_sync, pd, kcd, vcd, i32((B,)), i32((B,)))
            # admissions also row-copy into the DRAFT cache (its shapes
            # differ from the target's) and prefix reuse copies draft
            # side caches — warm those signatures too
            warm(self._admit, kcd, kc1d, 0)
            warm(self._copy_cache, kc1d)
        return counts

    def _count_step(self, kind):
        self._m["steps"][kind] = self._m["steps"].get(kind, 0) + 1
        _STEPS.labels(kind=kind).inc()

    def _acc_phase(self, kind, *phases):
        """Book one step-kind slice of stats()['breakdown']: the summed
        wall time of the closed step phases that cover it (a decode kind
        is its dispatch + wait phases — under the lookahead loop the
        dispatch of one step() call and the wait of the next; what passes
        between them is booked under its own kinds, and counting it twice
        would make the kinds sum past real wall time). The phases' clock
        reads are the only ones taken."""
        st = self._m["step_ms"].setdefault(kind, [0, 0.0])
        st[0] += 1
        st[1] += sum(ph.end_ns - ph.start_ns for ph in phases) / 1e6

    def stats(self):
        """Engine-lifetime observability snapshot: request counts by
        outcome, token totals, step split (prefill/decode/speculative),
        batch-occupancy average, prefix-cache hit rate, speculative
        accept rate, and queue-wait/TTFT/inter-token latency summaries.
        Host-side accounting only — never touches the device. The same
        families stream into paddle_tpu.monitor (serving_* metrics) for
        the snapshot/Prometheus/JSONL exporters."""
        m = self._m
        occ = (m["occupancy_sum"] / m["occupancy_steps"]
               if m["occupancy_steps"] else 0.0)
        prefix_n = m["prefix_hit"] + m["prefix_miss"]
        out = {
            "slots": self.B,
            "requests": {"submitted": m["submitted"],
                         "queued": len(self._queue),
                         "handoff": len(self._handoff),
                         "prefilling": len(self._prefilling),
                         # decoding slots only: mid-prefill slots hold a
                         # _slot_req reservation but belong to "prefilling"
                         # (and rows whose slot is free again with their
                         # last token still on its way: _unread)
                         "running": sum(1 for s in range(self.B)
                                        if self._slot_req[s] is not None
                                        and s not in self._prefilling)
                         + len(self._unread()),
                         "finished": dict(m["finished"])},
            "tokens_generated": m["tokens"],
            "steps": dict(m["steps"]),
            "batch_occupancy_avg": occ,
            "kv_tiles_read": m["kv_tiles_read"],
            "kv_tiles_held": m["kv_tiles_held"],
            "kv_tiles_written": m["kv_tiles_written"],
            # key tiles the prefill chunks' attention walked through a
            # kernel, over the layers; 0 where it is not a kernel's walk
            "chunk_kv_tiles": m["chunk_kv_tiles"],
            # bytes of state by kind (kv, recurrent, conv): what the engine
            # holds, and what its decode steps so far had to move of it
            "state_bytes": {"held": dict(self._state_held),
                            "moved": dict(m["state_bytes_moved"])},
            **m["step_counts"],
            # how often the lookahead loop engages: decode steps
            # dispatched, those dispatched while the one before was still
            # unread, and columns computed for rows that had finished
            # (eos, cancel, deadline, error: what the host learns a step
            # late). All 0 on an engine that keeps the serial loop.
            "lookahead": dict(m["lookahead"],
                              in_flight=int(self._flight is not None)),
            "prefix_cache": {"hit": m["prefix_hit"],
                             "miss": m["prefix_miss"],
                             "hit_rate": (m["prefix_hit"] / prefix_n
                                          if prefix_n else None)},
            "speculative": {"proposed": m["spec_proposed"],
                            "accepted": m["spec_accepted"],
                            "accept_rate": (m["spec_accepted"]
                                            / m["spec_proposed"]
                                            if m["spec_proposed"]
                                            else None)},
            "queue_wait_ms": m["queue_wait_ms"].to_dict(),
            "ttft_ms": m["ttft_ms"].to_dict(),
            "inter_token_ms": m["inter_token_ms"].to_dict(),
            "breakdown": self._breakdown(),
            "health": self.health(),
            # lineage (ISSUE 20): what the engine serves RIGHT NOW;
            # per-request stamps live in each request's stats()
            "weight_version": str(self._weight_version),
        }
        if self._adapter_versions:
            out["adapter_versions"] = {
                n: str(v)
                for n, v in sorted(self._adapter_versions.items())}
        if self._paged:
            pg = self._pool.stats()
            live = sum(1 for r in self._slot_req if r is not None)
            pg["live_sessions"] = live
            # pool bytes actually held per live session vs what the dense
            # engine pins per slot (one full-length row) — the paged-KV
            # memory win in one ratio (gate-asserted ≥ 2x under shared
            # prefixes in tests/test_paging_gate.py)
            pg["kv_bytes_per_session"] = (
                self._pool.bytes_in_use() / live if live else 0.0)
            pg["dense_bytes_per_session"] = (
                self._pool.block_bytes * self._pool.maxb)
            if self._adapters is not None:
                ad = self._adapters.stats()
                ad["loaded_names"] = sorted(self._adapters.loaded())
                pg["adapters"] = ad
            out["paging"] = pg
        return out

    def _kind_programs(self, kind):
        """THIS engine's CachedJit wrappers whose device work the kind's
        wall time covers (speculative = draft proposal + target verify).
        Two draft programs are deliberately unattributed because ONE
        wrapper's cumulative counters feed MORE than one kind and cannot
        be split: draft_sync runs inside both decode kinds' fallback
        steps, and draft_feed inside whole-prompt (prefill), chunked
        (prefill_chunk), AND prefix-registration windows — draft-enabled
        engines therefore understate those kinds' flops by the (small by
        design) draft model's share rather than double-count it."""
        progs = {
            "prefill": [getattr(self, "_prefill", None),
                        getattr(self, "_prefill_pg", None)],
            "prefill_chunk": [getattr(self, "_prefill_chunk", None)],
            "decode_greedy": [getattr(self, "_step_greedy", None),
                              getattr(self, "_step_greedy_pg", None)],
            "decode_sample": [getattr(self, "_step_sample", None),
                              getattr(self, "_step_sample_pg", None)],
            "speculative": [getattr(self, "_draft_propose", None),
                            getattr(self, "_verify", None)],
        }
        return [p for p in progs.get(kind, ())
                if isinstance(p, _aot.CachedJit)]

    def _breakdown(self):
        """Step-time breakdown: host wall time per step kind joined with
        THIS engine's executed device FLOPs (each program wrapper's own
        per-signature accounting — a bucketed prefill family weights
        every bucket's flops, and a second engine in the process cannot
        bleed into this one's numbers). flops fields appear once the
        program family has executables captured — FLAGS_trace=1 or
        warmup() populate them; without them the wall-time split still
        stands on its own."""
        total_ms = sum(st[1] for st in self._m["step_ms"].values())
        kinds = {}
        flops_total = 0.0
        flops_known = False
        for kind in sorted(self._m["step_ms"]):
            count, ms = self._m["step_ms"][kind]
            row = {"count": count, "wall_ms": ms,
                   "wall_fraction": (ms / total_ms) if total_ms else 0.0}
            wrappers = self._kind_programs(kind)
            ex_calls, ex_flops = 0, 0.0
            for w in wrappers:
                e = w.executed()
                ex_calls = max(ex_calls, e["calls"])
                ex_flops += e["flops"]
            per_call = total = None
            if ex_calls:
                total = ex_flops
                per_call = ex_flops / ex_calls
            else:
                # no execution accounting (e.g. programs ran before any
                # cost capture): fall back to the site-global latest
                # entries under the SAME wrappers' labels, so the two
                # paths agree on what one call covers
                entries = [_costs.get("serving", w._label)
                           for w in wrappers]
                entries = [e for e in entries if e is not None]
                if entries:
                    per_call = sum(e["flops"] for e in entries)
                    total = per_call * count
            if per_call is not None:
                row["flops_per_call"] = per_call
                row["device_flops_total"] = total
                flops_total += total
                flops_known = True
            kinds[kind] = row
        out = {"kinds": kinds, "wall_ms_total": total_ms}
        if flops_known:
            out["device_flops_total"] = flops_total
            peak = _costs.peak_flops()
            if total_ms > 0 and peak:
                # achieved device FLOP/s over the engine's measured step
                # time, against the chip's peak — the serving-side MFU
                out["device_flops_per_sec"] = flops_total / (total_ms / 1e3)
                out["mfu"] = out["device_flops_per_sec"] / peak
        return out

    def get_request(self, rid):
        """The live Request object for a submitted id — queued, in-flight,
        or finished. The per-request observability surface: read
        output_ids as tokens stream, or req.stats() for queue-wait/TTFT/
        inter-token latencies (engine-level aggregates: stats()). Raises
        KeyError for an unknown id."""
        for req in self._queue:
            if req.rid == rid:
                return req
        for req in self._slot_req:
            if req is not None and req.rid == rid:
                return req
        for entry in self._prefilling.values():
            if entry[0].rid == rid:
                return entry[0]
        for entry in self._handoff:
            if entry[0].rid == rid:
                return entry[0]
        for req in self._unread():
            if req.rid == rid:
                return req
        if rid in self._finished:
            return self._finished[rid]
        raise KeyError(f"unknown request id {rid}")

    def unregister_prefix(self, prefix_id):
        """Free a registered prefix's cached KV (each pins a [1, max_seq]
        side cache on device — long-lived engines rotating system prompts
        should release retired ones). In-flight requests that already
        copied it are unaffected; later submits with this id raise."""
        if prefix_id not in self._prefixes:
            raise ValueError(f"unknown prefix_id {prefix_id}")
        if self._paged and self._prefixes[prefix_id][1] == "paged":
            # drop the registry's frame references; frames still mapped by
            # live sessions stay alive until those sessions finish
            self._pool.drop_prefix(prefix_id)
        del self._prefixes[prefix_id]

    # -- multi-LoRA adapter management (FLAGS_paged_kv engines) --------------
    def _require_adapters(self):
        if not self._paged:
            raise RuntimeError(
                "multi-LoRA adapters need FLAGS_paged_kv=1 — the paged "
                "engine owns the adapter registry (docs/SERVING.md)")
        if self._adapters is None:
            raise RuntimeError(
                f"decode model {self._dm.name!r} does not support "
                "multi-LoRA serving (no lora_init), or the engine was "
                "built with max_adapters=0")

    def _resolve_adapter_slot(self, name):
        """Loaded adapter name -> device slot index (None -> 0 = base)."""
        if name is None:
            return 0
        self._require_adapters()
        slot = self._adapters.peek(name)
        if slot is None:
            raise ValueError(
                f"adapter {name!r} is not loaded — load_adapter() it "
                f"first (loaded: {sorted(self._adapters.loaded())})")
        return slot

    def load_adapter(self, name, exported, pin=False):
        """Hot-load one exported LoRA adapter (``incubate.lora.
        export_lora`` form) into a device slot of the stacked multi-LoRA
        factors; returns the slot index. Requests then select it with
        submit(adapter=name) — every loaded adapter decodes batched in
        the SAME jitted step (one gathered einsum per site; no
        per-adapter programs, no recompiles: the write below is a
        same-shape .at[slot].set).

        A full registry evicts the least-recently-used unpinned adapter;
        its in-flight sessions restart from the queue head and complete
        bit-identically once their adapter returns (greedy/seeded decode
        is deterministic — chaos-pinned by tools/chaos_check.py
        adapter_evict_under_load). pin=True exempts this adapter from
        LRU eviction; loading raises RuntimeError while every slot is
        pinned, ValueError for a malformed/duplicate adapter (a bad
        adapter never evicts a healthy one)."""
        self._require_adapters()
        if not isinstance(name, str) or not name:
            raise ValueError(
                f"adapter name must be a non-empty str, got {name!r}")
        if self._adapters.peek(name) is not None:
            raise ValueError(f"adapter {name!r} is already loaded")
        _fp.failpoint("serving/adapter")
        # pack BEFORE claiming a slot: packing validates rank/shape/layer
        # coverage, and a malformed adapter must leave the registry and
        # the device factors exactly as they were
        packed = self._dm.lora_pack(self.cfg, exported, self._lora_rank)
        slot, evicted = self._adapters.admit(name, pin=pin)
        if evicted is not None:
            self._restart_adapter_sessions(evicted)
            self._adapter_versions.pop(evicted, None)
        self._write_adapter_slot(slot, packed)
        # lineage stamp (ISSUE 20): which base-weight version this
        # adapter's factors were loaded under, origin adapter_load —
        # completions submitted with adapter=name carry it
        self._adapter_versions[name] = _lineage.WeightVersion(
            self._weight_version.run_id, self._weight_version.counter,
            "adapter_load")
        return slot

    def evict_adapter(self, name):
        """Explicitly evict a loaded adapter: its device slot zeroes and
        its in-flight sessions are reset and requeued at the head (they
        wait there — _AdapterUnavailable backpressure — and regenerate
        bit-identically once the adapter is loaded again). Returns the
        freed slot index; KeyError for an unknown name."""
        self._require_adapters()
        _fp.failpoint("serving/adapter")
        slot = self._adapters.evict(name)
        self._write_adapter_slot(slot, None)
        self._restart_adapter_sessions(name)
        self._adapter_versions.pop(name, None)
        return slot

    def _write_adapter_slot(self, slot, packed):
        """Write (packed) or zero (None) ONE slot of the stacked device
        factors — same-shape .at[slot].set updates only, so the decode
        programs never re-trace."""
        import jax.numpy as jnp

        lora = dict(self._lora)
        scale = 0.0 if packed is None else float(packed["scale"])
        lora["scale"] = lora["scale"].at[slot].set(scale)
        for kind in self._lora:
            if kind == "scale":
                continue
            fac = dict(self._lora[kind])
            for side in ("A", "B"):
                new = 0.0 if packed is None else jnp.asarray(
                    packed[kind][side], fac[side].dtype)
                fac[side] = fac[side].at[slot].set(new)
            lora[kind] = fac
        self._lora = lora

    def _restart_adapter_sessions(self, name):
        """An evicted adapter's in-flight sessions cannot keep decoding
        (their slot's factors just zeroed): free each session's blocks,
        reset it to its pre-admission state, and requeue it at the head.
        Deterministic decode (greedy, or the per-request seeded PRNG
        stream) regenerates the SAME tokens on re-admission, so an evict
        + reload mid-stream is invisible in the output."""
        for s in range(self.B):
            req = self._slot_req[s]
            if req is None or req.adapter != name:
                continue
            self._pool.free_slot(s)
            self._slot_req[s] = None
            self._prefilling.pop(s, None)
            self._adapter_slot[s] = 0
            req.output_ids.clear()
            req.first_token_time = None
            req.last_token_time = None
            req._inter_token = _MsSummary()
            self._queue.insert(0, req)

    def hot_swap(self, model, decode_model=None):
        """Replace the served weights IN PLACE with `model`'s — same
        architecture, same shapes/dtypes — without recompiling or
        dropping sessions, and bump the engine's weight version (origin
        ``hot_swap``). The params are step ARGUMENTS, not closure
        captures, so identically-shaped replacements reuse every warmed
        executable.

        Sessions already in flight keep decoding — each finishes under
        the replacement weights (a decode step already dispatched, whose
        tokens the next step() reads, ran under the old ones: the device
        orders the swap behind it) but CARRIES its submission-time version
        stamp, so its completion is attributable to the lineage it
        started on (and counts ``serving_stale_sessions_total`` under
        FLAGS_goodput). Requests submitted after the swap carry the
        bumped version. Returns the new :class:`WeightVersion`.

        Rejects tensor-parallel engines (the Megatron re-split would
        re-place device state mid-flight) and any replacement whose
        extracted param tree differs in keys, shapes, or dtypes — a
        mismatched swap must fail loudly BEFORE touching served state."""
        import jax.numpy as jnp

        if self._tp_mesh is not None:
            raise ValueError(
                "hot_swap does not compose with tp_mesh= serving — "
                "restart the engine to replace tensor-parallel weights")
        dm = _dm_registry.resolve(model, decode_model)
        if type(dm) is not type(self._dm):
            raise ValueError(
                f"hot_swap: replacement model resolves to decode adapter "
                f"{type(dm).__name__}, engine serves "
                f"{type(self._dm).__name__}")
        dm.check_config(model.cfg)
        params, _ = dm.extract_params(model, "the replacement model")
        if self._compute_dtype is not None:
            params = {k: (v.astype(self._compute_dtype)
                          if jnp.issubdtype(v.dtype, jnp.floating) else v)
                      for k, v in params.items()}
        if set(params) != set(self._params):
            missing = sorted(set(self._params) - set(params))
            extra = sorted(set(params) - set(self._params))
            raise ValueError(
                f"hot_swap: param tree mismatch (missing {missing[:3]}, "
                f"unexpected {extra[:3]}) — the replacement must be the "
                "same architecture")
        for k in sorted(params):
            new, cur = params[k], self._params[k]
            if tuple(new.shape) != tuple(cur.shape) \
                    or new.dtype != cur.dtype:
                raise ValueError(
                    f"hot_swap: param {k!r} is {new.shape}/{new.dtype}, "
                    f"engine serves {cur.shape}/{cur.dtype} — shapes and "
                    "dtypes must match exactly (no recompiles)")
        self._params = params
        self._weight_version = self._weight_version.bump("hot_swap")
        if self._goodput is not None:
            self._goodput.note_serving_version(
                self._weight_version.counter)
        _blackbox.note("hot_swap",
                       version=str(self._weight_version))
        return self._weight_version

    def _validate_decode_args(self, ids, max_new_tokens, temperature,
                              deadline_ms, top_k, top_p, seed):
        """Shared submit()/admit_prefilled() argument validation; returns
        the int-converted seed (None stays None)."""
        if max_new_tokens < 1:   # generate()'s own validation, mirrored
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        if temperature < 0:
            raise ValueError(f"temperature must be >= 0, got {temperature}")
        if deadline_ms is not None and not deadline_ms > 0:
            raise ValueError(f"deadline_ms must be > 0, got {deadline_ms}")
        if top_k is not None and top_k < 1:
            raise ValueError(f"top_k must be >= 1, got {top_k}")
        if top_p is not None and not 0.0 < top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        if seed is not None:
            # fail HERE, not at admission steps later: the PRNG fold takes
            # an int32 (mask a 64-bit time/hash seed yourself if desired)
            seed = int(seed)
            if not -2**31 <= seed < 2**31:
                raise ValueError(
                    f"seed must fit int32, got {seed} (mask with "
                    "& 0x7FFFFFFF for hash/time-derived seeds)")
        if len(ids) == 0:
            raise ValueError("empty prompt")
        return seed

    def _new_request(self, ids, max_new_tokens, temperature, top_k, top_p,
                     seed, prefix_id, prefix_len, deadline_ms, priority,
                     trace_id=None, parent_span=None, adapter=None):
        """Accepted-request factory shared by submit()/admit_prefilled():
        mints the rid, stamps submit_time, opens the trace spans (a
        router/pool passes its own trace_id — and optionally its routing
        span as parent — so one request's spans thread
        router -> engine -> slot), and counts the submission."""
        rid = self._next_rid
        self._next_rid += 1
        req = Request(rid, ids, max_new_tokens,
                      temperature=temperature, top_k=top_k,
                      top_p=top_p, seed=seed, prefix_id=prefix_id,
                      prefix_len=prefix_len, deadline_ms=deadline_ms,
                      priority=priority, adapter=adapter)
        req.submit_time = time.perf_counter()
        # lineage stamp (ISSUE 20): the version of the weights (and of
        # the selected adapter) this session will decode under — read at
        # finish to detect sessions that outlived a hot_swap
        req.weight_version = self._weight_version
        if adapter is not None:
            req.adapter_version = self._adapter_versions.get(adapter)
        if _trace.is_enabled():
            # end-to-end trace: every request gets a trace_id here; all
            # later spans (queue-wait, prefill chunks, per-step decode,
            # speculative, finish) parent back to this root span
            req.trace_id = trace_id or _trace.new_trace_id()
            req._span = _trace.start_span(
                "request", subsystem="serving", trace_id=req.trace_id,
                parent=parent_span, rid=rid, prompt_tokens=int(len(ids)),
                prefix_tokens=prefix_len, priority=priority)
            req._qspan = _trace.start_span(
                "queue_wait", subsystem="serving", parent=req._span)
        if deadline_ms is not None:
            self._deadline_live += 1
        self._m["submitted"] += 1
        _REQ_SUBMITTED.inc()
        return req

    def submit(self, prompt_ids, max_new_tokens=32, temperature=0.0,
               top_k=None, top_p=None, seed=None, prefix_id=None,
               deadline_ms=None, priority=0, trace_id=None,
               parent_span=None, adapter=None):
        """Queue a prompt; returns the request id. temperature=0 (default)
        decodes greedy; temperature>0 samples (optionally top_k- and/or
        top_p/nucleus-truncated, same semantics as generate()) with a
        per-request deterministic PRNG stream (seed defaults to the
        request id).

        deadline_ms: wall-clock budget from submit; an overdue request is
        finished with reason="deadline" at the next step() (batch-mates
        are untouched). priority: higher values outrank on a FULL bounded
        queue (max_queue=): the lowest-priority queued request is shed
        (reason="shed") to admit a strictly-higher-priority arrival;
        otherwise submit raises QueueFullError.

        trace_id/parent_span: a fronting Router propagates its per-request
        trace id (and its routing span) so the engine's spans join the
        router's trace instead of minting a fresh one.

        adapter: name of a LOADED LoRA adapter (FLAGS_paged_kv engines,
        load_adapter()); its low-rank delta applies to this request only,
        batched with every other adapter's requests in the same decode
        step — outputs are byte-identical to a dedicated engine serving
        the merged adapter. None = base weights."""
        if self._draining:
            raise RuntimeError(
                "ServingEngine is draining — not accepting new requests "
                "(in-flight work runs to completion; see drain())")
        if adapter is not None:
            self._require_adapters()
            if self._adapters.lookup(adapter) is None:
                raise ValueError(
                    f"adapter {adapter!r} is not loaded — load_adapter() "
                    f"it first (loaded: "
                    f"{sorted(self._adapters.loaded())})")
        ids = prompt_ids._data if isinstance(prompt_ids, Tensor) \
            else np.asarray(prompt_ids)
        ids = np.asarray(ids, np.int32).ravel()
        seed = self._validate_decode_args(ids, max_new_tokens, temperature,
                                          deadline_ms, top_k, top_p, seed)
        prefix_len = 0
        if prefix_id is not None:
            if prefix_id not in self._prefixes:
                raise ValueError(f"unknown prefix_id {prefix_id}")
            prefix_ids = self._prefixes[prefix_id][0]
            prefix_len = len(prefix_ids)
            # the request's logical prompt = prefix + suffix; only the
            # suffix will be prefilled (from the cached prefix KV)
            ids = np.concatenate([prefix_ids, ids])
        if len(ids) + 1 > self.T:
            raise ValueError(
                f"prompt ({len(ids)}) too long for max_seq_len {self.T}")
        if self._paged:
            # reject requests that can NEVER fit the pool up front: the
            # whole-budget reservation (reserve-before-compute) would
            # otherwise raise PagePoolFullError at every admission attempt
            # and the request would requeue forever
            need = self._pool.blocks_for(
                min(self.T, len(ids) + int(max_new_tokens)))
            cap = self._pool.stats()["n_blocks"] - 1   # frame 0 = null
            if need > cap:
                raise ValueError(
                    f"request needs {need} KV blocks but the page pool "
                    f"only has {cap}; raise page_blocks or shorten the "
                    "request")
        priority = int(priority)
        if self._max_queue is not None and \
                len(self._queue) + len(self._handoff) >= self._max_queue:
            # the bound covers BOTH admission backlogs (queue + prefilled
            # handoff rows) — matching admit_prefilled and health().
            # Shed the lowest-priority queued request (newest among ties —
            # it has the least sunk wait) iff the arrival strictly
            # outranks it; handoff rows are never shed (their prefill is
            # already paid); otherwise reject the arrival
            victim_idx = None
            for i, r in enumerate(self._queue):
                if victim_idx is None \
                        or r.priority <= self._queue[victim_idx].priority:
                    victim_idx = i
            if victim_idx is not None \
                    and self._queue[victim_idx].priority < priority:
                victim = self._queue.pop(victim_idx)
                self._finish_req(victim, "shed")
                _SHED.labels(reason="preempted").inc()
            else:
                _SHED.labels(reason="queue_full").inc()
                raise QueueFullError(
                    f"admission queue full ({len(self._queue)} queued "
                    f"+ {len(self._handoff)} handoff / {self._max_queue});"
                    " request rejected — retry later or submit with a "
                    "higher priority")
        req = self._new_request(ids, max_new_tokens, temperature, top_k,
                                top_p, seed, prefix_id, prefix_len,
                                deadline_ms, priority, trace_id=trace_id,
                                parent_span=parent_span, adapter=adapter)
        self._queue.append(req)
        return req.rid

    def admit_prefilled(self, prompt_ids, kv_row, logits,
                        max_new_tokens=32, temperature=0.0, top_k=None,
                        top_p=None, seed=None, deadline_ms=None,
                        priority=0, trace_id=None, parent_span=None):
        """Disaggregated prefill->decode handoff (docs/SERVING.md): admit
        a request whose prompt KV was ALREADY prefilled elsewhere.

        ``kv_row`` is the (kc1, vc1) single-row cache pair matching this
        engine's DecodeModel cache spec — i.e. produced by a
        ``serving.PrefillWorker`` (or another engine) built from the SAME
        adapter, config, dtype and cache_dtype. ``logits`` is the
        prompt's last-position vocab logits [V] (f32). The row waits in
        the handoff queue until a slot frees, then the standard admission
        tail runs: row copy into the big cache + first token through the
        same pick program submit()'s own prefill uses — outputs are
        bit-identical to submitting the prompt to this engine directly
        (pinned by tests/test_serving_disagg.py).

        Returns the request id. Raises while draining; a bounded engine
        (max_queue=) rejects with QueueFullError when queue + handoff
        backlogs are at the bound (no priority shedding across handoff
        rows — the producer should back off or pick another engine);
        speculative engines (draft_model=) do not compose with handoff
        (the draft's side cache was never prefilled)."""
        if self._draining:
            raise RuntimeError(
                "ServingEngine is draining — not accepting new requests "
                "(in-flight work runs to completion; see drain())")
        if self._draft is not None:
            raise RuntimeError(
                "admit_prefilled does not compose with speculative "
                "decoding (draft_model=): the handoff row carries no "
                "draft-model KV — disaggregate with a plain engine")
        if self._paged:
            raise RuntimeError(
                "admit_prefilled does not compose with FLAGS_paged_kv: "
                "the handoff row targets the dense big cache, a paged "
                "engine re-blocks prompts locally — disaggregate with "
                "dense decode engines")
        ids = prompt_ids._data if isinstance(prompt_ids, Tensor) \
            else np.asarray(prompt_ids)
        ids = np.asarray(ids, np.int32).ravel()
        seed = self._validate_decode_args(ids, max_new_tokens, temperature,
                                          deadline_ms, top_k, top_p, seed)
        if len(ids) + 1 > self.T:
            raise ValueError(
                f"prompt ({len(ids)}) too long for max_seq_len {self.T}")
        # typed transfer edge (ISSUE 13, docs/ANALYSIS.md): the row must
        # match the disagg_kv HANDOFF_SCHEMA — the SAME literal the
        # static auditor extracts and baselines — with symbolic dims
        # bound to THIS engine's cache. A drifted/misshaped row raises
        # here, naming the offending leaf, instead of corrupting a slot.
        kc1, vc1 = kv_row
        from ..analysis import handoff_schema as _hs
        from ..serving.disagg import HANDOFF_SCHEMA

        if self._described:
            # a described tree of state kinds: the row is held to the
            # one-slot tree this engine's own prefill makes, leaf by leaf
            _dm_registry.check_row(self._state_leaves, self._row_template,
                                   (kc1, vc1))
        else:
            side = self._kc[0] if isinstance(self._kc, tuple) else self._kc
            dims = {}
            if getattr(side, "ndim", 0) == 5:
                L, _, KVh, T, hd = side.shape
                dims = {"L": int(L), "KVh": int(KVh), "T": int(T),
                        "hd": int(hd)}
            vocab = getattr(self.cfg, "vocab_size", None)
            if vocab:
                dims["V"] = int(vocab)
            _hs.validate(HANDOFF_SCHEMA,
                         {"kc": kc1, "vc": vc1, "logits": logits},
                         dims=dims, dtypes={"cache": str(side.dtype)})
        # the bound check runs AFTER validation (matching submit()): an
        # unservable request must fail permanently (ValueError), never
        # masquerade as retryable backpressure
        if self._max_queue is not None \
                and len(self._queue) + len(self._handoff) >= self._max_queue:
            _SHED.labels(reason="queue_full").inc()
            raise QueueFullError(
                f"admission queue full ({len(self._queue)} queued + "
                f"{len(self._handoff)} handoff / {self._max_queue}); "
                "handoff rejected — back off or target another engine")
        req = self._new_request(ids, max_new_tokens, temperature, top_k,
                                top_p, seed, None, 0, deadline_ms,
                                int(priority), trace_id=trace_id,
                                parent_span=parent_span)
        self._handoff.append([req, kc1, vc1, logits])
        return req.rid

    def _bucket(self, n):
        for b in self._buckets:
            if n <= b:
                return b
        return self.T

    def _finish_req(self, req, reason, slot=None):
        """Terminal transition for a request wherever it lives: stamps the
        outcome, records it, and (slot given) frees the slot + any
        in-flight prefill reservation. Freed rows need no scrubbing — the
        next admission's row copy overwrites them (the invariant the whole
        engine rides on). A slot the lookahead loop released ahead of the
        request's last token (_release) may be another request's by now:
        it is left alone."""
        if slot is not None and self._slot_req[slot] not in (req, None):
            slot = None
        req.finished = True
        req.finish_reason = reason
        req.finish_time = time.perf_counter()
        if req._qspan is not None:   # finished while still queued
            req._qspan.end()
            req._qspan = None
        if req._span is not None:
            req._span.end(finish_reason=reason,
                          new_tokens=len(req.output_ids))
            req._span = None
        if req.deadline_ms is not None:
            self._deadline_live -= 1
        self._m["finished"][reason] = self._m["finished"].get(reason, 0) + 1
        _REQ_FINISHED.labels(reason=reason).inc()
        if (self._goodput is not None and req.weight_version is not None
                and req.weight_version.counter
                < self._weight_version.counter):
            # the session finished under weights older than what the
            # engine now serves (a hot_swap landed mid-stream) — exactly
            # once per stale finish (FLAGS_goodput, ISSUE 20)
            self._goodput.note_stale_session()
        self._finished[req.rid] = req
        if slot is not None:
            self._slot_req[slot] = None
            self._prefilling.pop(slot, None)
            # a free row rides along in every decode step: at column 0 it
            # costs the step one cache tile, not its last session's
            self._pos[slot] = 0
            if self._paged:
                # return the session's frames (shared prefix frames only
                # deref); no-op for a slot that never reserved
                self._pool.free_slot(slot)
                self._adapter_slot[slot] = 0

    def _note_error(self):
        self._last_error_step = self._step_no

    def cancel(self, rid):
        """Cancel a queued or in-flight request: it is finished immediately
        with reason="cancelled" and its slot (if any) freed for the next
        admission. Returns True if cancelled, False if the request had
        already finished; raises KeyError for an unknown id."""
        for i, req in enumerate(self._queue):
            if req.rid == rid:
                self._queue.pop(i)
                self._finish_req(req, "cancelled")
                return True
        for slot, entry in list(self._prefilling.items()):
            if entry[0].rid == rid:
                self._finish_req(entry[0], "cancelled", slot=slot)
                return True
        for entry in list(self._handoff):
            if entry[0].rid == rid:
                self._handoff.remove(entry)
                self._finish_req(entry[0], "cancelled")
                return True
        for slot in range(self.B):
            req = self._slot_req[slot]
            if req is not None and req.rid == rid:
                # a column the step in flight computes for it is discarded
                self._finish_req(req, "cancelled", slot=slot)
                return True
        for req in self._unread():
            if req.rid == rid:
                self._finish_req(req, "cancelled")
                return True
        if rid in self._finished:
            return False
        raise KeyError(f"unknown request id {rid}")

    def drain(self, stop=True):
        """Graceful-shutdown valve: stop admitting new requests (submit()
        raises) while queued and in-flight work runs to completion via
        step()/run_until_complete(). health() reports "draining" until
        drain(False) re-opens admission."""
        self._draining = bool(stop)

    def health(self):
        """Liveness verdict for load balancers: state is "draining" after
        drain(), "degraded" when a request finished with reason="error" in
        the last 100 steps or the bounded queue is at >= 80% depth, else
        "ok". Also wired into stats()["health"]. queue_depth counts BOTH
        admission backlogs — the regular queue and the prefilled-handoff
        queue — so a disaggregated decode engine can't look idle while
        holding a deep handoff backlog."""
        depth = len(self._queue) + len(self._handoff)
        state = "ok"
        if self._draining:
            state = "draining"
        else:
            recent_error = (self._last_error_step is not None
                            and self._step_no - self._last_error_step <= 100)
            q_pressure = (self._max_queue is not None and depth
                          >= max(1, int(0.8 * self._max_queue)))
            if recent_error or q_pressure:
                state = "degraded"
        return {"state": state,
                "queue_depth": depth,
                "queue_limit": self._max_queue,
                "active_slots": sum(1 for r in self._slot_req
                                    if r is not None),
                "errors": self._m["finished"].get("error", 0),
                "steps": self._step_no}

    def _expire_deadlines(self):
        """Finish every overdue request (reason="deadline") wherever it
        lives — queue, mid-prefill, or an active slot. Batch-mates are
        untouched: a freed slot is just another don't-care row until the
        next admission overwrites it. A request whose last token is on
        its way with the step in flight (_unread) is left to finish by
        it: under the serial loop it had finished a call ago."""
        if not self._deadline_live:
            return   # nothing carries a deadline: keep step() O(1) here
        now = time.perf_counter()

        def overdue(req):
            return (req.deadline_ms is not None
                    and (now - req.submit_time) * 1e3 > req.deadline_ms)

        for req in [r for r in self._queue if overdue(r)]:
            self._queue.remove(req)
            self._finish_req(req, "deadline")
            _DEADLINE.inc()
        for entry in [e for e in self._handoff if overdue(e[0])]:
            self._handoff.remove(entry)
            self._finish_req(entry[0], "deadline")
            _DEADLINE.inc()
        for slot, entry in list(self._prefilling.items()):
            if overdue(entry[0]):
                self._finish_req(entry[0], "deadline", slot=slot)
                _DEADLINE.inc()
        for slot in range(self.B):
            req = self._slot_req[slot]
            if req is not None and slot not in self._prefilling \
                    and overdue(req):
                self._finish_req(req, "deadline", slot=slot)
                _DEADLINE.inc()

    def _activate(self, slot, req, kc1, vc1, logits, draft_caches=None):
        """Shared admission tail: copy the side cache(s) into the slot's
        row and put the first generated token through the standard pick.
        Under the lookahead loop nothing here waits for the device."""
        n = len(req.prompt_ids)
        if self._paged:
            # HANDOFF_SCHEMA "kv_page_admit" producer site: the prefilled
            # dense row re-blocks into the slot's reserved PRIVATE frames
            # (shared prefix frames stay untouched — admit_row writes only
            # past the shared span)
            self._pool.admit_row(slot, kc1[:, 0], vc1[:, 0])
            self._adapter_slot[slot] = self._resolve_adapter_slot(
                req.adapter)
        else:
            self._kc = self._admit(self._kc, kc1, slot)
            self._vc = self._admit_second(self._vc, vc1, slot)
        if draft_caches is not None:
            kc1d, vc1d = draft_caches
            self._kc_d = self._admit(self._kc_d, kc1d, slot)
            self._vc_d = self._admit(self._vc_d, vc1d, slot)
        temp = np.float32(req.temperature)
        topk = np.int32(req.top_k or self.cfg.vocab_size)
        topp = np.float32(1.0 if req.top_p is None else req.top_p)
        seed = np.int32(req.seed)
        self._slot_req[slot] = req
        self._pos[slot] = n
        self._temps[slot] = temp
        self._topk[slot] = topk
        self._topp[slot] = topp
        self._seeds[slot] = seed
        # fold value = index of the context's last token (n-1), matching
        # the decode step's schedule (each emission folds a unique value)
        if self._lookahead:
            # the device half only: the host reads the token with the
            # round's others (_emit_round), and the next decode step takes
            # it from the device's vector
            tok, self._toks = self._pick1_put(
                self._toks, np.int32(slot), logits, temp, topk, topp, seed,
                np.int32(n - 1))
            self._firsts.append((slot, req, tok))
            if req.max_new_tokens == 1:
                self._release(slot)     # nothing more to dispatch for it
            return
        with _trace.phase("serve/prefill_wait"):
            tok = int(self._pick1(logits, temp, topk, topp, seed,
                                  np.int32(n - 1)))
        self._last[slot] = tok
        req.output_ids.append(tok)
        self._after_emit(slot, req)

    def _release(self, slot):
        """The lookahead loop has dispatched the last step the slot's
        request needs (`length` and `capacity` follow from counts the host
        has): the slot is free for the next admission at once, whose row
        copy the device orders behind that step; the request lives on in
        `_flight` / `_firsts` until its last token is read (_unread)."""
        self._slot_req[slot] = None
        self._pos[slot] = 0

    def _unread(self):
        """Requests that hold no slot any more and whose last token is on
        its way with the step in flight."""
        if self._flight is None:
            return []
        return [req for slot, req in self._flight.rows
                if not req.finished and self._slot_req[slot] is not req]

    def _note_admission(self, req):
        """Queue wait ends when admission work starts (prefill or slot
        reservation); prefix hit/miss is counted at the branch that
        actually decides reuse (_admit_one)."""
        req.admit_time = time.perf_counter()
        self._admitted[0] += 1
        self._admitted[1] += len(req.prompt_ids)
        wait_ms = (req.admit_time - req.submit_time) * 1e3 \
            if req.submit_time is not None else 0.0
        self._m["queue_wait_ms"].add(wait_ms)
        _QUEUE_WAIT_MS.observe(wait_ms)
        if req._qspan is not None:
            req._qspan.end(wait_ms=wait_ms)
            req._qspan = None

    def _chunk_plan(self, req):
        """(first column, chunk width) where a dense engine admits the
        request in chunks, None where it prefills the prompt whole.

        A request with a registered prefix starts at the prefix's end, in
        chunks of the engine's `prefill_chunk` or a default for prefix
        users; any other request of an engine with `prefill_chunk` starts
        at 0. Either falls back to the whole prompt (recomputing the
        prefix: slower but correct near the capacity edge) where the
        schedule's fixed-width last write would cross max_seq_len
        (dynamic_update_slice CLAMPS out-of-range starts, which would
        silently shift tokens onto valid columns)."""
        n = len(req.prompt_ids)
        if req.prefix_len and req.prefix_id in self._prefixes:
            # (unregistered while the request sat in the queue: the
            # combined prompt is already in prompt_ids)
            C = self._chunk or min(64, self.T)
            if req.prefix_len + -(-(n - req.prefix_len) // C) * C <= self.T:
                return req.prefix_len, C
        if self._chunk is not None and \
                -(-n // self._chunk) * self._chunk <= self.T:
            return 0, self._chunk
        return None

    def _admit_one(self, slot, req):
        with _blackbox.progress("serving/admit"):
            self._admit_one_inner(slot, req)

    def _admit_one_inner(self, slot, req):
        import jax.numpy as jnp

        if self._paged:
            return self._admit_one_paged(slot, req)
        n = len(req.prompt_ids)
        plan = self._chunk_plan(req)
        self._note_admission(req)
        if plan is not None and plan[0]:
            # suffix-only prefill from a COPY of the cached prefix KV
            # (the chunk program donates its cache args)
            prefix_len, C = plan
            self._m["prefix_hit"] += 1
            _PREFIX.labels(event="hit").inc()
            sp = None if req._span is None else _trace.start_span(
                "admit", subsystem="serving", parent=req._span,
                slot=slot, prefix="hit", prefix_tokens=prefix_len)
            try:
                _, kc_p, vc_p, kc_pd, vc_pd = self._prefixes[req.prefix_id]
                kc1 = self._copy_cache(kc_p)
                vc1 = self._copy_cache(vc_p)
                kc1d = vc1d = None
                if self._draft is not None:
                    kc1d = self._copy_cache(kc_pd)
                    vc1d = self._copy_cache(vc_pd)
                self._slot_req[slot] = req
                self._prefilling[slot] = [req, kc1, vc1, prefix_len, C,
                                          kc1d, vc1d]
            except BaseException:
                if sp is not None:
                    sp.end(error=True)
                raise
            if sp is not None:
                sp.end()
            return
        if req.prefix_len:   # wanted prefix reuse, got a full recompute
            self._m["prefix_miss"] += 1
            _PREFIX.labels(event="miss").inc()
        if plan is not None:
            # chunked admission: reserve the slot, consume the prompt one
            # chunk per step() so active decodes run in between
            self._slot_req[slot] = req
            kc1d = vc1d = None
            if self._draft is not None:
                kc1d, vc1d = self._draft_row()
            self._prefilling[slot] = [req, *self._prefill_start(), 0,
                                      plan[1], kc1d, vc1d]
            return
        # whole-prompt (bucketed) prefill
        pb = self._bucket(n)
        sp = None if req._span is None else _trace.start_span(
            "prefill", subsystem="serving", parent=req._span, slot=slot,
            tokens=n, bucket=pb)
        try:
            with _trace.phase("serve/prefill", slot=slot, tokens=n,
                              bucket=pb, true_len=n) as ph:
                padded = np.zeros((1, pb), np.int32)
                padded[0, :n] = req.prompt_ids
                kc1, vc1, logits = self._prefill(self._params,
                                                 jnp.asarray(padded),
                                                 np.int32(n))
                draft_caches = None
                if self._draft is not None:
                    draft_caches = self._draft_feed(self._params_d,
                                                    jnp.asarray(padded),
                                                    np.int32(0),
                                                    *self._draft_row())
                self._activate(slot, req, kc1, vc1, logits,
                               draft_caches=draft_caches)
        except BaseException:
            # the failing admission's span must still be recorded (the
            # request itself is finished reason="error" by step())
            if sp is not None:
                sp.end(error=True)
            raise
        self._acc_phase("prefill", ph)
        if sp is not None:
            sp.end()

    def _admit_one_paged(self, slot, req):
        """Paged admission: reserve the session's WHOLE block budget
        FIRST — a pool that cannot cover it raises PagePoolFullError
        here, before any prefill compute runs or any state mutates
        (_advance_and_admit turns that into requeue-at-head
        backpressure). A registered prefix under the SAME adapter maps
        its full blocks shared (refcount++, zero new bytes); a partial
        boundary block is re-blocked private (copy-on-write). Then one
        whole-prompt prefill (the request's adapter delta applied) and
        _activate re-blocks the row into the reserved private frames."""
        import jax.numpy as jnp

        aid = 0
        if req.adapter is not None:
            aid = None if self._adapters is None \
                else self._adapters.peek(req.adapter)
            if aid is None:
                raise _AdapterUnavailable(
                    f"adapter {req.adapter!r} is not loaded (evicted "
                    "mid-flight?) — the request waits at the queue head "
                    "for a reload")
        n = len(req.prompt_ids)
        shared, cow = (), False
        prefix_len = req.prefix_len
        entry = None
        if prefix_len and req.prefix_id in self._prefixes:
            entry = self._prefixes[req.prefix_id]
            if not (entry[1] == "paged" and entry[2] == req.adapter):
                entry = None   # foreign-adapter prefix: full recompute
        if entry is not None:
            # may raise PagePoolFullError while re-admitting cold pages —
            # before reserve(), so backpressure stays mutation-free
            frames = self._pool.prefix_frames(req.prefix_id)
            if frames:
                shared = frames
                cow = prefix_len % self._pool.bs != 0
        self._pool.reserve(slot, min(self.T, n + req.max_new_tokens),
                           shared_frames=shared, cow=cow)
        if prefix_len:   # counted only once reservation succeeds — a
            # backpressure retry must not inflate the hit rate
            ev = "hit" if shared else "miss"
            self._m[f"prefix_{ev}"] += 1
            _PREFIX.labels(event=ev).inc()
        self._note_admission(req)
        pb = self._bucket(n)
        sp = None if req._span is None else _trace.start_span(
            "prefill", subsystem="serving", parent=req._span, slot=slot,
            tokens=n, bucket=pb, paged=True)
        try:
            with _trace.phase("serve/prefill", slot=slot, tokens=n,
                              bucket=pb, true_len=n) as ph:
                padded = np.zeros((1, pb), np.int32)
                padded[0, :n] = req.prompt_ids
                kc1, vc1, logits = self._prefill_pg(
                    self._params, jnp.asarray(padded), np.int32(n),
                    self._lora, jnp.asarray([aid], np.int32))
                self._activate(slot, req, kc1, vc1, logits)
        except BaseException:
            if sp is not None:
                sp.end(error=True)
            raise
        self._acc_phase("prefill", ph)
        if sp is not None:
            sp.end()

    def _note_occupancy(self, active):
        self._m["occupancy_sum"] += len(active)
        self._m["occupancy_steps"] += 1
        _OCCUPANCY.set(len(active))

    def _count_kv_tiles(self, disp, one_token=True):
        """Onto the `serve/decode_dispatch` phase and the running sums: the
        cache tiles this round's step reads, those its attention writes
        back, and the tiles the cache holds. The step walks every row, a
        free one (position 0) as well. A speculative round's verify
        (several columns a row) reads all and stores by itself."""
        held = read = self.B * -(-self.T // self._kv_tile)
        cols = np.minimum(self._pos, self.T - 1)
        written = 0
        if one_token:
            read = int((cols // self._kv_tile + 1).sum())
            written = self._kv_tiles_written
        disp.counts.update(kv_tiles_read=read, kv_tiles_held=held,
                           kv_tiles_written=written)
        self._m["kv_tiles_read"] += read
        self._m["kv_tiles_held"] += held
        self._m["kv_tiles_written"] += written
        moved = self._m["state_bytes_moved"]
        for kind, nbytes in self._state_held.items():
            # live columns read and one a row written; fixed-size state
            # read and written whole
            step = (int(cols.sum()) + 2 * self.B) * self._kv_col_bytes \
                if kind == "kv" else 2 * nbytes
            disp.counts["state_bytes_" + kind] = step
            moved[kind] += step

    def _note_step_counts(self, disp, counts):
        """A decode step's own counts (the adapter's `step_counts`), read
        once its tokens are: onto the `serve/decode_dispatch` phase that
        dispatched it (the ring holds the phase's dict) and the sums of
        stats()."""
        values = np.asarray(counts).tolist()  # lint: allow(step-loop-host-sync)
        sums = self._m["step_counts"]
        for name, v in zip(self._count_names, values):
            disp.counts[name] = v
            sums[name] += v

    def _dispatch_decode(self, active):
        """Enqueue ONE decode program for the active slots (device work
        starts immediately — jax dispatch is asynchronous). Host-side
        dispatch: an all-greedy batch keeps the lean argmax step (no
        sort/categorical in its compiled program at all); inactive slots
        ride along harmlessly — their rows are don't-care (freed) and
        re-prefilled on admission. Returns (device tokens, kind)."""
        import jax.numpy as jnp

        if self._paged:
            # block tables + adapter ids ride to the device each round
            # (tiny int32 [B, maxb]/[B] uploads); the pool sides donate
            # through the step like the dense big cache
            pool = self._pool
            tables = pool.tables_device()
            aids = jnp.asarray(self._adapter_slot)
            if any(self._temps[s] > 0 for s in active):
                kind = "decode_sample"
                next_toks, pool.kp, pool.vp = self._step_sample_pg(
                    self._params, pool.kp, pool.vp, tables,
                    jnp.asarray(self._last), jnp.asarray(self._pos),
                    jnp.asarray(self._temps), jnp.asarray(self._topk),
                    jnp.asarray(self._topp), jnp.asarray(self._seeds),
                    self._lora, aids)
            else:
                kind = "decode_greedy"
                next_toks, pool.kp, pool.vp = self._step_greedy_pg(
                    self._params, pool.kp, pool.vp, tables,
                    jnp.asarray(self._last), jnp.asarray(self._pos),
                    self._lora, aids)
            self._count_step(kind)
            return next_toks, kind
        def up(a):
            # a COPY goes up: the host writes these vectors again (the
            # positions right after this dispatch, the knobs at the next
            # admission) while the step may not have read them yet, and a
            # CPU backend's device array can alias the numpy buffer
            return jnp.asarray(a.copy())

        # the lookahead loop's input tokens never left the device
        last = self._toks if self._lookahead else up(self._last)
        if any(self._temps[s] > 0 for s in active):
            kind = "decode_sample"
            next_toks, self._kc, self._vc, *counts = self._step_sample(
                self._params, self._kc, self._vc, last, up(self._pos),
                up(self._temps), up(self._topk), up(self._topp),
                up(self._seeds))
        else:
            kind = "decode_greedy"
            next_toks, self._kc, self._vc, *counts = self._step_greedy(
                self._params, self._kc, self._vc, last, up(self._pos))
        # a family's own counts of the step (device values: read with the
        # tokens, _note_step_counts)
        self._step_counts = counts[0] if counts else None
        self._count_step(kind)
        return next_toks, kind

    def _apply_decode(self, active, next_toks, kind, t0_ns, t1_ns):
        """The serial loop's emit (the lookahead loop's is _emit_round):
        one fetched round's tokens slot by slot. Per-slot
        failures isolate (the failing request finishes reason="error");
        the slot-level decode span attributes the batched device step's
        window to each request."""
        for s in active:
            req = self._slot_req[s]
            try:
                _fp.failpoint("serving/slot")
                self._pos[s] += 1
                self._last[s] = next_toks[s]
                req.output_ids.append(int(next_toks[s]))
                if req._span is not None:
                    _trace.emit("decode", t0_ns, t1_ns,
                                subsystem="serving", parent=req._span,
                                slot=s, pos=int(self._pos[s]),
                                kind=kind, token=int(next_toks[s]))
                self._after_emit(s, req)
            except Exception:
                if self._slot_req[s] is not None:
                    self._finish_req(req, "error", slot=s)
                self._note_error()

    def _advance_and_admit(self):
        """The round's admission window, shared by both loops: advance
        the chunked prefill in flight ONE chunk (so active decodes never
        wait for more than a chunk), then admit queued/handoff requests
        into free slots; a queued request that would prefill in chunks
        waits while another does. Per-request failures isolate: the
        failing request finishes reason="error" and the pass
        continues."""
        for slot in list(self._prefilling):
            req = self._prefilling[slot][0]
            try:
                self._advance_prefill(slot)
            except Exception:
                self._finish_req(req, "error", slot=slot)
                self._note_error()
        for slot in range(self.B):
            # while, not if: a request finishing DURING admission (eos on
            # its prefill token / max_new_tokens=1) frees the slot for the
            # next queued request in the same pass. Handoff rows admit
            # FIRST — their prefill is already paid, holding them behind
            # un-prefilled queue entries would waste the disaggregation
            while self._slot_req[slot] is None and (self._handoff
                                                    or self._queue):
                if self._handoff:
                    req, kc1, vc1, logits = self._handoff.pop(0)
                    try:
                        with _blackbox.progress("serving/admit"):
                            self._note_admission(req)
                            with _trace.phase("serve/handoff_admit",
                                              slot=slot) as ph:
                                self._activate(slot, req, kc1, vc1, logits)
                            self._acc_phase("handoff_admit", ph)
                    except Exception:
                        self._finish_req(req, "error", slot=slot)
                        self._note_error()
                        continue
                else:
                    if self._prefilling and \
                            self._chunk_plan(self._queue[0]) is not None:
                        # one slot prefills in chunks at a time: a round
                        # carries one chunk and one side row is held. The
                        # queue's head waits for that slot's last chunk
                        return
                    req = self._queue.pop(0)
                    try:
                        self._admit_one(slot, req)
                    except Exception as e:
                        if self._paged and isinstance(
                                e, (self._paging.PagePoolFullError,
                                    _AdapterUnavailable)):
                            # admission BACKPRESSURE, not a failure: the
                            # pool cannot cover the request's whole block
                            # budget (or its adapter was evicted and not
                            # yet reloaded). Nothing ran and nothing was
                            # reserved — requeue at the head and stop
                            # admitting this round; finishing sessions
                            # free blocks for the retry
                            self._queue.insert(0, req)
                            return
                        # half-done admission must not leak a reservation
                        self._finish_req(req, "error", slot=slot)
                        self._note_error()
                        continue
                if self._slot_req[slot] is not None:
                    break

    def _advance_prefill(self, slot):
        """Consume one chunk of a reserved slot's prompt; on the final
        chunk, activate the slot."""
        import jax.numpy as jnp

        req, kc1, vc1, off, C, kc1d, vc1d = self._prefilling[slot]
        self._count_step("prefill_chunk")
        sp = None if req._span is None else _trace.start_span(
            "prefill_chunk", subsystem="serving", parent=req._span,
            slot=slot, offset=off, width=C)
        n = len(req.prompt_ids)
        end = min(off + C, n)
        try:
            with _trace.phase("serve/prefill_chunk", slot=slot, offset=off,
                              width=C) as ph:
                chunk = np.zeros((1, C), np.int32)
                chunk[0, :end - off] = req.prompt_ids[off:end]
                kc1, vc1, logits = self._prefill_chunk(
                    self._params, jnp.asarray(chunk), np.int32(off), kc1,
                    vc1, np.int32(end - off - 1))
                if self._draft is not None:
                    kc1d, vc1d = self._draft_feed(self._params_d,
                                                  jnp.asarray(chunk),
                                                  np.int32(off), kc1d, vc1d)
                tile = self._chunk_kv_tile(C)
                walked = 0 if tile is None else \
                    tile[1] * -(-min(off + C, self.T) // tile[0])
                ph.counts["chunk_kv_tiles"] = walked
                self._m["chunk_kv_tiles"] += walked
                if end >= n:
                    del self._prefilling[slot]
                    self._slot_req[slot] = None   # _activate re-binds
                    self._activate(slot, req, kc1, vc1, logits,
                                   draft_caches=(None if self._draft is None
                                                 else (kc1d, vc1d)))
                else:
                    self._prefilling[slot] = [req, kc1, vc1, end, C, kc1d,
                                              vc1d]
        except BaseException:
            if sp is not None:   # record the failing chunk's span too
                sp.end(error=True)
            raise
        self._acc_phase("prefill_chunk", ph)
        if sp is not None:
            sp.end(consumed=end)

    def _after_emit(self, slot, req):
        now = time.perf_counter()
        gap_ms = req._note_token(now)
        self._m["tokens"] += 1
        _TOKENS.inc()
        if gap_ms is None:  # first generated token: TTFT
            if req.submit_time is not None:
                ttft = (now - req.submit_time) * 1e3
                self._m["ttft_ms"].add(ttft)
                _TTFT_MS.observe(ttft)
        else:
            self._m["inter_token_ms"].add(gap_ms)
            _ITL_MS.observe(gap_ms)
        if self.eos is not None and req.output_ids[-1] == self.eos:
            reason = "eos"
        elif len(req.output_ids) >= req.max_new_tokens:
            reason = "length"
        elif len(req.prompt_ids) + len(req.output_ids) > self.T:
            reason = "capacity"     # next write column out of cache
        else:
            return
        self._finish_req(req, reason, slot=slot)

    def step(self):
        """Admit queued requests into free slots, then run ONE decode step
        for every active slot. Returns requests finished this step.

        A dense engine without a draft model keeps one decode step in
        flight (_step_inner_lookahead): the step this call dispatches is
        read by the next call, and the tokens this call emits are those of
        the step the call before dispatched. Streams, counts and finish
        reasons do not change by it; a token may show one call later.

        Per-request failure isolation: host-side per-slot work (admission,
        chunked-prefill advance, token emission) that throws finishes ONLY
        that slot's request with reason="error" and evicts it — the rest
        of the batch continues. A failure in the batched device program
        itself is not isolatable (one executable) and propagates."""
        # window beacon around the WHOLE step (the failpoint delay
        # included): a thread wedged anywhere inside leaves an active,
        # non-advancing site for the stall sentinel to name — and a
        # finished sibling engine cannot mask it, because the site only
        # deactivates when the LAST open step window closes
        with _blackbox.progress("serving/step"):
            root = _trace.phase("serve/step", queued=len(self._queue))
            try:
                with root:
                    tokens0 = self._m["tokens"]
                    admitted0 = self._admitted[0]
                    done = self._step_inner(root)
                    root.counts.update(
                        admitted=self._admitted[0] - admitted0,
                        emitted=self._m["tokens"] - tokens0,
                        finished=len(done))
                    return done
            finally:
                if self._perf_ledger is not None:
                    self._ledger_round(root.ms)

    def _ledger_round(self, step_ms):
        """Armed-only (FLAGS_perf_ledger) per-round feed: the regression
        sentinel sees every round's wall ms; every
        FLAGS_perf_ledger_interval-th round appends the full
        stats()['breakdown'] ledger row (per-kind step ms, executed
        device flops, queue-wait/TTFT/inter-token digests)."""
        led = self._perf_ledger
        led.observe("serving", {"step_ms": step_ms})
        self._perf_rounds += 1
        if self._perf_rounds % led.interval == 0:
            from ..monitor import perfledger as _perfledger

            _perfledger.record_engine(self, ledger=led)

    def _paged_active(self):
        """Construction-consumed FLAGS_paged_kv vs the live flag: a
        post-construction disarm under a live paged engine raises (there
        is no dense cache to fall back to). Dense engines short-circuit —
        they never read the flag per step."""
        if self._paged and not _flags.get_flag("paged_kv", False):
            raise RuntimeError(
                "FLAGS_paged_kv was disarmed under a live paged engine — "
                "the flag is consumed at ENGINE CONSTRUCTION; build a new "
                "engine instead of toggling it mid-flight")
        return self._paged

    def _step_inner(self, root):
        if self._paged_active():
            # cold-page sweep rides the step cadence: registry-only prefix
            # frames untouched for page_cold_steps sweeps compress to int8
            # host pages (host bookkeeping; no device sync)
            self._pool.sweep()
        if self._lookahead:
            return self._step_inner_lookahead(root)
        return self._step_inner_sync(root)

    def _admit_phase(self):
        """The round's admission window as one `serve/admit` phase: what
        every decoding row waits through."""
        admitted0, tokens0 = self._admitted
        with _trace.phase("serve/admit") as ph:
            self._advance_and_admit()
            ph.counts.update(admitted=self._admitted[0] - admitted0,
                             prompt_tokens=self._admitted[1] - tokens0)
        return ph

    def _step_inner_lookahead(self, root):
        """One round with a decode step kept in flight (docs/SERVING.md
        "The decode loop"): the engine's only loop for a dense cache
        without a draft model. A call that finds step N running does this
        round's admissions, dispatches step N+1 from the token vector
        step N leaves ON THE DEVICE (admissions lay their first token over
        it there), and only then reads and emits step N's tokens: when it
        returns, N+1 is running, under the caller's bookkeeping too. A
        call that finds nothing in flight dispatches one step first, so
        every call has a round to emit.

        What the host can foresee it does not compute: a row that the
        step in flight brings to `max_new_tokens` or the cache's capacity
        is left out of the next dispatch and its slot released at once
        (_release). What it cannot foresee (eos, cancel, deadline, a
        per-slot error) it learns one step late: that row's column of the
        step in flight is computed and DISCARDED, never emitted, and lands
        in a freed row, which is don't-care until an admission overwrites
        it (the row copy is ordered on the device behind the step).

        Per-request token streams, counts and finish reasons are those of
        the serial loop bit for bit (a row's step depends on its own cache
        row, position, last token and knobs, and the positions stay the
        host's); a request admitted while a step is in flight sees its
        tokens one step() call later than there."""
        _fp.failpoint("serving/step")
        self._step_no += 1
        before = set(self._finished)
        # after the snapshot: deadline expiries belong to THIS step's
        # returned finishes, same as error/eos/length
        self._expire_deadlines()
        self._admit_phase()
        if self._flight is None:
            self._flight = self._dispatch_ahead()
        flight, self._flight = self._flight, self._dispatch_ahead()
        firsts, self._firsts = self._firsts, []
        rows = () if flight is None else flight.rows
        self._note_occupancy(rows)
        root.counts["active"] = len(rows)
        self._emit_round(flight, firsts)
        return [self._finished[r] for r in set(self._finished) - before]

    def _decoding_slots(self):
        return [s for s in range(self.B)
                if self._slot_req[s] is not None
                and s not in self._prefilling]

    def _dispatch_ahead(self):
        """Dispatch one decode step for the rows with a token still to
        come and advance the host's positions past it; returns what
        `_flight` holds, or None with no such row. Nothing here waits for
        the device."""
        active = self._decoding_slots()
        if not active:
            return None
        ahead = int(self._flight is not None)
        with _trace.phase("serve/decode_dispatch", in_flight=ahead) as disp:
            self._toks, kind = self._dispatch_decode(active)
            self._count_kv_tiles(disp)
        la = self._m["lookahead"]
        la["rounds"] += 1
        la["rounds_overlapped"] += ahead
        rows = [(s, self._slot_req[s]) for s in active]
        for s, req in rows:
            self._pos[s] += 1
            n_tokens = self._pos[s] - len(req.prompt_ids) + 1
            if n_tokens >= req.max_new_tokens or self._pos[s] >= self.T:
                self._release(s)    # `length` or `capacity`, foreseen
        return _Flight(self._toks, rows, kind, disp, self._step_counts)

    def _emit_round(self, flight, firsts):
        """The lookahead round's reads and its emit: the tokens of the
        step dispatched a round ago (`serve/decode_wait`) and of this
        round's admissions (`serve/prefill_wait`), then every token
        through the standard _after_emit, a request's first before its
        next. Per-slot failures isolate (the failing request finishes
        reason="error")."""
        import jax

        if flight is not None:
            # THE round's host sync, with the next step already running
            with _trace.phase("serve/decode_wait") as wait:
                toks = np.asarray(flight.toks).tolist()  # lint: allow(step-loop-host-sync)
                if flight.counts is not None:
                    # computed by the same program: there with the tokens
                    self._note_step_counts(flight.disp, flight.counts)
            self._acc_phase(flight.kind, flight.disp, wait)
            decode = (flight.disp.start_ns, wait.end_ns, flight.kind)
        if firsts:
            with _trace.phase("serve/prefill_wait"):
                first_toks = jax.device_get(  # lint: allow(step-loop-host-sync)
                    [tok for _, _, tok in firsts])
        discarded = 0
        with _trace.phase("serve/emit") as emit:
            for i, (slot, req, _) in enumerate(firsts):
                if not req.finished:    # (its admission failed half-way)
                    self._emit_token(slot, req, int(first_toks[i]))
            for slot, req in (() if flight is None else flight.rows):
                if req.finished:
                    discarded += 1
                else:
                    self._emit_token(slot, req, toks[slot], decode)
            ahead = self._flight
            if ahead is not None and all(r.finished for _, r in ahead.rows):
                # nobody is left to read the step just dispatched (the
                # last rows ended by eos or an error): a drain ends with
                # nothing in flight
                discarded += len(ahead.rows)
                self._flight = None
            emit.counts["discarded"] = discarded
        self._m["lookahead"]["tokens_discarded"] += discarded

    def _emit_token(self, slot, req, tok, decode=None):
        """One read token to its request (`decode`: the step's window and
        kind, for the slot-level span that attributes the batched device
        step to each request)."""
        try:
            if decode is not None:
                _fp.failpoint("serving/slot")
            req.output_ids.append(tok)
            if decode is not None and req._span is not None:
                _trace.emit("decode", decode[0], decode[1],
                            subsystem="serving", parent=req._span,
                            slot=slot, kind=decode[2], token=tok,
                            pos=len(req.prompt_ids) + len(req.output_ids)
                            - 1)
            self._after_emit(slot, req)
        except Exception:
            if not req.finished:
                self._finish_req(req, "error", slot=slot)
            self._note_error()

    def _step_inner_sync(self, root):
        import jax.numpy as jnp

        _fp.failpoint("serving/step")
        self._step_no += 1
        before = set(self._finished)
        # after the snapshot: deadline expiries belong to THIS step's
        # returned finishes, same as error/eos/length
        self._expire_deadlines()
        # chunked admissions in flight advance ONE chunk each, so active
        # decodes below never wait for a whole long prefill
        self._admit_phase()

        active = self._decoding_slots()
        self._note_occupancy(active)
        root.counts["active"] = len(active)
        if active:
            # speculative round: every active slot greedy AND spec_k+1
            # columns of headroom (near-capacity slots fall back to exact
            # single-token steps — junk writes past T would clamp)
            if (self._draft is not None
                    and all(self._temps[s] == 0 for s in active)
                    and all(int(self._pos[s]) + self._spec_k + 1 <= self.T
                            for s in active)):
                self._step_speculative(active)
                return [self._finished[r]
                        for r in set(self._finished) - before]
            # fallback (single-token) step with a draft around: mirror the
            # fed token into the draft cache so later speculative rounds
            # see an intact context (review r5: without this, one sampling
            # neighbor permanently cold-starts every survivor's draft)
            with _trace.phase("serve/decode_dispatch") as disp:
                if self._draft is not None:
                    self._kc_d, self._vc_d = self._draft_sync(
                        self._params_d, self._kc_d, self._vc_d,
                        jnp.asarray(self._last), jnp.asarray(self._pos))
                next_toks, kind = self._dispatch_decode(active)
                self._count_kv_tiles(disp)
            with _trace.phase("serve/decode_wait") as wait:
                next_toks = np.asarray(next_toks)  # lint: allow(step-loop-host-sync)
            self._acc_phase(kind, disp, wait)
            with _trace.phase("serve/emit"):
                self._apply_decode(active, next_toks, kind, disp.start_ns,
                                   wait.end_ns)
        return [self._finished[r] for r in set(self._finished) - before]

    def _step_speculative(self, active):
        """One speculative round for all active (greedy) slots: K draft
        proposals per slot, one batched (K+1)-token target verify at
        per-slot positions, 1..K+1 tokens emitted per slot. Tokens are
        appended one at a time through the standard _after_emit, so
        eos/length finishing matches the single-token engine exactly;
        junk positions on freed/mid-prefill rows ride along like every
        other batched step. Clamping the draft's junk-row writes is safe
        for the same reason admission row-copies are: those rows are
        fully overwritten before they are read."""
        import jax.numpy as jnp

        self._count_step("speculative")
        with _trace.phase("serve/decode_dispatch") as disp:
            props, self._kc_d, self._vc_d = self._draft_propose(
                self._params_d, self._kc_d, self._vc_d,
                jnp.asarray(self._last), jnp.asarray(self._pos))
            t_draft_ns = time.perf_counter_ns()   # draft | verify boundary
            emit, m, self._kc, self._vc = self._verify(
                self._params, self._kc, self._vc, jnp.asarray(self._last),
                jnp.asarray(self._pos), props)
            self._count_kv_tiles(disp, one_token=False)
        with _trace.phase("serve/decode_wait") as wait:
            emit = np.asarray(emit)  # lint: allow(step-loop-host-sync)
            m = np.asarray(m)  # lint: allow(step-loop-host-sync)
        t0_ns, t1_ns = disp.start_ns, wait.end_ns
        self._acc_phase("speculative", disp, wait)
        if _trace.is_enabled():
            _trace.emit("spec_draft", t0_ns, t_draft_ns,
                        subsystem="serving", slots=len(active),
                        k=self._spec_k)
            _trace.emit("spec_verify", t_draft_ns, t1_ns,
                        subsystem="serving", slots=len(active))
        proposed = self._spec_k * len(active)
        accepted = int(sum(int(m[s]) for s in active))
        self._m["spec_proposed"] += proposed
        self._m["spec_accepted"] += accepted
        _SPEC.labels(event="proposed").inc(proposed)
        _SPEC.labels(event="accepted").inc(accepted)
        with _trace.phase("serve/emit"):
            for s in active:
                req = self._slot_req[s]
                try:
                    _fp.failpoint("serving/slot")
                    n_acc = int(m[s]) + 1
                    toks = emit[s, :n_acc]
                    old_pos = int(self._pos[s])
                    self._last[s] = int(toks[-1])
                    if req._span is not None:
                        _trace.emit("decode", t0_ns, t1_ns,
                                    subsystem="serving", parent=req._span,
                                    slot=s, pos=old_pos, kind="speculative",
                                    accepted=int(m[s]), emitted=n_acc)
                    for i, t in enumerate(toks):
                        # advance pos PER TOKEN so _after_emit's eos/length/
                        # capacity decisions are made at exactly the state the
                        # single-token engine would have seen
                        self._pos[s] = old_pos + i + 1
                        req.output_ids.append(int(t))
                        self._after_emit(s, req)
                        if req.finished:
                            break
                except Exception:
                    if self._slot_req[s] is not None:
                        self._finish_req(req, "error", slot=s)
                    self._note_error()

    def has_work(self):
        return bool(self._queue) or bool(self._handoff) \
            or any(r is not None for r in self._slot_req) \
            or bool(self._unread())     # tokens computed and not read

    def run_until_complete(self, max_steps=100_000):
        """Drain the queue; returns {rid: Request}. Non-convergence fails
        every in-flight request with reason="engine_stalled" (nothing is
        left dangling for callers polling get_request) and raises with
        their rids."""
        steps = 0
        while self.has_work():
            self.step()
            steps += 1
            if steps > max_steps:
                stalled = []
                # the dump captures the wedge's live state; the finishes
                # below rewrite it, so write the bundle FIRST
                dump_path = None
                if _blackbox.is_enabled():
                    dump_path = _blackbox.dump(
                        "stall", site="serving/step",
                        extra={"trigger": "run_until_complete",
                               "max_steps": max_steps})
                for req in list(self._queue):
                    self._queue.remove(req)
                    self._finish_req(req, "engine_stalled")
                    stalled.append(req.rid)
                for entry in list(self._handoff):
                    self._handoff.remove(entry)
                    self._finish_req(entry[0], "engine_stalled")
                    stalled.append(entry[0].rid)
                for slot, entry in list(self._prefilling.items()):
                    self._finish_req(entry[0], "engine_stalled", slot=slot)
                    stalled.append(entry[0].rid)
                for slot in range(self.B):
                    req = self._slot_req[slot]
                    if req is not None:
                        self._finish_req(req, "engine_stalled", slot=slot)
                        stalled.append(req.rid)
                for req in self._unread():
                    self._finish_req(req, "engine_stalled")
                    stalled.append(req.rid)
                self._flight = None     # its columns are nobody's now
                raise RuntimeError(
                    "serving engine did not converge within "
                    f"{max_steps} steps; failed in-flight requests "
                    f"{sorted(set(stalled))} with reason='engine_stalled'"
                    + (f"; blackbox dump bundle: {dump_path}"
                       if dump_path else ""))
        return dict(self._finished)
