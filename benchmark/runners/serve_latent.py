"""Serving cells of the `axk1` family: the serving runner's loop
(runners/serve.py: the closed loop, the window's counters, the sample that
decides `correct`) and the other expert family's window counts and fixed order
of requests (runners/serve_hybrid.py: the engine's `moe_*` counts and
`state_bytes` over the window, `"order": "size_seed"`) around a model built by
this family's own constructor.

What differs from runners/serve_hybrid.py: the model (AXK1ForCausalLM, built
in the cell's dtype with benchmark/latent_weights.py's leaves handed to its
constructor one by one), the engine's admission (`prefill_chunk` of the
workload's file: every prompt enters in chunks between decode steps, one
slot at a time and one chunk a round, so the only prefill program is the
chunk's and no whole-prompt bucket is compiled),
the plain reference (reference/axk1.py, one sequence at a time, padded to a
power of two so that a handful of shapes compile), the count of chunks in the
window, and the per-layer metrics that wait for a manifest entry.
"""
import jax
import numpy as np

from benchmark import compare, latent_counts, latent_weights, traffic
from benchmark.reference import axk1 as reference
from benchmark.runners import serve_hybrid

#: the family's per-layer metrics that BENCHMARK.json cannot list yet (PERF.md
#: section 7): read as run.py would read them (benchmark/metrics/<name>.json
#: names the reader and its params) and noted under run.notes of every run
READY = ("step_mfu.serve_latent", "step_hbm_share.serve_latent",
         "prefill_share.serve", "latent_decode_attention_roofline",
         "moe_rows_padded.serve", "kv_read_share.serve")


def program_config(cfg, max_seq_len):
    """The program's config from the configuration file's published keys."""
    from paddle_tpu.models.axk1 import AXK1Config

    dep = cfg.get("deployment", {})
    return AXK1Config(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        first_k_dense_replace=cfg["first_k_dense_replace"],
        num_attention_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        n_routed_experts=dep.get("n_routed_experts_published",
                                 cfg["n_routed_experts"]),
        held_experts=dep.get("held_experts"),
        num_experts_per_tok=cfg["num_experts_per_tok"],
        n_shared_experts=cfg["n_shared_experts"],
        norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        scoring_func=cfg["scoring_func"], topk_method=cfg["topk_method"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        rope_scaling=cfg.get("rope_scaling"), max_seq_len=max_seq_len)


class Runner(serve_hybrid.Runner):
    in_flight = ()      # (prompt ids, tokens served by the window's end)

    # -- set-up --------------------------------------------------------------
    def setup(self):
        import paddle_tpu as paddle
        from paddle_tpu.inference.serving import ServingEngine
        from paddle_tpu.models.axk1 import AXK1ForCausalLM

        ctx, wl, cfg, mix = self.ctx, self.ctx.workload, self.ctx.cfg, \
            self.ctx.mix
        paddle.seed(ctx.seed)
        init = latent_weights.initializer(cfg, ctx.seed, round_to=wl["dtype"])
        ctx.mark("router_balance_s")
        model = AXK1ForCausalLM(
            program_config(cfg, int(wl["max_seq_len"])), initializer=init,
            dtype=wl["dtype"])
        if init.missing():
            raise KeyError("the program never asked for "
                           f"{init.missing()[:4]}")
        model.eval()
        ctx.mark("model_and_weights_s")
        self.model = model
        self.max_batch = int(wl["max_batch"])
        chunk = int(wl["prefill_chunk"])
        lengths = sorted({n for n, _ in traffic.request_sizes(mix)})
        if -(-lengths[-1] // chunk) * chunk > int(wl["max_seq_len"]):
            raise ValueError("the mix's longest prompt would leave the "
                             "chunked path for a whole-prompt prefill")
        self.eng = eng = ServingEngine(model, max_batch=self.max_batch,
                                       dtype=wl["dtype"],
                                       prompt_buckets=(chunk,),
                                       prefill_chunk=chunk)
        ctx.mark("engine_s")
        self.steps_called = 0
        # every prompt enters through the chunk's program: no bucket to warm
        eng.warmup(batch_shapes=(), sampling=False)
        ctx.mark("warmup_s")
        # execute every program once: a one-chunk prompt and the longest,
        # whose later chunks read the columns before them
        rng = np.random.default_rng([ctx.seed, 4])
        for n in (lengths[0], lengths[-1]):
            eng.submit(rng.integers(0, cfg["vocab_size"], (n,),
                                    dtype=np.int32), max_new_tokens=2)
        while eng.has_work():
            self._eng_step()
        ctx.mark("burst_s")
        self.source = self._requests()
        self.clients = [None] * int(mix["clients"])   # [req, seen, last_t]
        for c in range(len(self.clients)):
            self._send(c)
        self._loop(float(mix["lead_seconds"]), record=False)
        ctx.mark("lead_in_s")

    def window(self, seconds):
        chunks0 = self.eng.stats()["steps"].get("prefill_chunk", 0)
        k = super().window(seconds)
        # serve.Runner counts every kind of step but whole-prompt prefills
        # as a decode step: the chunks are not
        k["prefill_chunks"] = k["steps"].get("prefill_chunk", 0) - chunks0
        k["decode_steps"] -= k["prefill_chunks"]
        # what the requests still in flight had been served when the window
        # closed (`sample` has the use)
        self.in_flight = [(req.prompt_ids, list(req.output_ids))
                          for req, _, _ in self.clients
                          if not req.finished and len(req.output_ids) >= 8]
        # the result line counts the operations a run attempted, and a run
        # that attempted none is no result. Where no reply ended inside the
        # window (the 4 s of a traced run) the operations are the replies
        # it served tokens of, the ones `sample` draws from; none of them
        # has failed, or the loop would have counted it finished
        k["requests_finished"] = k["attempted"]
        if not k["attempted"]:
            k["attempted"] = len(self.in_flight)
        return k

    def sample(self):
        """runners/serve.py's sample of the requests the window finished.
        A window in which none did (a traced run's 4 s, 12 s after 128
        prompts of 2,500 tokens began to enter in chunks: the first reply
        ends some 17 s in) samples the requests in flight at its close
        instead, each by the tokens it had been served by then: what the
        timed path produced, held to the same reference."""
        if self.finished or not self.in_flight:
            return super().sample()
        done, self.finished = self.finished, self.in_flight
        try:
            return super().sample()
        finally:
            self.finished = done

    # -- what decides `correct` ----------------------------------------------
    def gaps(self, precision=None, faults=()):
        """As runners/serve.py's: per sampled request the gaps of its
        served tokens below the float32 reference's best (with `precision`
        or `faults` the reference at that precision, or with those parts of
        the mathematics left out, in the program's place)."""
        cfg = self.ctx.cfg
        P = latent_weights.flat(cfg, self.ctx.seed,
                                round_to=self.ctx.workload["dtype"])
        D = reference.dims_of(cfg)
        out = []
        for prompt, tokens in self.sample():
            n = len(prompt) + len(tokens)
            ids = np.zeros((max(512, 1 << (n - 1).bit_length()),), np.int32)
            ids[:n] = np.concatenate([prompt, tokens])
            ref = np.asarray(reference.sequence_logits(
                P, jax.numpy.asarray(ids), D, "float32"))
            if precision is not None or faults:
                low = np.asarray(reference.sequence_logits(
                    P, jax.numpy.asarray(ids), D, precision or "float32",
                    tuple(faults)))
                tokens = low[len(prompt) - 1: n - 1].argmax(-1)
            out.append(compare.token_gaps(ref, len(prompt), tokens))
        return out

    def check(self):
        # the family's per-layer metrics, which no manifest entry reads yet
        import importlib
        import json
        import os

        from benchmark import run, tracing
        from benchmark.readers import phase_idle, program_phase

        if "trace_stop_s" in self.ctx.notes and self.ctx.trace is None \
                and self.ctx.devices[0].platform == "tpu":
            # a traced run: run.py loads the profile after this check, and
            # one of the metrics read here is a kernel's device time (on
            # a chip alone: elsewhere the step takes the einsums, and the
            # default directory may be another process's, half written)
            try:
                self.ctx.trace = tracing.load(tracing.newest_xplane(
                    os.path.join(run.ROOT, ".bench_trace")), self.ctx.chips)
            except FileNotFoundError:   # kept elsewhere (a test's own place)
                pass
        ready = {}
        for name in READY:
            with open(os.path.join(os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))), "metrics",
                    name + ".json")) as f:
                spec = json.load(f)
            value = importlib.import_module(
                "benchmark.readers." + spec["reader"]).read(
                    self.ctx, spec.get("params", {}))
            if value is not None:
                ready[name] = value
        self.ctx.notes["per_layer_without_an_entry"] = ready
        self.ctx.notes["work"] = latent_counts.work(self.ctx.cfg,
                                                    self.ctx.counters)
        # the program's step phases, which only PR 27's metrics would note
        # and their lists cannot take this cell (PERF.md section 7)
        rows = phase_idle.window_phases(self.ctx)
        if rows:
            program_phase.note_medians(self.ctx, rows)
        return self.numbers(self.gaps())

    @staticmethod
    def numbers(per_request):
        """What `correct` compares, from the sampled requests' gaps: the
        widest (`token_gap`, as the other serving cells have it) and the
        MEAN over all compared tokens (`token_gap_mean`). The widest gap of
        this family is set by single events of a size no precision changes
        (a token whose 8th and 9th router scores swap takes or loses a held
        expert whatever made them swap), so the program and the reference
        computed in fp8 read only twice apart by it; the mean follows how
        OFTEN a served token is not the reference's best, which is what a
        lower precision changes (PERF.md section 2)."""
        if not per_request:
            return {"token_gap": float("inf"),
                    "token_gap_mean": float("inf"), "tokens_compared": 0}
        gaps = np.concatenate(per_request)
        return {"token_gap": float(gaps.max()),
                "token_gap_mean": float(gaps.mean()),
                "tokens_compared": int(gaps.size)}
