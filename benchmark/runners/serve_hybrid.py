"""Serving cells of the `solar_open2` family: the serving runner's loop
(runners/serve.py: the closed loop, the window's counters, the sample that
decides `correct`) around a model built by the family's own constructor.

What differs from runners/serve.py: the model (SolarOpen2ForCausalLM, built
in the cell's dtype with the benchmark's weights handed to its constructor
leaf by leaf, so that 3.3 B parameters are never held twice), the engine's
`max_seq_len` and prompt buckets (the workload's and the mix's), the plain
reference (reference/solar_open2.py, one sequence at a time, padded to a
power of two so that a handful of shapes compile), and the counters the
family's own per-layer metrics read: the engine's `moe_*` counts and
`state_bytes` over the window (stats() at both ends).
"""
import jax
import numpy as np

from benchmark import compare, hybrid_counts, hybrid_weights, traffic
from benchmark.reference import solar_open2 as reference
from benchmark.runners import serve

#: the family's per-layer metrics that BENCHMARK.json cannot list yet (PERF.md
#: section 7): read as run.py would read them (benchmark/metrics/<name>.json
#: names the reader and its params) and noted under run.notes of every run
READY = ("step_mfu.serve_hybrid", "step_hbm_share.serve_hybrid",
         "moe_rows_padded.serve")


def program_config(cfg, max_seq_len):
    """The program's config from the configuration file's published keys."""
    from paddle_tpu.models.solar_open2 import SolarOpen2Config

    lin, dep = cfg["linear_attn_config"], cfg.get("deployment", {})
    return SolarOpen2Config(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], gqa_layers=cfg["gqa_layers"],
        kda_num_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
        short_conv_kernel_size=lin["short_conv_kernel_size"],
        kda_rank=cfg["assumed"]["kda_low_rank"],
        n_routed_experts=dep.get("n_routed_experts_published",
                                 cfg["n_routed_experts"]),
        held_experts=dep.get("held_experts"),
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        n_shared_experts=cfg["n_shared_experts"],
        norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        rms_norm_eps=cfg["rms_norm_eps"], max_seq_len=max_seq_len)


class Runner(serve.Runner):
    # -- set-up --------------------------------------------------------------
    def setup(self):
        import paddle_tpu as paddle
        from paddle_tpu.inference.serving import ServingEngine
        from paddle_tpu.models.solar_open2 import SolarOpen2ForCausalLM

        ctx, wl, cfg, mix = self.ctx, self.ctx.workload, self.ctx.cfg, \
            self.ctx.mix
        paddle.seed(ctx.seed)
        init = hybrid_weights.initializer(cfg, ctx.seed, round_to=wl["dtype"])
        model = SolarOpen2ForCausalLM(
            program_config(cfg, int(wl["max_seq_len"])), initializer=init,
            dtype=wl["dtype"])
        if init.missing():
            raise KeyError("the program never asked for "
                           f"{init.missing()[:4]}")
        model.eval()
        ctx.mark("model_and_weights_s")
        self.model = model
        self.max_batch = int(wl["max_batch"])
        lengths = sorted({n for n, _ in traffic.request_sizes(mix)})
        buckets, b = [], 1 << (lengths[0] - 1).bit_length()
        while b < lengths[-1]:
            buckets.append(b)
            b *= 2
        buckets.append(b)
        self.eng = eng = ServingEngine(model, max_batch=self.max_batch,
                                       dtype=wl["dtype"],
                                       prompt_buckets=tuple(buckets))
        ctx.mark("engine_s")
        self.steps_called = 0
        eng.warmup(batch_shapes=lengths, sampling=False)
        ctx.mark("warmup_s")
        # execute every program once: one short request through every
        # prompt bucket
        rng = np.random.default_rng([ctx.seed, 4])
        for n in buckets:
            eng.submit(rng.integers(0, cfg["vocab_size"],
                                    (min(n, lengths[-1]),), dtype=np.int32),
                       max_new_tokens=2)
        while eng.has_work():
            self._eng_step()
        ctx.mark("burst_s")
        self.source = self._requests()
        self.clients = [None] * int(mix["clients"])   # [req, seen, last_t]
        for c in range(len(self.clients)):
            self._send(c)
        self._loop(float(mix["lead_seconds"]), record=False)
        ctx.mark("lead_in_s")

    def _requests(self):
        """The mix's requests. Where the mix says `"order": "size_seed"`, the
        generator orders the pool from the mix's own `size_seed` and --seed
        draws the ids alone: every seed then sends the same sizes in the same
        order. Replies here last 128-2,048 steps and a window about 1,050, so
        what a window holds is set by the order of the first 256 requests;
        ordered by the seed, one program on one seed twice read the same to
        0.002 % and on six seeds 2.2 % apart (PERF.md, PR 34)."""
        ctx, mix, vocab = self.ctx, self.ctx.mix, self.ctx.cfg["vocab_size"]
        if mix.get("order") != "size_seed":
            return traffic.requests(mix, vocab, ctx.seed)
        rng = np.random.default_rng([ctx.seed, 3])
        return ((rng.integers(0, vocab, prompt.shape, dtype=np.int32), new)
                for prompt, new in traffic.requests(mix, vocab,
                                                    mix["size_seed"]))

    def window(self, seconds):
        before = self.eng.stats()
        k = super().window(seconds)
        after = self.eng.stats()
        for name in ("moe_assignments", "moe_assignments_held",
                     "moe_rows_computed", "moe_experts_touched"):
            if name in after:
                k[name] = after[name] - before[name]
        if "moe_rows_computed" in k:
            k["moe_rows_padded"] = k["moe_rows_computed"] \
                - k["moe_assignments_held"]
        moved0, moved1 = (s.get("state_bytes", {}).get("moved", {})
                          for s in (before, after))
        k["state_bytes_moved"] = {kind: moved1[kind] - moved0.get(kind, 0)
                                  for kind in moved1}
        k["state_bytes_held"] = dict(
            after.get("state_bytes", {}).get("held", {}))
        return k

    # -- what decides `correct` ----------------------------------------------
    def gaps(self, precision=None):
        """As runners/serve.py's: per sampled request the gaps of its
        served tokens below the float32 reference's best (with `precision`
        the reference at that precision in the program's place)."""
        cfg = self.ctx.cfg
        P = hybrid_weights.flat(cfg, self.ctx.seed,
                                round_to=self.ctx.workload["dtype"])
        D = reference.dims_of(cfg)
        out = []
        for prompt, tokens in self.sample():
            n = len(prompt) + len(tokens)
            ids = np.zeros((max(512, 1 << (n - 1).bit_length()),), np.int32)
            ids[:n] = np.concatenate([prompt, tokens])
            ref = np.asarray(reference.sequence_logits(
                P, jax.numpy.asarray(ids), D, "float32"))
            if precision is not None:
                low = np.asarray(reference.sequence_logits(
                    P, jax.numpy.asarray(ids), D, precision))
                tokens = low[len(prompt) - 1: n - 1].argmax(-1)
            out.append(compare.token_gaps(ref, len(prompt), tokens))
        return out

    def check(self):
        # the family's per-layer metrics, which no manifest entry reads yet
        import importlib
        import json
        import os

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
        self.ctx.notes["work"] = hybrid_counts.work(self.ctx.cfg,
                                                    self.ctx.counters)
        # the program's step phases, which only PR 27's metrics would note
        # and their lists cannot take this cell (PERF.md section 7)
        from benchmark.readers import phase_idle, program_phase

        rows = phase_idle.window_phases(self.ctx)
        if rows:
            program_phase.note_medians(self.ctx, rows)
        return super().check()
