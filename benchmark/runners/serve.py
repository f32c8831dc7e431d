"""Serving cells: ServingEngine.submit + ServingEngine.step, dense KV cache,
greedy, under the closed loop the traffic mix describes.

Set-up builds the engine, compiles its programs from shapes
(`eng.warmup`, the cell's prompt buckets only), sends one short request
through every bucket, then starts the closed loop and lets it run for the
mix's `lead_seconds` so that the window opens on a steady system: slots at
every stage of their replies, not 32 prefills in a row. The window goes on
with the same loop and the same engine.

`correct`: once the window has closed and the engine is freed, a sample of
the requests the window finished (the longest always among them), drawn
from the seed; the plain reference runs once over each prompt with its
served tokens, and the number compared is the widest gap by which a served
token's logit lies below the reference's best.
"""
import time

import jax
import numpy as np

from benchmark import compare, reduce, traffic, weights
from benchmark.reference import gpt as reference


class Runner:
    def __init__(self, ctx):
        self.ctx = ctx
        self.eng = self.model = None
        self.finished = []       # (prompt ids, served tokens) of the window

    # -- set-up --------------------------------------------------------------
    def setup(self):
        import paddle_tpu as paddle
        from paddle_tpu.inference.serving import ServingEngine
        from paddle_tpu.models import GPTConfig, GPTForCausalLM

        ctx, wl, cfg, mix = self.ctx, self.ctx.workload, self.ctx.cfg, \
            self.ctx.mix
        paddle.seed(ctx.seed)
        model = GPTForCausalLM(GPTConfig(
            vocab_size=weights.dims(cfg)[2], hidden_size=cfg["n_embd"],
            num_layers=cfg["n_layer"], num_heads=cfg["n_head"],
            max_seq_len=cfg["n_positions"], dropout=0.0,
            gelu_approx=cfg["activation_function"] == "gelu_new"))
        ctx.mark("model_constructor_s")
        made = weights.flat(cfg, ctx.seed, round_to=wl["dtype"])
        for name, p in model.named_parameters():
            p.set_value(made.pop(name))
        model.eval()
        self.model = model
        self.max_batch = int(wl["max_batch"])
        self.eng = eng = ServingEngine(model, max_batch=self.max_batch,
                                       dtype=wl["dtype"])
        ctx.mark("weights_and_engine_s")
        self.steps_called = 0
        lengths = sorted({n for n, _ in traffic.request_sizes(mix)})
        eng.warmup(batch_shapes=lengths, sampling=False)
        ctx.mark("warmup_s")
        # execute every program once: one short request through every
        # prompt bucket (doubling lengths reach each power-of-two bucket)
        rng = np.random.default_rng([ctx.seed, 4])
        n, burst = lengths[0], []
        while n < lengths[-1]:
            burst.append(n)
            n *= 2
        for n in burst + [lengths[-1]]:
            eng.submit(rng.integers(0, cfg["vocab_size"], (n,),
                                    dtype=np.int32), max_new_tokens=2)
        while eng.has_work():
            self._eng_step()
        ctx.mark("burst_s")
        self.source = traffic.requests(mix, cfg["vocab_size"], ctx.seed)
        self.clients = [None] * int(mix["clients"])   # [req, seen, last_t]
        for c in range(len(self.clients)):
            self._send(c)
        self._loop(float(mix["lead_seconds"]), record=False)
        ctx.mark("lead_in_s")

    def _eng_step(self):
        self.steps_called += 1
        return self.eng.step()

    def _send(self, c):
        prompt, new = next(self.source)
        rid = self.eng.submit(prompt, max_new_tokens=int(new))
        self.clients[c] = [self.eng.get_request(rid), 0, None]

    # -- the loop that the lead-in and the window share ----------------------
    def _loop(self, seconds, record):
        spans = self.ctx.spans
        k = {"new_tokens": 0, "prompt_tokens": 0, "prompt_sq": 0,
             "ctx_tokens": 0, "attempted": 0, "failed": 0}
        gaps = []
        t_open = time.perf_counter()
        while True:
            now = time.perf_counter()
            if now - t_open >= seconds:
                break
            with spans.span("eng.step"):
                self._eng_step()
            now = time.perf_counter()
            with spans.span("bookkeeping"):
                for c, slot in enumerate(self.clients):
                    req, seen, last = slot
                    n = len(req.output_ids)
                    if n > seen:
                        plen = len(req.prompt_ids)
                        if seen == 0:
                            k["prompt_tokens"] += plen
                            k["prompt_sq"] += plen * plen
                        else:
                            gaps.append(now - last)
                        # every token after the prompt's own first one
                        # attended to its whole context through the cache
                        k["ctx_tokens"] += sum(
                            plen + j for j in range(max(seen, 1), n))
                        gaps.extend([0.0] * (n - seen - 1))
                        k["new_tokens"] += n - seen
                        slot[1], slot[2] = n, now
                    if req.finished:
                        k["attempted"] += 1
                        ok = (req.finish_reason == "length"
                              and n == req.max_new_tokens)
                        k["failed"] += 0 if ok else 1
                        if record and ok:
                            self.finished.append(
                                (req.prompt_ids, list(req.output_ids)))
                        with spans.span("submit"):
                            self._send(c)
        k["window_s"] = time.perf_counter() - t_open
        k["t_open"] = t_open
        k["gaps"] = gaps
        return k

    def window(self, seconds):
        eng = self.eng
        before = eng.stats()
        calls0 = self.steps_called
        k = self._loop(seconds, record=True)
        after = eng.stats()
        gaps = k.pop("gaps")
        calls = self.steps_called - calls0
        # occupancy is noted once a step() call: the window's average from
        # the engine's lifetime averages at both ends
        occ = (after["batch_occupancy_avg"] * self.steps_called
               - before["batch_occupancy_avg"] * calls0) / max(calls, 1)
        k.update(
            decode_steps=sum(after["steps"].values())
            - sum(before["steps"].values())
            - (after["steps"].get("prefill", 0)
               - before["steps"].get("prefill", 0)),
            steps=dict(after["steps"]), engine_step_calls=calls,
            occupancy_avg=occ, max_batch=self.max_batch,
            itl_samples=len(gaps),
            end_to_end={
                "serve_tokens_per_s": k["new_tokens"] / k["window_s"],
                "serve_itl_p95_ms": 1e3 * (reduce.percentile(gaps, 95)
                                           or float("nan"))})
        return k

    def release(self):
        self.eng = self.model = None
        self.clients = None

    # -- what decides `correct` ----------------------------------------------
    def sample(self):
        """The requests to compare: the longest the window finished, and
        others drawn from the seed, `check_requests` in all."""
        want = int(self.ctx.mix["check_requests"])
        done = self.finished
        if not done:
            return []
        longest = max(range(len(done)),
                      key=lambda i: len(done[i][0]) + len(done[i][1]))
        rng = np.random.default_rng([self.ctx.seed, 5])
        rest = [i for i in rng.permutation(len(done)) if i != longest]
        return [done[i] for i in [longest] + rest[:want - 1]]

    def gaps(self, precision=None):
        """Per sampled request the gaps of its served tokens below the
        float32 reference's best. With `precision` the reference at that
        precision stands in the program's place: at each position the gap
        of the token that IT puts first."""
        cfg, mix = self.ctx.cfg, self.ctx.mix
        P = weights.stacked(cfg, self.ctx.seed,
                            round_to=self.ctx.workload["dtype"])
        pad = mix["prompt_len"]["max"] + mix["new_tokens"]["max"]
        args = (cfg["n_head"], cfg["layer_norm_epsilon"])
        out = []
        for prompt, tokens in self.sample():
            ids = np.zeros((pad,), np.int32)
            n = len(prompt) + len(tokens)
            ids[:n] = np.concatenate([prompt, tokens])
            ref = np.asarray(reference.sequence_logits(
                P, jax.numpy.asarray(ids), *args, "float32"))
            if precision is not None:
                low = np.asarray(reference.sequence_logits(
                    P, jax.numpy.asarray(ids), *args, precision))
                rows = slice(len(prompt) - 1, n - 1)
                tokens = low[rows].argmax(-1)
            out.append(compare.token_gaps(ref, len(prompt), tokens))
        return out

    def check(self):
        per_request = self.gaps()
        if not per_request:
            return {"token_gap": float("inf"), "tokens_compared": 0}
        return {"token_gap": float(max(g.max() for g in per_request)),
                "tokens_compared": int(sum(len(g) for g in per_request))}
