"""The serving engine's decode loop with one step in flight
(`ServingEngine._step_inner_lookahead`, docs/SERVING.md "The decode loop"):
step N+1 is dispatched from the tokens step N left on the device, and the
host reads step N's tokens while N+1 runs. The bar: every request's token
stream, token count and finish reason are what the serial loop gave (greedy
rows: a solo `model.generate`; sampling rows: the streams the engine gave
before PR 33, recorded here), nothing is emitted after a finish, a drain
leaves nothing in flight, and `stats()["lookahead"]` accounts for every
round."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.inference.serving import ServingEngine
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.serving.disagg import PrefillWorker

EOS = 10      # g31 meets it in mid-stream, g40 and s9 as their last token


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                    num_heads=2, max_seq_len=96, dropout=0.0)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


def _generate(m, prompt, n, eos=None):
    """A solo greedy generate, cut after the first `eos`: (tokens, reason)."""
    out = m.generate(paddle.to_tensor(prompt[None]), max_new_tokens=n,
                     temperature=0.0)
    toks = np.asarray(out._data)[0, len(prompt):].tolist()
    if eos is not None and eos in toks:
        return toks[:toks.index(eos) + 1], "eos"
    return toks, "length"


def _mixed(m):
    """The mixed run's requests, by name: (prompt, submit keywords). Prompts
    fall into both buckets (32, 64); `max_new_tokens` runs from 1 up."""
    rng = np.random.RandomState(7)

    def p(n):
        return rng.randint(0, 64, (n,)).astype(np.int32)

    return {
        "g5": (p(5), dict(max_new_tokens=9)),
        "g40": (p(40), dict(max_new_tokens=12)),
        "one": (p(7), dict(max_new_tokens=1)),
        "two": (p(33), dict(max_new_tokens=2)),
        "s9": (p(9), dict(max_new_tokens=10, temperature=0.9, seed=11)),
        "s20k": (p(20), dict(max_new_tokens=8, temperature=0.7, top_k=8,
                             seed=5)),
        "s12p": (p(12), dict(max_new_tokens=7, temperature=1.1, top_p=0.8,
                             seed=3)),
        "g31": (p(31), dict(max_new_tokens=20)),
        "g3": (p(3), dict(max_new_tokens=16)),
        "cancelled": (p(6), dict(max_new_tokens=30)),
        "late": (p(8), dict(max_new_tokens=30, deadline_ms=1e9)),
        "handoff": (p(11), dict(max_new_tokens=6)),
        "handoff_s": (p(14), dict(max_new_tokens=5, temperature=0.8,
                                  seed=21)),
        "prefixed": (p(4), dict(max_new_tokens=6)),
    }


def _run_mixed(m, **engine_kw):
    """Drive the mixed run through one engine: staggered submissions, a
    prefix hit, two rows prefilled elsewhere, a cancel and a deadline while
    their rows decode. Returns (engine, {name: request})."""
    reqs = _mixed(m)
    eng = ServingEngine(m, max_batch=3, eos_token_id=EOS, **engine_kw)
    rid = {}

    def submit(name, **more):
        prompt, kw = reqs[name]
        rid[name] = eng.submit(prompt, **kw, **more)

    for name in ("g5", "g40", "one", "cancelled", "s9"):
        submit(name)
    eng.step()
    eng.step()
    pid = eng.register_prefix(reqs["g5"][0])
    submit("prefixed", prefix_id=pid)
    for name in ("two", "s20k", "late"):
        submit(name)
    worker = PrefillWorker(m, cache_dtype=engine_kw.get("cache_dtype"))
    for name in ("handoff", "handoff_s"):
        prompt, kw = reqs[name]
        kv_row, logits = worker.prefill(prompt)
        rid[name] = eng.admit_prefilled(prompt, kv_row, logits, **kw)
    calls = 0
    while eng.has_work():
        eng.step()
        calls += 1
        victim = eng.get_request(rid["cancelled"])
        if len(victim.output_ids) >= 3 and not victim.finished:
            seen = list(victim.output_ids)
            assert eng.cancel(rid["cancelled"]) is True
            assert victim.output_ids == seen
        late = eng.get_request(rid["late"])
        if len(late.output_ids) >= 4 and not late.finished:
            late.deadline_ms = 1e-6         # overdue at the next step()
        if calls == 6:
            for name in ("s12p", "g31", "g3"):
                submit(name)
        assert calls < 500
    return eng, {name: eng.get_request(r) for name, r in rid.items()}


# what the engine gave for the sampling rows before PR 33 (the serial loop,
# commit 7029a3f, this scenario, plain and chunked alike): seeded sampling
# folds (seed, position), so a stream depends on nothing around it
RECORDED = {
    "s9": ([7, 40, 40, 13, 18, 5, 33, 57, 21, 10], "eos"),
    "s20k": ([44, 44, 44, 29, 42, 42, 42, 42], "length"),
    "s12p": ([28, 22, 20, 57, 40, 54, 28], "length"),
    "handoff_s": ([55, 42, 51, 21, 44], "length"),
}


class TestStreams:
    @pytest.mark.parametrize("engine_kw", [
        {}, {"prefill_chunk": 8}, {"cache_dtype": "int8"}],
        ids=["whole_prompt", "chunked", "int8_cache"])
    def test_mixed_run_matches_solo_generate_and_recorded(self, model,
                                                          engine_kw):
        eng, got = _run_mixed(model, **engine_kw)
        reqs = _mixed(model)
        # an int8 cache parts from generate's float32 one: its rows are
        # held to the same engine serving each request alone
        alone = "cache_dtype" in engine_kw
        for name, req in got.items():
            prompt, kw = reqs[name]
            assert req.finished, name
            full = prompt if name != "prefixed" else \
                np.concatenate([reqs["g5"][0], prompt])
            if alone:
                solo = ServingEngine(model, max_batch=1, eos_token_id=EOS,
                                     **engine_kw)
                r = solo.submit(full, **kw)
                r = solo.run_until_complete()[r]
                want, reason = r.output_ids, r.finish_reason
            elif name in RECORDED:
                want, reason = RECORDED[name]
            else:
                want, reason = _generate(model, full, kw["max_new_tokens"],
                                         EOS)
            if name in ("cancelled", "late"):
                # cut short from outside: a prefix of the stream, and
                # nothing computed after the finish was appended
                n = len(req.output_ids)
                assert req.finish_reason == {"cancelled": "cancelled",
                                             "late": "deadline"}[name]
                assert 3 <= n < len(want) and req.output_ids == want[:n]
                continue
            assert (req.output_ids, req.finish_reason) == (want, reason), \
                name
            assert len(req.output_ids) <= kw["max_new_tokens"]
            assert EOS not in req.output_ids[:-1]
        # the mix holds what it says it does
        reasons = {r.finish_reason for r in got.values()}
        assert reasons == {"length", "eos", "cancelled", "deadline"}
        st = eng.stats()
        assert st["prefix_cache"]["hit"] == 1
        if "prefill_chunk" in engine_kw:
            assert st["steps"]["prefill_chunk"] > 10
        assert st["steps"]["decode_sample"] > 0 < st["steps"]["decode_greedy"]
        # (b) the drain left nothing in flight and nothing unread, and the
        # counter accounts for every round
        la = st["lookahead"]
        assert eng._flight is None and not eng._firsts
        assert not eng.has_work() and la["in_flight"] == 0
        assert la["rounds"] == st["steps"]["decode_greedy"] + \
            st["steps"]["decode_sample"]
        assert 0 < la["rounds_overlapped"] < la["rounds"]
        # the eos rows, the cancel and the deadline were each learnt with a
        # step in flight
        assert la["tokens_discarded"] >= 3
        assert st["tokens_generated"] == sum(len(r.output_ids)
                                             for r in got.values())
        assert st["requests"]["running"] == 0

    def test_a_token_shows_at_most_one_call_late(self, model):
        """A row admitted while a step is in flight joins the NEXT
        dispatch: first token in its admission's call, as ever, and from
        then on one token a call."""
        eng = ServingEngine(model, max_batch=2)
        a = eng.submit(np.arange(5, dtype=np.int32), max_new_tokens=8)
        eng.step()
        ra = eng.get_request(a)
        assert len(ra.output_ids) == 2      # nothing was in flight: as ever
        b = eng.submit(np.arange(9, dtype=np.int32), max_new_tokens=8)
        eng.step()
        rb = eng.get_request(b)
        assert len(ra.output_ids) == 3
        assert len(rb.output_ids) == 1      # the serial loop showed 2
        for k in range(2, 6):
            eng.step()
            assert (len(ra.output_ids), len(rb.output_ids)) == (2 + k, k)
        res = eng.run_until_complete()
        for rid, n in ((a, 5), (b, 9)):
            want, _ = _generate(model, np.arange(n, dtype=np.int32), 8)
            assert res[rid].output_ids == want


class TestFinishes:
    def _solo(self, model, n_new, eos=None, prompt=(3, 1, 4, 1, 5)):
        prompt = np.asarray(prompt, np.int32)
        eng = ServingEngine(model, max_batch=2, eos_token_id=eos)
        rid = eng.submit(prompt, max_new_tokens=n_new)
        res = eng.run_until_complete()
        return eng, res[rid], _generate(model, prompt, n_new, eos)

    def test_an_eos_discards_one_column_and_emits_nothing_after(self, model):
        """(c) The host learns an `eos` one step late: the row's column of
        the step in flight is computed, discarded, never emitted."""
        eng, req, (want, reason) = self._solo(
            model, 12, eos=EOS, prompt=_mixed(model)["g31"][0])
        assert reason == "eos" and len(want) == 7 and EOS not in want[:6]
        assert (req.output_ids, req.finish_reason) == (want, "eos")
        la = eng.stats()["lookahead"]
        assert la["tokens_discarded"] == 1
        # 6 decode steps gave tokens, the 7th was in flight as eos was read
        assert la["rounds"] == eng.stats()["steps"]["decode_greedy"] == 7
        assert eng._flight is None and not eng.has_work()

    @pytest.mark.parametrize("n_new", [1, 2, 3, 7])
    def test_a_length_finish_dispatches_no_extra_step(self, model, n_new):
        """(d) `length` follows from counts the host has: the step counts
        are the serial loop's, one decode step a token after the first."""
        eng, req, (want, _) = self._solo(model, n_new)
        assert (req.output_ids, req.finish_reason) == (want, "length")
        st = eng.stats()
        assert st["steps"].get("decode_greedy", 0) == n_new - 1
        assert st["lookahead"]["rounds"] == n_new - 1
        assert st["lookahead"]["tokens_discarded"] == 0
        # ... and no call more than the serial loop took
        assert st["health"]["steps"] == max(1, n_new - 1)

    def test_capacity_is_foreseen_too(self, model):
        prompt = np.arange(90, dtype=np.int32) % 64
        eng = ServingEngine(model, max_batch=2)
        rid = eng.submit(prompt, max_new_tokens=50)
        req = eng.run_until_complete()[rid]
        # columns 90..95 are written, and the token picked after the last
        assert req.finish_reason == "capacity" and len(req.output_ids) == 7
        want, _ = _generate(model, prompt, 6)
        assert req.output_ids[:6] == want
        st = eng.stats()
        assert st["lookahead"]["tokens_discarded"] == 0
        assert st["steps"]["decode_greedy"] == 6

    def test_a_released_slot_is_admitted_into_at_once(self, model):
        """A row whose last step is in flight gives up its slot: the next
        request's row copy is ordered on the device behind that step, and
        the finishing request is still found until its token is read."""
        prompts = [np.asarray(p, np.int32)
                   for p in ([1, 2, 3], [4, 5, 6, 7], [8, 9])]
        eng = ServingEngine(model, max_batch=1)
        rids = [eng.submit(p, max_new_tokens=3) for p in prompts]
        eng.step()      # r0: first token + step 1 read, step 2 in flight
        r0 = eng.get_request(rids[0])
        assert len(r0.output_ids) == 2 and not r0.finished
        assert eng._slot_req == [None] and eng._unread() == [r0]
        assert eng.stats()["requests"]["running"] == 1 and eng.has_work()
        eng.step()      # r1 takes the slot in the call that reads r0's last
        assert r0.finished and len(r0.output_ids) == 3
        assert eng.get_request(rids[1]).output_ids
        res = eng.run_until_complete()
        for rid, p in zip(rids, prompts):
            assert res[rid].output_ids == _generate(model, p, 3)[0]
        st = eng.stats()
        assert st["lookahead"]["tokens_discarded"] == 0
        assert st["steps"]["decode_greedy"] == 6
        # a full queue behind one slot never left it idle
        assert st["batch_occupancy_avg"] == 1.0

    def test_cancel_finds_a_request_whose_last_token_is_unread(self, model):
        eng = ServingEngine(model, max_batch=1)
        rid = eng.submit(np.asarray([1, 2, 3], np.int32), max_new_tokens=3)
        eng.step()
        req = eng.get_request(rid)
        assert eng._unread() == [req]
        assert eng.cancel(rid) is True and eng.cancel(rid) is False
        assert req.finish_reason == "cancelled" and len(req.output_ids) == 2
        assert not eng.has_work()
        # the column in flight is nobody's: the next call discards it
        nxt = eng.submit(np.asarray([4, 5], np.int32), max_new_tokens=2)
        res = eng.run_until_complete()
        assert res[nxt].output_ids == _generate(
            model, np.asarray([4, 5], np.int32), 2)[0]
        assert eng.stats()["lookahead"]["tokens_discarded"] == 1
        assert len(req.output_ids) == 2

    def test_a_slot_error_costs_its_request_alone(self, model):
        from paddle_tpu.testing import failpoints

        prompts = [np.asarray(p, np.int32) for p in ([1, 2, 3], [4, 5, 6])]
        eng = ServingEngine(model, max_batch=2)
        rids = [eng.submit(p, max_new_tokens=6) for p in prompts]
        eng.step()
        with failpoints.scoped("serving/slot=error:1"):
            eng.step()
        res = eng.run_until_complete()
        reasons = sorted(res[r].finish_reason for r in rids)
        assert reasons == ["error", "length"]
        ok = next(r for r in rids if res[r].finish_reason == "length")
        assert res[ok].output_ids == _generate(
            model, prompts[rids.index(ok)], 6)[0]
        bad = res[next(r for r in rids if r != ok)]
        assert bad.output_ids == _generate(
            model, prompts[rids.index(bad.rid)], 6)[0][:len(bad.output_ids)]
        assert eng.stats()["lookahead"]["tokens_discarded"] == 1


class TestWarmup:
    @pytest.mark.parametrize("sampling", [False, True])
    def test_warmup_leaves_no_compile_to_the_first_step(self, model,
                                                        sampling):
        """(e) Every program the loop calls is among those `warmup`
        compiled: the decode steps, the prefill buckets, the row copy and
        `pick1_put`, the admission's token laid into the device's vector."""
        eng = ServingEngine(model, max_batch=2)
        warmed = eng.warmup(sampling=sampling)
        assert warmed["pick1_put"] == 1 and "pick1" not in warmed
        monitor.reset()
        kw = dict(temperature=0.9, seed=4) if sampling else {}
        eng.submit(np.arange(5, dtype=np.int32), max_new_tokens=4, **kw)
        eng.submit(np.arange(40, dtype=np.int32), max_new_tokens=3)
        eng.run_until_complete()
        assert monitor.counter("compile_total",
                               labelnames=("site",)).labels(
            site="serving").value == 0
        for prog in (eng._pick1_put, eng._admit, eng._prefill,
                     eng._step_sample if sampling else eng._step_greedy):
            # each call found its executable among the warmed ones
            assert prog.executed()["calls"] > 0
            assert all(c._compiled is not None
                       for c in prog._store.values())

    def test_serial_engines_keep_their_loop(self, model):
        """Paged and speculative engines keep the serial loop, by what the
        engine sees at construction: nothing in flight, counters at 0."""
        eng = ServingEngine(model, max_batch=2, draft_model=model, spec_k=2)
        assert not eng._lookahead and hasattr(eng, "_pick1")
        rid = eng.submit(np.arange(5, dtype=np.int32), max_new_tokens=6)
        res = eng.run_until_complete()
        assert res[rid].output_ids == _generate(
            model, np.arange(5, dtype=np.int32), 6)[0]
        assert eng.stats()["lookahead"] == {
            "rounds": 0, "rounds_overlapped": 0, "tokens_discarded": 0,
            "in_flight": 0}
        paddle.set_flags({"paged_kv": True})
        try:
            paged = ServingEngine(model, max_batch=2)
        finally:
            paddle.set_flags({"paged_kv": False})
        assert not paged._lookahead
