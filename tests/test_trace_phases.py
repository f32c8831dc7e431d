"""The step-phase timeline (paddle_tpu.trace.phase / phases): the ring's
own semantics, and that the serving engine's and the trainer's step-time
accounting is computed from the phases they emit — same keys as before,
values equal to the phases' own clock reads."""
import threading

import numpy as np
import pytest

import jax

import paddle_tpu as paddle
from paddle_tpu import trace
from paddle_tpu.models import GPTConfig, GPTForCausalLM


@pytest.fixture(autouse=True)
def _clean():
    trace.disable()
    trace.clear()
    yield
    trace.disable()
    trace.clear()


def _by_name(rows):
    out = {}
    for r in rows:
        out.setdefault(r[0], []).append(r)
    return out


def _ms(rows):
    return sum(e - s for _, s, e, *_ in rows) / 1e6


class TestPhaseRing:
    def test_nesting_gives_parent_name_and_one_step_no(self):
        with trace.phase("t/root", queued=2) as root:
            with trace.phase("t/child"):
                with trace.phase("t/leaf", slot=1):
                    pass
            root.counts["active"] = 3
        with trace.phase("t/root"):
            pass
        rows, lost = trace.phases()
        assert not lost
        # a phase lands when it closes: innermost first
        assert [r[0] for r in rows] == ["t/leaf", "t/child", "t/root",
                                        "t/root"]
        leaf, child, root1, root2 = rows
        assert (leaf[3], child[3], root1[3]) == ("t/child", "t/root", None)
        assert leaf[4] == child[4] == root1[4] and root2[4] == root1[4] + 1
        assert root1[5] == {"queued": 2, "active": 3}
        assert leaf[5] == {"slot": 1} and child[5] is None
        # each row is (name, start_ns, end_ns, parent, step_no, counts)
        assert all(len(r) == 6 and r[1] <= r[2] for r in rows)
        assert root1[1] <= child[1] and child[2] <= root1[2]
        assert root.start_ns == root1[1] and root.end_ns == root1[2]
        assert root.ms == pytest.approx((root1[2] - root1[1]) / 1e6)

    def test_on_with_default_flags_and_no_span_is_made(self, monkeypatch):
        def boom(*a, **k):
            raise AssertionError("a phase built a Span")
        monkeypatch.setattr(trace, "Span", boom)
        monkeypatch.setattr(trace, "_record", boom)
        assert not trace.is_enabled()
        with trace.phase("t/on"):
            pass
        assert [r[0] for r in trace.phases()[0]] == ["t/on"]
        assert not trace.spans()

    def test_a_failing_block_still_records_and_unwinds(self):
        with pytest.raises(KeyError):
            with trace.phase("t/outer"):
                with trace.phase("t/inner"):
                    raise KeyError("x")
        with trace.phase("t/after"):
            pass
        rows = trace.phases()[0]
        assert [r[0] for r in rows] == ["t/inner", "t/outer", "t/after"]
        assert rows[2][3] is None        # the stack was unwound

    def test_capacity_is_fixed_and_eviction_is_reported(self):
        assert trace.PHASE_CAPACITY == 60 * 200 * 8
        assert trace._PHASES.maxlen == trace.PHASE_CAPACITY
        trace.set_capacity(8)            # the span ring's, not this one's
        try:
            assert trace._PHASES.maxlen == trace.PHASE_CAPACITY
        finally:
            trace.sync_from_flag()
        for _ in range(trace.PHASE_CAPACITY):
            with trace.phase("t/fill"):
                pass
        rows, lost = trace.phases()
        assert len(rows) == trace.PHASE_CAPACITY and not lost
        first_end = rows[0][2]
        with trace.phase("t/one_more"):
            pass
        rows, lost = trace.phases()
        assert len(rows) == trace.PHASE_CAPACITY and lost
        assert rows[-1][0] == "t/one_more"
        # what was evicted ended at `first_end`: a reader that asks from
        # there on has lost something, one that asks from later has not
        assert trace.phases(since_ns=first_end)[1]
        kept, lost = trace.phases(since_ns=rows[0][2])
        assert not lost and len(kept) == len(rows)
        newest, lost = trace.phases(since_ns=rows[-1][1])
        assert not lost and [r[0] for r in newest] == ["t/one_more"]
        trace.clear()
        assert trace.phases() == ([], False)

    def test_request_spans_cannot_evict_phases(self):
        trace.enable()
        trace.set_capacity(4)
        try:
            with trace.phase("t/kept"):
                for i in range(64):
                    with trace.span(f"noise{i}"):
                        pass
        finally:
            trace.sync_from_flag()
            trace.disable()
        assert len(trace.spans()) <= 4
        assert [r[0] for r in trace.phases()[0]] == ["t/kept"]

    def test_threads_get_independent_stacks(self):
        seen = {}

        def work(tag):
            with trace.phase(f"t/{tag}"):
                with trace.phase(f"t/{tag}/child"):
                    pass

        with trace.phase("t/main"):
            ts = [threading.Thread(target=work, args=(i,)) for i in range(4)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=30)
                assert not t.is_alive()
        for name, _, _, parent, step, _ in trace.phases()[0]:
            seen[name] = (parent, step)
        assert seen["t/main"][0] is None
        for i in range(4):
            assert seen[f"t/{i}"][0] is None        # not under t/main
            assert seen[f"t/{i}/child"] == (f"t/{i}", seen[f"t/{i}"][1])
        assert len({s for _, s in seen.values()}) == 5

    def test_chrome_export_draws_the_phases(self, tmp_path):
        import json

        with trace.phase("serve/step", active=2):
            with trace.phase("serve/emit"):
                pass
        with trace.phase("train/step", step=np.int64(7)):
            pass
        path = str(tmp_path / "p.json")
        trace.export_chrome(path, include_host_events=False)
        with open(path) as f:
            events = json.load(f)["traceEvents"]
        drawn = {e["name"]: e for e in events if e.get("cat") == "phase"}
        assert set(drawn) == {"serve/step", "serve/emit", "train/step"}
        assert drawn["serve/emit"]["args"]["parent"] == "serve/step"
        assert drawn["serve/step"]["args"]["active"] == 2
        assert drawn["train/step"]["args"]["step"] == 7
        pids = {e["pid"] for e in drawn.values()}
        assert len(pids) == 1
        meta = {(e["name"], e["args"]["name"]) for e in events
                if e["ph"] == "M" and e["pid"] in pids}
        assert meta == {("process_name", "phases"), ("thread_name", "serve"),
                        ("thread_name", "train")}
        # the two families are two tracks
        assert drawn["serve/step"]["tid"] != drawn["train/step"]["tid"]
        assert drawn["serve/step"]["tid"] == drawn["serve/emit"]["tid"]
        assert not [e for e in events if e["ph"] == "C"]


def _tiny_gpt():
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=1,
                    num_heads=2, max_seq_len=64, dropout=0.0)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


def _serve(chunk=None, n_requests=3, eos=None):
    from paddle_tpu.inference.serving import ServingEngine

    m = _tiny_gpt()
    rng = np.random.RandomState(0)
    kw = {} if chunk is None else {"prefill_chunk": chunk}
    eng = ServingEngine(m, max_batch=2, eos_token_id=eos, **kw)
    trace.clear()
    for n in (5, 9, 4)[:n_requests]:
        eng.submit(rng.randint(0, 64, (n,)).astype(np.int32),
                   max_new_tokens=6)
    eng.run_until_complete()
    return eng, trace.phases()[0]


class TestServingPhases:
    def test_step_tree_counts_and_breakdown(self):
        eng, rows = _serve()
        by = _by_name(rows)
        assert set(by) == {"serve/step", "serve/admit", "serve/prefill",
                           "serve/prefill_wait", "serve/decode_dispatch",
                           "serve/decode_wait", "serve/emit"}
        assert {r[3] for r in by["serve/step"]} == {None}
        # the first tokens of a round's admissions are waited for once,
        # beside the decode step's, not inside each admission
        for child in ("serve/admit", "serve/decode_dispatch",
                      "serve/decode_wait", "serve/prefill_wait",
                      "serve/emit"):
            assert {r[3] for r in by[child]} == {"serve/step"}
        assert {r[3] for r in by["serve/prefill"]} == {"serve/admit"}
        assert len(by["serve/prefill_wait"]) == len(
            [r for r in by["serve/admit"] if r[5]["admitted"]])
        steps = by["serve/step"]
        assert len(steps) == eng.stats()["health"]["steps"]
        assert len(by["serve/admit"]) == len(steps)
        st = eng.stats()
        counts = [r[5] for r in steps]
        assert all(set(c) == {"queued", "active", "admitted", "emitted",
                              "finished"} for c in counts)
        assert sum(c["emitted"] for c in counts) == st["tokens_generated"]
        assert sum(c["admitted"] for c in counts) == 3
        assert sum(c["finished"] for c in counts) == 3
        assert max(c["active"] for c in counts) == 2
        assert counts[0]["queued"] == 3
        assert sum(c["active"] for c in counts) / len(counts) == \
            pytest.approx(st["batch_occupancy_avg"])
        admits = [r[5] for r in by["serve/admit"]]
        assert sum(a["admitted"] for a in admits) == 3
        assert sum(a["prompt_tokens"] for a in admits) == 5 + 9 + 4
        assert sorted(r[5]["tokens"] for r in by["serve/prefill"]) == \
            [4, 5, 9]
        assert all(set(r[5]) == {"slot", "tokens", "bucket", "true_len"}
                   and r[5]["true_len"] == r[5]["tokens"]
                   for r in by["serve/prefill"])
        # stats()["breakdown"]: the keys it had, the values of the phases
        bd = st["breakdown"]
        # (flops/mfu fields join them once the process has cost entries)
        assert {"kinds", "wall_ms_total"} <= set(bd)
        assert set(bd["kinds"]) == {"prefill", "decode_greedy"}
        for row in bd["kinds"].values():
            assert {"count", "wall_ms", "wall_fraction"} <= set(row)
        assert bd["kinds"]["prefill"]["count"] == 3
        assert bd["kinds"]["prefill"]["wall_ms"] == \
            pytest.approx(_ms(by["serve/prefill"]), rel=1e-9)
        # a step's slice: its dispatch in one call, its wait in the next
        decode = bd["kinds"]["decode_greedy"]
        assert decode["count"] == len(by["serve/decode_wait"]) == \
            len(by["serve/decode_dispatch"]) == st["steps"]["decode_greedy"]
        assert decode["wall_ms"] == pytest.approx(
            _ms(by["serve/decode_dispatch"]) + _ms(by["serve/decode_wait"]),
            rel=1e-9)
        assert bd["wall_ms_total"] == pytest.approx(
            decode["wall_ms"] + bd["kinds"]["prefill"]["wall_ms"])
        # each root's children cover it but for the root's own bookkeeping
        for root in steps:
            inside = [r for r in rows if r[4] == root[4]
                      and r[3] == "serve/step"]
            assert sum(r[2] - r[1] for r in inside) <= root[2] - root[1]

    def test_chunked_admission_phases(self):
        eng, rows = _serve(chunk=4, n_requests=2)
        by = _by_name(rows)
        assert "serve/prefill" not in by
        chunks = by["serve/prefill_chunk"]
        assert {r[3] for r in chunks} == {"serve/admit"}
        assert len(chunks) == eng.stats()["steps"]["prefill_chunk"]
        assert all(set(r[5]) == {"slot", "offset", "width", "chunk_kv_tiles"}
                   for r in chunks)
        # no kernel walks a GPT chunk's keys (ops/latent_prefill_attention.py
        # is the latent family's, on a TPU)
        assert {r[5]["chunk_kv_tiles"] for r in chunks} == {0}
        assert eng.stats()["chunk_kv_tiles"] == 0
        assert {r[3] for r in by["serve/prefill_wait"]} == {"serve/step"}
        bd = eng.stats()["breakdown"]["kinds"]["prefill_chunk"]
        assert bd["count"] == len(chunks)
        assert bd["wall_ms"] == pytest.approx(_ms(chunks), rel=1e-9)

    def test_lookahead_round_is_the_phases_in_order_with_their_counts(self):
        trace.enable()
        try:
            eng, rows = _serve(eos=9)     # the second request's 2nd token
            spans = trace.spans()
        finally:
            trace.disable()
        by = _by_name(rows)
        assert "async_overlap" not in eng.stats()["breakdown"]
        assert not [sp for sp in spans if sp.name.startswith("dispatch/")]
        la = eng.stats()["lookahead"]
        assert set(la) == {"rounds", "rounds_overlapped",
                           "tokens_discarded", "in_flight"}
        disp = by["serve/decode_dispatch"]
        assert la["rounds"] == len(disp) == eng.stats()["steps"][
            "decode_greedy"]
        assert la["rounds_overlapped"] == sum(r[5]["in_flight"]
                                              for r in disp)
        assert la["tokens_discarded"] == sum(r[5]["discarded"]
                                             for r in by["serve/emit"]) == 1
        assert la["in_flight"] == 0 and eng._flight is None
        # inside a round: admissions, the NEXT step's dispatch, and only
        # then the read of the step dispatched a round ago, the read of
        # the admissions' first tokens, the emit
        order = ["serve/admit", "serve/decode_dispatch",
                 "serve/decode_wait", "serve/prefill_wait", "serve/emit"]
        for root in by["serve/step"]:
            inside = sorted((r for r in rows
                             if r[4] == root[4] and r[3] == "serve/step"),
                            key=lambda r: r[1])
            names = [r[0] for r in inside]
            assert names == sorted(names, key=order.index)
            assert all(a[2] <= b[1] for a, b in zip(inside, inside[1:]))
            ds = [r for r in inside if r[0] == "serve/decode_dispatch"]
            # a call that finds nothing in flight dispatches twice, the
            # first with nothing ahead of it; every other dispatch goes
            # out with the step before it still unread
            assert [r[5]["in_flight"] for r in ds] in ([1], [0, 1], [0], [])
        first = min(by["serve/step"], key=lambda r: r[1])
        assert [r[5]["in_flight"] for r in disp if r[4] == first[4]] == \
            [0, 1]
        # a decode kind's slice: the dispatches that were read + the waits
        # (a step dispatched for rows that all ended by eos is dropped
        # unread: it has a dispatch phase, no wait, and is not booked)
        decode = eng.stats()["breakdown"]["kinds"]["decode_greedy"]
        assert decode["count"] == len(by["serve/decode_wait"])
        assert len(disp) - decode["count"] in (0, 1)
        assert decode["wall_ms"] <= _ms(disp) + _ms(
            by["serve/decode_wait"]) + 1e-9
        assert decode["wall_ms"] >= _ms(by["serve/decode_wait"])


class TestTrainerPhases:
    def _trainer(self):
        from paddle_tpu.distributed.mesh import build_mesh
        from paddle_tpu.distributed.spmd import SpmdTrainer

        paddle.seed(0)
        model = paddle.nn.Linear(4, 1)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=model.parameters())
        mesh = build_mesh((1,), ("dp",), devices=jax.devices()[:1])
        return SpmdTrainer(model, opt, loss_fn=paddle.nn.MSELoss(),
                           mesh=mesh)

    @pytest.mark.parametrize("benchmark_flag", [False, True])
    def test_step_tree_and_stats_read_the_phases(self, benchmark_flag):
        tr = self._trainer()
        x = np.ones((2, 4), np.float32)
        y = np.zeros((2, 1), np.float32)
        old = paddle.get_flags(["FLAGS_benchmark"])
        paddle.set_flags({"benchmark": benchmark_flag})
        try:
            for _ in range(3):
                tr.train_step(x, y)
        finally:
            paddle.set_flags(old)
        rows = trace.phases()[0]
        by = _by_name(rows)
        want = {"train/step", "train/batch", "train/resolve",
                "train/dispatch", "train/finish"}
        if benchmark_flag:
            want.add("train/sync")
            assert {r[3] for r in by["train/sync"]} == {"train/finish"}
        assert set(by) == want
        assert all(len(v) == 3 for v in by.values())
        for child in want - {"train/step", "train/sync"}:
            assert {r[3] for r in by[child]} == {"train/step"}
        roots = by["train/step"]
        # no cache directory: the first step resolves a lazy jit
        assert [r[5]["source"] for r in roots] == ["bypass", "memory",
                                                   "memory"]
        assert [r[5]["step"] for r in roots] == [0, 1, 2]
        assert len({r[5]["sig"] for r in roots}) == 1
        assert all(r[5] == {"prefetch_hit": 0} for r in by["train/batch"])
        # stats(): the step ends where the sync ends, or, with no sync,
        # where train/finish starts; it runs from train/dispatch's start
        exec_ms = sync_ms = 0.0
        for root in roots:
            mine = {r[0]: r for r in rows if r[4] == root[4]}
            end = mine["train/sync"][2] if benchmark_flag \
                else mine["train/finish"][1]
            exec_ms += (end - mine["train/dispatch"][1]) / 1e6
            if benchmark_flag:
                sync_ms += (mine["train/sync"][2]
                            - mine["train/sync"][1]) / 1e6
            kids = [r for r in rows if r[4] == root[4]
                    and r[3] == "train/step"]
            assert sum(r[2] - r[1] for r in kids) <= root[2] - root[1]
        st = tr.stats()
        assert st["steps"] == 3
        assert st["step_ms_total"] == pytest.approx(exec_ms, rel=1e-9)
        assert st["breakdown"]["sync_ms_total"] == \
            pytest.approx(sync_ms, rel=1e-9)
        assert (sync_ms > 0) == benchmark_flag

    def test_prefetch_hit_is_counted(self):
        tr = self._trainer()
        x = paddle.to_tensor(np.ones((2, 4), np.float32))
        y = paddle.to_tensor(np.zeros((2, 1), np.float32))
        tr.train_step(x, y)
        tr.prefetch(x, y)
        tr.train_step(x, y)
        hits = [r[5]["prefetch_hit"] for r in trace.phases()[0]
                if r[0] == "train/batch"]
        assert hits == [0, 1]
