"""MPMD stage-program runtime (ISSUE 15): typed backpressured edges, the
int8 row codec, schedule equivalence on StageGraph, unequal per-stage
meshes, per-stage AOT cache keys, the shared _pvary helper, stage span
lineage, and the disagg pool's hand-off-over-edge parity + metering."""
import os
import sys

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import flags, monitor, trace
from paddle_tpu.analysis.handoff_schema import HandoffMismatch
from paddle_tpu.distributed import compress as C
from paddle_tpu.distributed import stage as stage_mod
from paddle_tpu.distributed.mesh import build_mesh
from paddle_tpu.distributed.pipeline import PipelineTrainer
from paddle_tpu.distributed.stage import (EdgeEmptyError, EdgeFullError,
                                          StageEdge)
from paddle_tpu.models import GPTConfig, GPTForCausalLM

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


@pytest.fixture
def mpmd():
    old = flags.get_flag("mpmd", False)
    paddle.set_flags({"mpmd": True})
    yield
    paddle.set_flags({"mpmd": old})


def _pipeline(schedule="1F1B", n_pp=2, hidden=32, heads=2, **kw):
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=64, hidden_size=hidden, num_layers=n_pp,
                    num_heads=heads, max_seq_len=32, dropout=0.0)
    model = GPTForCausalLM(cfg)
    pre, stages, post = model.pipeline_split(n_pp)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    mesh = build_mesh((n_pp,), ("pp",), devices=jax.devices()[:n_pp])
    return PipelineTrainer(pre, stages, post, opt, mesh=mesh,
                           n_micro=n_pp, schedule_mode=schedule, **kw)


def _losses(tr, steps=3, seed=0):
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(steps):
        x = rng.randint(0, 64, (4, 16)).astype(np.int32)
        y = rng.randint(0, 64, (4, 16)).astype(np.int32)
        out.append(float(np.asarray(tr.train_step(x, y)._data)))
    return out


class TestStageEdge:
    def test_validate_rejects_shape_and_key_mismatch(self):
        edge = StageEdge("e", stage_mod.HANDOFF_SCHEMA, capacity=2)
        with pytest.raises(HandoffMismatch):
            edge.put({"activation": np.ones((2, 3), np.float32)})  # rank 2
        with pytest.raises(HandoffMismatch):
            edge.put({"wrong_key": np.ones((1, 2, 4), np.float32)})
        assert len(edge) == 0  # a rejected payload is never enqueued

    def test_backpressure_counts_and_drains_fifo(self):
        edge = StageEdge("e", stage_mod.HANDOFF_SCHEMA, capacity=2)
        rows = [np.full((1, 2, 4), float(i + 1), np.float32)
                for i in range(3)]
        edge.put({"activation": rows[0]})
        edge.put({"activation": rows[1]})
        assert edge.full()
        with pytest.raises(EdgeFullError):
            edge.put({"activation": rows[2]})
        assert edge.stats["backpressured"] == 1
        assert edge.stats["puts"] == 2  # the rejected put did no work
        got = [np.asarray(edge.get()["activation"]) for _ in range(2)]
        assert all(np.array_equal(g, r) for g, r in zip(got, rows))
        with pytest.raises(EdgeEmptyError):
            edge.get()

    def test_dense_edge_meters_wire_eq_logical(self):
        monitor.reset()
        edge = StageEdge("e", stage_mod.HANDOFF_SCHEMA, capacity=1)
        row = np.ones((2, 4, 8), np.float32)
        wire = edge.put({"activation": row})
        assert wire == row.nbytes
        assert edge.stats["wire_bytes"] == edge.stats["logical_bytes"]
        flat = monitor.flatten(monitor.snapshot())
        assert flat["kv_handoff_bytes_total"] == row.nbytes

    def test_quantized_edge_hits_wire_ratio_and_meters_savings(self):
        """The acceptance bar: a compress=8 activation edge moves >=3.5x
        fewer wire bytes than logical at feature dim 256 (per-row int8:
        ratio = 4/(1 + 4/D) -> 3.94x), and the savings land on the
        collective chokepoint as {op=stage_edge}."""
        monitor.reset()
        edge = StageEdge("q", stage_mod.HANDOFF_SCHEMA, capacity=4,
                         compress=8)
        rng = np.random.RandomState(0)
        for _ in range(3):
            edge.put({"activation":
                      rng.randn(2, 4, 256).astype(np.float32)})
        st = edge.stats
        ratio = st["logical_bytes"] / st["wire_bytes"]
        assert ratio >= 3.5, f"wire ratio {ratio:.2f} < 3.5"
        flat = monitor.flatten(monitor.snapshot())
        assert flat["kv_handoff_bytes_total"] == st["wire_bytes"]
        assert flat["collective_bytes_total{op=stage_edge}"] == \
            st["wire_bytes"]
        assert flat["collective_bytes_saved_total{op=stage_edge}"] == \
            st["logical_bytes"] - st["wire_bytes"]

    def test_quantized_roundtrip_stays_close(self):
        edge = StageEdge("q", stage_mod.HANDOFF_SCHEMA, capacity=1,
                         compress=8)
        rng = np.random.RandomState(1)
        row = rng.randn(1, 3, 64).astype(np.float32)
        edge.put({"activation": row})
        out = np.asarray(edge.get()["activation"])
        assert out.dtype == np.float32
        # per-row int8: error bounded by half a quantization step
        bound = np.abs(row).max(axis=-1, keepdims=True) / 127.0
        assert np.all(np.abs(out - row) <= bound * 0.51 + 1e-8)


class TestRowCodec:
    def test_roundtrip_deterministic(self):
        rng = np.random.RandomState(2)
        x = rng.randn(5, 32).astype(np.float32)
        q1, s1 = C.quantize_rows(x)
        q2, s2 = C.quantize_rows(x)
        assert np.array_equal(np.asarray(q1), np.asarray(q2))
        assert np.array_equal(np.asarray(s1), np.asarray(s2))
        assert np.asarray(q1).dtype == np.int8
        assert np.asarray(s1).shape == (5, 1)
        back = np.asarray(C.dequantize_rows(q1, s1))
        step = np.abs(x).max(axis=-1, keepdims=True) / 127.0
        assert np.all(np.abs(back - x) <= step * 0.51 + 1e-8)

    def test_zero_row_is_exact(self):
        q, s = C.quantize_rows(np.zeros((2, 8), np.float32))
        assert np.array_equal(np.asarray(C.dequantize_rows(q, s)),
                              np.zeros((2, 8), np.float32))

    def test_nan_poisons_only_its_row(self):
        x = np.ones((2, 4), np.float32)
        x[0, 1] = np.nan
        back = np.asarray(C.dequantize_rows(*C.quantize_rows(x)))
        assert not np.all(np.isfinite(back[0]))
        assert np.allclose(back[1], x[1], atol=1e-2)


class TestPvaryDedupe:
    def test_single_definition_shared_by_both_consumers(self):
        """Satellite 1: one _pvary, owned by spmd — the pipeline and
        long-context modules alias it instead of carrying copies."""
        from paddle_tpu.distributed import long_context, pipeline, spmd

        assert pipeline._vary is spmd._pvary
        assert long_context._vary is spmd._pvary

    def test_marks_the_value_varying_over_the_axis(self):
        """No identity arm: inside shard_map the helper really casts a
        replicated value to device-varying over the named axis (an
        unchanged value would let shard_map pre-sum its cotangent)."""
        from jax.sharding import PartitionSpec as P

        from paddle_tpu.distributed import spmd

        mesh = build_mesh((2,), ("dp",), devices=jax.devices()[:2])
        seen = {}

        def body(x):
            seen["before"] = jax.typeof(x).vma
            y = spmd._pvary(x, "dp")
            seen["after"] = jax.typeof(y).vma
            return y

        jax.shard_map(body, mesh=mesh, in_specs=P(), out_specs=P("dp"))(
            np.ones((2,), np.float32))
        assert seen == {"before": frozenset(), "after": frozenset({"dp"})}


class TestSchedulesAndMeshes:
    def test_armed_1f1b_matches_disarmed_loss_exactly(self, mpmd):
        paddle.set_flags({"mpmd": False})
        ref = _losses(_pipeline())
        paddle.set_flags({"mpmd": True})
        assert _losses(_pipeline()) == ref

    def test_all_schedules_bit_equal(self, mpmd):
        ref = _losses(_pipeline("1F1B"))
        assert _losses(_pipeline("F-then-B")) == ref
        assert _losses(_pipeline("interleaved")) == ref

    def test_unequal_stage_meshes_train_to_same_loss(self, mpmd):
        """Satellite 5: a 2-stage graph with DIFFERENT per-stage device
        counts (1 vs 3) trains to the same loss as the equal-mesh run —
        stage programs replicate within their own mesh, so mesh width
        is a placement choice, not a numerics choice."""
        ref = _losses(_pipeline())
        meshes = [build_mesh((1,), ("stage",), devices=jax.devices()[:1]),
                  build_mesh((3,), ("stage",),
                             devices=jax.devices()[1:4])]
        tr = _pipeline(stage_meshes=meshes)
        assert [len(m.devices.ravel())
                for m in tr._mpmd_runner.stage_meshes] == [1, 3]
        got = _losses(tr)
        np.testing.assert_allclose(got, ref, rtol=0, atol=1e-6)

    def test_quantized_edge_trains_close_and_meters(self, mpmd):
        monitor.reset()
        ref = _losses(_pipeline(hidden=64, heads=4))
        got = _losses(_pipeline(hidden=64, heads=4, compress=8))
        np.testing.assert_allclose(got, ref, rtol=0, atol=5e-2)
        flat = monitor.flatten(monitor.snapshot())
        saved = flat["collective_bytes_saved_total{op=stage_edge}"]
        wire = flat["collective_bytes_total{op=stage_edge}"]
        assert (saved + wire) / wire >= 3.5  # logical/wire at d=64


class TestStageSpans:
    def test_stage_step_spans_share_one_trace_id(self, mpmd):
        tr = _pipeline()
        _losses(tr, steps=1)
        trace.clear()
        trace.enable()
        try:
            _losses(tr, steps=1, seed=1)
        finally:
            trace.disable()
        roots = [s for s in trace.spans() if s.name == "stage_graph"]
        ticks = [s for s in trace.spans() if s.name == "stage_step"]
        assert len(roots) == 1
        assert ticks and all(s.trace_id == roots[0].trace_id
                             for s in ticks)
        assert all(s.subsystem == "stage" for s in roots + ticks)


class TestDisaggOverEdge:
    def _pool(self, m, **kw):
        from paddle_tpu.serving.disagg import DisaggregatedPool

        return DisaggregatedPool(m, prefill_workers=1, decode_engines=1,
                                 max_batch=2, **kw)

    def test_armed_pool_byte_identical_and_edge_metered(self, mpmd):
        paddle.seed(0)
        cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=1,
                        num_heads=2, max_seq_len=64, dropout=0.0)
        m = GPTForCausalLM(cfg)
        m.eval()
        rng = np.random.RandomState(0)
        prompts = [rng.randint(0, 64, (n,)).astype(np.int32)
                   for n in (5, 8, 4)]
        paddle.set_flags({"mpmd": False})
        ref_pool = self._pool(m)
        ref_ids = [ref_pool.submit(p, max_new_tokens=5) for p in prompts]
        ref = ref_pool.run_until_complete()
        paddle.set_flags({"mpmd": True})
        monitor.reset()
        pool = self._pool(m)
        rids = [pool.submit(p, max_new_tokens=5) for p in prompts]
        res = pool.run_until_complete()
        for a, b in zip(ref_ids, rids):
            np.testing.assert_array_equal(ref[a].tokens, res[b].tokens)
        st = pool.stats()["edge"]
        assert st["puts"] == st["gets"] == len(prompts)
        assert st["wire_bytes"] == st["logical_bytes"]  # dense hand-off
        flat = monitor.flatten(monitor.snapshot())
        assert flat["kv_handoff_bytes_total"] == st["wire_bytes"]
