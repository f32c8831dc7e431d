"""Unit + integration tests for the bandwidth-frugal dp stack (ISSUE 10):
distributed/compress.py quantize/dequantize/quantized_all_reduce, the
collective chokepoint's compressed opt-in, and the SpmdTrainer's
FLAGS_quantized_allreduce / FLAGS_shard_weight_update builds — error
feedback, guard/numerics composition, exact update-sharding parity,
checkpoint round-trips, and the construction-time flag contract.
"""
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu import monitor, nn  # noqa: E402
from paddle_tpu.distributed import collective  # noqa: E402
from paddle_tpu.distributed import compress  # noqa: E402
from paddle_tpu.distributed.mesh import build_mesh  # noqa: E402
from paddle_tpu.distributed.spmd import SpmdTrainer  # noqa: E402


@pytest.fixture(autouse=True)
def _clean_flags():
    keys = ("quantized_allreduce", "shard_weight_update",
            "quantized_allreduce_bits", "quantized_allreduce_min_size",
            "check_nan_inf", "numerics", "numerics_interval")
    old = {k: paddle.get_flags(["FLAGS_" + k])["FLAGS_" + k] for k in keys}
    yield
    paddle.set_flags(old)


def _key(i=0):
    return jax.random.fold_in(jax.random.key(7), i)


# ---------------------------------------------------------------------------
# quantize / dequantize primitives
# ---------------------------------------------------------------------------

class TestQuantizePrimitives:
    def test_roundtrip_error_bounded_by_block_scale(self):
        rng = np.random.RandomState(0)
        x = rng.randn(4 * compress.DEFAULT_BLOCK).astype(np.float32) * 3
        q, s = compress.quantize(jnp.asarray(x), _key())
        out = np.asarray(compress.dequantize(q, s))
        scales = np.repeat(np.asarray(s), compress.DEFAULT_BLOCK)
        # stochastic rounding moves each element by at most one step
        assert np.all(np.abs(out - x) <= scales + 1e-7)
        assert np.asarray(q).dtype == np.int8

    def test_deterministic_under_same_key(self):
        x = jnp.asarray(np.random.RandomState(1)
                        .randn(compress.DEFAULT_BLOCK).astype(np.float32))
        q1, s1 = compress.quantize(x, _key(3))
        q2, s2 = compress.quantize(x, _key(3))
        assert np.array_equal(np.asarray(q1), np.asarray(q2))
        assert np.array_equal(np.asarray(s1), np.asarray(s2))
        q3, _ = compress.quantize(x, _key(4))
        assert not np.array_equal(np.asarray(q1), np.asarray(q3))

    def test_stochastic_rounding_is_unbiased(self):
        # a constant mid-step value must average back to itself
        x = jnp.full((compress.DEFAULT_BLOCK,), 0.3, jnp.float32)
        x = x.at[0].set(1.27)      # pins the block scale at 0.01
        outs = np.stack([
            np.asarray(compress.quantize_dequantize(x, _key(i)))
            for i in range(200)])
        assert abs(float(outs[:, 1:].mean()) - 0.3) < 5e-4

    def test_zero_block_exact(self):
        x = jnp.zeros((compress.DEFAULT_BLOCK,), jnp.float32)
        out = compress.quantize_dequantize(x, _key())
        assert np.array_equal(np.asarray(out), np.zeros_like(x))

    def test_nan_poisons_its_block_loudly(self):
        x = np.ones((2 * compress.DEFAULT_BLOCK,), np.float32)
        x[3] = np.nan
        out = np.asarray(compress.quantize_dequantize(jnp.asarray(x),
                                                      _key()))
        # the poisoned block comes back non-finite (the NaN rides the
        # fp32 scale); the clean block is untouched
        assert not np.all(np.isfinite(out[:compress.DEFAULT_BLOCK]))
        assert np.all(np.isfinite(out[compress.DEFAULT_BLOCK:]))

    def test_shape_preserved_and_padding_trimmed(self):
        x = jnp.asarray(np.random.RandomState(2)
                        .randn(3, 17).astype(np.float32))
        out = compress.quantize_dequantize(x, _key())
        assert out.shape == x.shape

    def test_wire_bytes_math(self):
        b = compress.DEFAULT_BLOCK
        assert compress.padded_size(1, block=b) == b
        assert compress.padded_size(b + 1, block=b) == 2 * b
        assert compress.padded_size(10, block=b, world=4) == 4 * b
        # int8 payload + one fp32 scale per block
        assert compress.wire_bytes(b, block=b) == b + 4
        assert compress.wire_bytes(4 * b, block=b) == 4 * b + 16

    def test_unsupported_bits_raise(self):
        with pytest.raises(ValueError, match="bits"):
            compress.quantize(jnp.zeros(256), _key(), bits=4)
        with pytest.raises(ValueError, match="bits"):
            compress.wire_bytes(256, bits=16)


# ---------------------------------------------------------------------------
# quantized_all_reduce on a real dp axis
# ---------------------------------------------------------------------------

def _shard_reduce(x_per_rank, world, **kw):
    """Run quantized_all_reduce_ef under shard_map on `world` devices;
    returns the (replicated) reduced array from rank 0."""
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:world]), ("dp",))

    def body(v):
        out, _ = compress.quantized_all_reduce_ef(
            v[0], "dp", _key(9), **kw)
        return out[None]

    f = shard_map(body, mesh=mesh, in_specs=(P("dp"),),
                  out_specs=P("dp"), check_vma=False)
    return np.asarray(jax.jit(f)(jnp.asarray(x_per_rank)))


class TestQuantizedAllReduce:
    @pytest.mark.parametrize("world", [2, 8])
    def test_sum_close_and_identical_across_ranks(self, world):
        if len(jax.devices()) < world:
            pytest.skip(f"needs {world} devices")
        rng = np.random.RandomState(0)
        x = rng.randn(world, 2048).astype(np.float32)
        out = _shard_reduce(x, world)
        ref = x.sum(0)
        # every rank dequantizes the identical gathered bytes
        for r in range(1, world):
            assert np.array_equal(out[r], out[0])
        scale = np.max(np.abs(ref))
        assert np.max(np.abs(out[0] - ref)) / scale < 0.05

    def test_mean(self):
        if len(jax.devices()) < 2:
            pytest.skip("needs 2 devices")
        x = np.ones((2, 512), np.float32) * np.array([[1.0], [3.0]])
        out = _shard_reduce(x, 2, mean=True)
        assert np.allclose(out[0], 2.0, atol=0.05)

    def test_error_feedback_keeps_cumulative_error_one_step_deep(self):
        """The EF contract: sum of applied values over T steps equals
        T*x minus the CURRENT residual — the error never accumulates
        beyond one quantization step."""
        rng = np.random.RandomState(3)
        x = rng.randn(1024).astype(np.float32)
        res = np.zeros_like(x)
        applied_sum = np.zeros_like(x)
        T = 8
        for t in range(T):
            inp = jnp.asarray(x + res)
            rt = np.asarray(compress.quantize_dequantize(inp, _key(t)))
            applied_sum += rt
            res = np.asarray(inp) - rt
        one_step = np.max(np.abs(
            x - np.asarray(compress.quantize_dequantize(jnp.asarray(x),
                                                        _key(99)))))
        # the algebraic identity: what was applied is T*x minus exactly
        # the CURRENT residual — nothing was lost along the way
        assert np.allclose(applied_sum, T * x - res, atol=1e-4)
        # and that residual is one quantization step deep (1.5x slack:
        # the residual rides inside the quantized input, nudging the
        # block scale), NOT T steps deep
        assert np.max(np.abs(res)) <= 1.5 * one_step + 1e-6
        assert np.max(np.abs(applied_sum / T - x)) \
            <= 1.5 * one_step / T + 1e-6

    def test_ste_gradient_matches_psum_cotangent(self):
        data = np.random.RandomState(1).randn(4, 512).astype(np.float32)

        def quant_loss(v):
            s = compress.quantized_all_reduce(v, "c", key=_key(5))
            return jnp.sum(s * s)

        def exact_loss(v):
            s = jax.lax.psum(v, "c")
            return jnp.sum(s * s)

        g = jax.grad(lambda v: jnp.sum(jax.vmap(
            quant_loss, axis_name="c")(v)))(jnp.asarray(data))
        gref = jax.grad(lambda v: jnp.sum(jax.vmap(
            exact_loss, axis_name="c")(v)))(jnp.asarray(data))
        rel = float(jnp.max(jnp.abs(g - gref)) / jnp.max(jnp.abs(gref)))
        assert rel < 0.05   # straight-through: ct of the exact sum


# ---------------------------------------------------------------------------
# the collective chokepoint's compressed opt-in
# ---------------------------------------------------------------------------

def _op_series(snap, name):
    """{op: value} of one family's NON-ZERO series — robust to zeroed
    leftovers other tests' families leave in the shared registry."""
    for m in snap["metrics"]:
        if m["name"] == name:
            return {s["labels"].get("op"): s["value"]
                    for s in m["series"] if s["value"]}
    return {}


class TestChokepointCompressedPath:
    def test_eager_ws1_roundtrip_and_exact_metering(self):
        monitor.reset()
        n = 1000
        x = paddle.to_tensor(np.linspace(-1, 1, n).astype(np.float32))
        out = collective.all_reduce(x, compress=8)
        # paddle all_reduce is in-place — the round-trip lands in the
        # caller's tensor even at world size 1
        assert out is x
        err = np.max(np.abs(np.asarray(out._data)
                            - np.linspace(-1, 1, n)))
        assert 0 < err < 2.0 / 127
        snap = monitor.snapshot()
        wire = compress.wire_bytes(n)
        assert _op_series(snap, "collective_bytes_total") == {
            "quantized_all_reduce": wire}
        assert _op_series(snap, "collective_bytes_saved_total") == {
            "quantized_all_reduce": n * 4 - wire}
        assert _op_series(snap, "collective_calls_total") == {
            "quantized_all_reduce": 1}

    def test_uncompressed_metering_unchanged(self):
        """The PR 2 regression pin: an uncompressed all_reduce still
        counts its LOGICAL payload in collective_bytes_total and
        records nothing saved."""
        monitor.reset()
        x = paddle.to_tensor(np.ones(100, np.float32))
        collective.all_reduce(x)
        snap = monitor.snapshot()
        assert _op_series(snap, "collective_bytes_total") == {
            "all-reduce": 400}
        assert _op_series(snap, "collective_bytes_saved_total") == {}

    def test_integer_payload_raises(self):
        with pytest.raises(ValueError, match="float"):
            collective.all_reduce(paddle.to_tensor(np.arange(4)),
                                  compress=True)

    def test_max_op_raises(self):
        with pytest.raises(ValueError, match="SUM/AVG"):
            collective.all_reduce(
                paddle.to_tensor(np.ones(4, np.float32)),
                op=collective.ReduceOp.MAX, compress=8)

    def test_client_reduce_placed_compressed(self):
        from paddle_tpu.federated import client_map

        data = np.random.RandomState(0).randn(4, 512).astype(np.float32)

        def per_client(v):
            return collective.client_reduce(
                v, op=collective.ReduceOp.SUM, compress=8,
                compress_key=_key(11))

        res = client_map(per_client, paddle.to_tensor(data))
        ref = data.sum(0)
        rel = np.max(np.abs(np.asarray(res._data)[0] - ref)) \
            / np.max(np.abs(ref))
        assert rel < 0.05

    def test_client_reduce_leading_compressed(self):
        monitor.reset()
        data = np.random.RandomState(0).randn(4, 100).astype(np.float32)
        res = collective.client_reduce(paddle.to_tensor(data),
                                       placed=False, compress=8)
        ref = data.sum(0)
        rel = np.max(np.abs(np.asarray(res._data) - ref)) \
            / np.max(np.abs(ref))
        assert rel < 0.05
        # each row is its own payload: 4 x (one padded block + a scale),
        # NOT one contiguous 400-element encoding
        snap = monitor.snapshot()
        assert _op_series(snap, "collective_bytes_total") == {
            "federated_sum": 4 * compress.wire_bytes(100)}


# ---------------------------------------------------------------------------
# trainer integration — quantized all-reduce
# ---------------------------------------------------------------------------

def _build_trainer(mesh_n=1, flags=None, opt="adamw", lr=1e-2,
                   grad_clip=None, **kw):
    paddle.set_flags({"quantized_allreduce": False,
                      "shard_weight_update": False,
                      "quantized_allreduce_min_size": 1024,
                      **(flags or {})})
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(64, 64), nn.Linear(64, 8))
    opt_obj = {
        "adamw": lambda: paddle.optimizer.AdamW(
            learning_rate=lr, parameters=net.parameters(),
            grad_clip=grad_clip),
        "sgd": lambda: paddle.optimizer.SGD(
            learning_rate=lr, parameters=net.parameters()),
        "momentum": lambda: paddle.optimizer.Momentum(
            learning_rate=lr, parameters=net.parameters()),
        "lamb": lambda: paddle.optimizer.Lamb(
            learning_rate=lr, parameters=net.parameters()),
    }[opt]()
    mesh = build_mesh((mesh_n,), ("dp",), devices=jax.devices()[:mesh_n])
    return SpmdTrainer(net, opt_obj, loss_fn=nn.MSELoss(), mesh=mesh,
                       **kw)


_RNG = np.random.RandomState(0)
_X = _RNG.randn(16, 64).astype(np.float32)
_Y = _RNG.randn(16, 8).astype(np.float32)


def _run(tr, steps=3, x=_X, y=_Y):
    for _ in range(steps):
        loss = tr.train_step(x, y)
    return (float(np.asarray(loss._data)),
            {k: np.asarray(v) for k, v in tr.params.items()})


QFLAGS = {"quantized_allreduce": True, "quantized_allreduce_min_size": 1}

#: cached plain-dp references + one exercised quantized trainer — each
#: trainer build compiles a jitted step; sharing them keeps this file's
#: tier-1 wall time down without losing any assertion
_CACHE = {}


def _plain_ref(opt="adamw", mesh_n=1):
    key = (opt, mesh_n)
    if key not in _CACHE:
        _CACHE[key] = _run(_build_trainer(mesh_n=mesh_n, opt=opt))
    return _CACHE[key]


def _qtrainer():
    """A quantized dp1 trainer after 2 steps (built once)."""
    if "qtr" not in _CACHE:
        tr = _build_trainer(flags=QFLAGS)
        _run(tr, 2)
        _CACHE["qtr"] = tr
    return _CACHE["qtr"]


class TestTrainerQuantized:
    def test_loss_stays_in_band_vs_plain(self):
        l0, _ = _plain_ref()
        tr = _qtrainer()
        paddle.set_flags(QFLAGS)   # stepping a quantized-built trainer
        l1 = float(np.asarray(tr.train_step(_X, _Y)._data))
        assert abs(l1 - l0) / abs(l0) < 0.02

    def test_residuals_ride_opt_state_and_feed_back(self):
        tr = _qtrainer()
        assert set(tr.opt_state["__qar_residual__"]) == set(
            tr._qar_eligible) == set(tr.params)
        res = {k: np.asarray(v)
               for k, v in tr.opt_state["__qar_residual__"].items()}
        assert any(np.any(v != 0) for v in res.values())
        assert all(np.all(np.isfinite(v)) for v in res.values())

    def test_min_size_threshold_respected(self):
        # eligibility is a construction-time property — no step needed
        tr = _build_trainer(flags={"quantized_allreduce": True,
                                   "quantized_allreduce_min_size": 1024})
        # 64x64 weight (4096) eligible; 8/64-element biases are not
        assert "0.weight" in tr._qar_eligible
        assert not any(n.endswith("bias") for n in tr._qar_eligible)
        assert set(tr.opt_state["__qar_residual__"]) == set(
            tr._qar_eligible)

    def test_quantize_error_surfaced_lazily(self):
        monitor.reset()
        tr = _qtrainer()
        val = tr.quantize_error()
        assert val is not None and val > 0
        assert tr.stats()["quantize_error_norm"] == val
        snap = monitor.snapshot()
        fams = {m["name"] for m in snap["metrics"] if m["series"]}
        assert "quantize_error_norm" in fams
        # a trainer that never ran a quantized step has nothing banked
        fresh = _build_trainer()
        assert fresh.quantize_error() is None

    def test_checkpoint_roundtrip_bit_exact(self):
        tr = _build_trainer(flags=QFLAGS)
        _run(tr, 2)
        state = tr.state_dict()
        tr2 = _build_trainer(flags=QFLAGS)
        tr2.set_state_dict(state)
        a, _ = _run(tr, 1)
        b, _ = _run(tr2, 1)
        assert a == b

    def test_flag_toggle_after_ctor_raises(self):
        tr = _build_trainer()   # built unarmed
        paddle.set_flags({"quantized_allreduce": True})
        with pytest.raises(RuntimeError, match="constructed"):
            tr.train_step(_X, _Y)
        paddle.set_flags({"quantized_allreduce": False})
        tr2 = _build_trainer(flags=QFLAGS)   # built armed
        paddle.set_flags({"quantized_allreduce": False})
        with pytest.raises(RuntimeError, match="constructed"):
            tr2.train_step(_X, _Y)

    def test_incompatible_configs_raise_at_ctor(self):
        with pytest.raises(ValueError, match="sharding_stage"):
            _build_trainer(mesh_n=2, flags=QFLAGS, sharding_stage=2)
        with pytest.raises(ValueError, match="gradient merge"):
            _build_trainer(flags=QFLAGS, accumulate_steps=2)
        with pytest.raises(ValueError, match="outputs"):
            _build_trainer(flags=QFLAGS, return_outputs=True)
        with pytest.raises(ValueError, match="bits"):
            _build_trainer(flags={**QFLAGS,
                                  "quantized_allreduce_bits": 4})

    def test_localsgd_carve_out_ignores_flag(self):
        if len(jax.devices()) < 2:
            pytest.skip("needs 2 devices")
        tr = _build_trainer(mesh_n=2, flags=QFLAGS, opt="sgd",
                            localsgd_k=2)
        assert not tr._quantized
        tr.train_step(_X, _Y)   # no raise: the flag is ignored, not live

    def test_numerics_composition_rows_align(self):
        tr = _build_trainer(flags={**QFLAGS, "numerics": True,
                                   "numerics_interval": 1})
        _run(tr, 2)
        host = tr.numerics_fetch()
        layers = sorted(tr.params)
        assert host is not None
        assert host["grad_norm"].shape == (len(layers),)
        assert np.all(np.isfinite(host["grad_norm"]))
        assert float(np.sum(host["nonfinite"])) == 0.0

    def test_guard_skip_restores_residuals(self):
        from paddle_tpu.testing import failpoints as fp

        tr = _build_trainer(flags={**QFLAGS, "check_nan_inf": True})
        _run(tr, 2)
        snap_r = {k: np.asarray(v).copy()
                  for k, v in tr.opt_state["__qar_residual__"].items()}
        snap_p = {k: np.asarray(v).copy() for k, v in tr.params.items()}
        with fp.scoped("trainer/batch=scale:nan"):
            loss = tr.train_step(_X, _Y)
        assert np.isnan(float(np.asarray(loss._data)))
        for k in snap_p:
            assert np.asarray(tr.params[k]).tobytes() \
                == snap_p[k].tobytes()
        for k in snap_r:
            assert np.asarray(
                tr.opt_state["__qar_residual__"][k]).tobytes() \
                == snap_r[k].tobytes()
        # the reported error norm is the RESTORED residual's, not the
        # poisoned one the skipped step computed and threw away
        qerr = tr.quantize_error()
        assert qerr is not None and np.isfinite(qerr)
        after, _ = _run(tr, 1)
        assert np.isfinite(after)

    def test_dp_multi_device_trains_close_to_plain(self):
        # dp2 covers the real cross-rank exchange; the dp8 structure is
        # pinned by test_perf_budgets and the shard-map unit test above
        if len(jax.devices()) < 2:
            pytest.skip("needs 2 devices")
        l0, _ = _plain_ref(mesh_n=2)
        l1, _ = _run(_build_trainer(mesh_n=2, flags=QFLAGS))
        assert abs(l1 - l0) / abs(l0) < 0.05


# ---------------------------------------------------------------------------
# trainer integration — cross-replica update sharding
# ---------------------------------------------------------------------------

SFLAGS = {"shard_weight_update": True}


class TestTrainerShardUpdate:
    @pytest.mark.parametrize("opt", ["adamw", "sgd", "momentum"])
    def test_dp1_bit_exact_vs_plain(self, opt):
        _, p0 = _plain_ref(opt=opt)
        _, p1 = _run(_build_trainer(opt=opt, flags=SFLAGS))
        for k in p0:
            assert np.array_equal(p0[k], p1[k]), k

    def test_dp4_matches_plain_dp4(self):
        if len(jax.devices()) < 4:
            pytest.skip("needs 4 devices")
        _, p0 = _plain_ref(mesh_n=4)
        _, p1 = _run(_build_trainer(mesh_n=4, flags=SFLAGS))
        for k in p0:
            assert np.allclose(p0[k], p1[k], rtol=1e-5, atol=1e-6), k

    def test_moments_stored_sharded(self):
        if len(jax.devices()) < 4:
            pytest.skip("needs 4 devices")
        tr = _build_trainer(mesh_n=4, flags=SFLAGS)
        m1 = tr.opt_state["0.weight"]["moment1"]
        assert m1.shape == (4, tr._shard_ps["0.weight"])
        # beta powers stay replicated scalars
        assert tr.opt_state["0.weight"]["beta1_pow"].shape == ()
        _run(tr, 2)

    def test_global_norm_clip_matches_plain(self):
        clip = nn.ClipGradByGlobalNorm(0.01)
        _, p0 = _run(_build_trainer(grad_clip=clip))
        clip2 = nn.ClipGradByGlobalNorm(0.01)
        _, p1 = _run(_build_trainer(grad_clip=clip2, flags=SFLAGS))
        for k in p0:
            assert np.allclose(p0[k], p1[k], rtol=1e-6, atol=1e-7), k

    def test_lamb_rejected(self):
        with pytest.raises(ValueError, match="elementwise"):
            _build_trainer(opt="lamb", flags=SFLAGS)

    def test_checkpoint_roundtrip(self):
        if len(jax.devices()) < 4:
            pytest.skip("needs 4 devices")
        tr = _build_trainer(mesh_n=4, flags=SFLAGS)
        _run(tr, 2)
        state = tr.state_dict()
        tr2 = _build_trainer(mesh_n=4, flags=SFLAGS)
        tr2.set_state_dict(state)
        a, _ = _run(tr, 1)
        b, _ = _run(tr2, 1)
        assert a == b

    def test_composed_with_quantized(self):
        if len(jax.devices()) < 4:
            pytest.skip("needs 4 devices")
        l0, _ = _plain_ref(mesh_n=4)
        tr = _build_trainer(mesh_n=4, flags={**QFLAGS, **SFLAGS})
        assert tr._quantized and tr._shard_update
        l1, _ = _run(tr)
        assert abs(l1 - l0) / abs(l0) < 0.05
        assert set(tr.opt_state["__qar_residual__"]) == set(tr.params)
        # moments sharded AND residuals per-rank at once
        assert tr.opt_state["0.weight"]["moment1"].ndim == 2


# ---------------------------------------------------------------------------
# the parity harness targets, in-process
# ---------------------------------------------------------------------------

class TestParityTargets:
    def _batches(self, steps=3):
        rng = np.random.RandomState(5)
        return [(rng.randn(8, 64).astype(np.float32),
                 rng.randn(8, 8).astype(np.float32))
                for _ in range(steps)]

    def _build(self):
        net = nn.Sequential(nn.Linear(64, 64), nn.Linear(64, 8))
        opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                     parameters=net.parameters())
        mesh = build_mesh((1,), ("dp",), devices=jax.devices()[:1])
        return SpmdTrainer(net, opt, loss_fn=nn.MSELoss(), mesh=mesh)

    def test_shard_weight_update_exact(self):
        from paddle_tpu.testing import parity

        report = parity.run_parity(
            self._build, self._batches(),
            candidate_flags={"shard_weight_update": True},
            loss_rtol=0.0, loss_atol=0.0, stat_rtol=0.0, stat_atol=0.0)
        assert not report["diverged"], report["first_divergence"]
        assert report["max_abs_loss_diff"] == 0.0

    @pytest.mark.slow
    def test_quantized_within_band_and_perturbed_diverges(self):
        # the CLI form of this pair (band + must-fail control) is the
        # tier-1-adjacent slow gate in test_compress_gate.py; this
        # in-process variant costs four trainer compiles, so it rides
        # the slow lane too
        from paddle_tpu.testing import parity

        report = parity.run_parity(
            self._build, self._batches(),
            candidate_flags={"quantized_allreduce": True,
                             "quantized_allreduce_min_size": 1},
            loss_rtol=0.08, loss_atol=0.05, stat_rtol=0.6, stat_atol=0.1)
        assert not report["diverged"], report["first_divergence"]

        def cand():
            tr = self._build()
            tr.optimizer.set_lr(8e-2)
            return tr

        bad = parity.run_parity(
            self._build, self._batches(), build_candidate=cand,
            candidate_flags={"quantized_allreduce": True,
                             "quantized_allreduce_min_size": 1},
            loss_rtol=0.08, loss_atol=0.05, stat_rtol=0.6, stat_atol=0.1)
        assert bad["diverged"]
