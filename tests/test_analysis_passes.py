"""Unit tests for the graph-analysis pass registry (paddle_tpu.analysis).

One positive + one negative case per builtin pass over minimal synthetic
jaxprs, registry contract tests (duplicate names rejected, severity
ordering stable), source-lint rule tests, the Program/Predictor analysis
hooks, and regression assertions for the real findings the passes
surfaced in paddle_tpu itself (int64 position arange; np.random sites).
"""
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from paddle_tpu.analysis import (  # noqa: E402
    AnalysisReport,
    Finding,
    count_hlo_collectives,
    registered_passes,
    run_passes,
)
from paddle_tpu.analysis.registry import register_pass  # noqa: E402
from paddle_tpu.analysis.source_lint import lint_source  # noqa: E402


def _by_pass(report, name):
    return [f for f in report.findings if f.pass_name == name]


# ---------------------------------------------------------------------------
# registry contract
# ---------------------------------------------------------------------------


class TestRegistry:
    def test_battery_size(self):
        # the issue's contract: >= 8 distinct registered jaxpr passes
        assert len(registered_passes()) >= 8
        assert len(set(registered_passes())) == len(registered_passes())

    def test_duplicate_name_rejected(self):
        with pytest.raises(ValueError, match="already registered"):
            @register_pass("host-sync")
            def clone(ctx):  # pragma: no cover
                return []

    def test_bad_severity_rejected(self):
        with pytest.raises(ValueError, match="severity"):
            register_pass("x-bad-severity", severity="fatal")
        with pytest.raises(ValueError, match="severity"):
            Finding("p", "catastrophic", "m")

    def test_unknown_pass_rejected(self):
        with pytest.raises(ValueError, match="unknown analysis pass"):
            run_passes(lambda x: x + 1, 1.0, passes=["no-such-pass"])

    def test_severity_ordering_stable(self):
        rep = AnalysisReport(name="t")
        rep.add(Finding("dead-code", "info", "i1"))
        rep.add(Finding("host-sync", "warning", "w1"))
        rep.add(Finding("prng-key-reuse", "error", "e1"))
        rep.add(Finding("host-sync", "error", "e2"))
        rep.sort()
        sevs = [f.severity for f in rep.findings]
        assert sevs == ["error", "error", "warning", "info"]
        # within a severity, registration order breaks the tie (host-sync
        # registered before prng-key-reuse)
        assert [f.pass_name for f in rep.findings[:2]] == [
            "host-sync", "prng-key-reuse"]
        # sorting again is a no-op (stable)
        again = [f.message for f in rep.sort().findings]
        assert again == ["e2", "e1", "w1", "i1"]

    def test_report_roundtrip(self):
        rep = run_passes(lambda x: x * 2.0, jnp.ones(3), name="t")
        d = rep.to_dict()
        assert d["name"] == "t"
        assert set(d["counts"]) == {"error", "warning", "info"}
        for f in d["findings"]:
            assert set(f) == {"pass", "severity", "message", "where"}

    def test_pass_subset_runs(self):
        rep = run_passes(lambda x: x + 1.0, jnp.ones(3),
                         passes=["host-sync"])
        assert rep.findings == []


# ---------------------------------------------------------------------------
# per-pass positive/negative cases
# ---------------------------------------------------------------------------


class TestHostSync:
    def test_positive_pure_callback(self):
        def f(x):
            return jax.pure_callback(
                lambda a: np.asarray(a) * 2, jax.ShapeDtypeStruct(
                    (3,), np.float32), x)

        rep = run_passes(f, jnp.ones(3), passes=["host-sync"])
        assert len(rep.errors) == 1
        assert "pure_callback" in rep.errors[0].message

    def test_positive_debug_callback_is_warning(self):
        def f(x):
            jax.debug.print("x={}", x)
            return x + 1

        rep = run_passes(f, jnp.ones(3), passes=["host-sync"])
        assert not rep.errors and len(rep.warnings) == 1

    def test_negative(self):
        rep = run_passes(lambda x: jnp.sin(x) + 1, jnp.ones(3),
                         passes=["host-sync"])
        assert rep.findings == []


class TestPrngKeyReuse:
    def test_positive_same_key_two_samplers(self):
        def f(k):
            return jax.random.uniform(k, (3,)) + jax.random.normal(k, (3,))

        rep = run_passes(f, jax.random.key(0), passes=["prng-key-reuse"])
        assert len(rep.errors) == 1
        assert "consumed 2x" in rep.errors[0].message

    def test_positive_double_split(self):
        # split(k) twice yields IDENTICAL subkeys — reuse even though no
        # sampler touches k directly
        def f(k, x):
            k1, _ = jax.random.split(k)
            k2, _ = jax.random.split(k)
            return (jax.random.uniform(k1, (2,))
                    + jax.random.uniform(k2, (2,)) + x)

        rep = run_passes(f, jax.random.key(0), jnp.ones(2),
                         passes=["prng-key-reuse"])
        assert len(rep.errors) >= 1

    def test_negative_split_chain(self):
        def f(k):
            k1, k2 = jax.random.split(k)
            return jax.random.uniform(k1, (3,)) + jax.random.normal(
                k2, (3,))

        rep = run_passes(f, jax.random.key(0), passes=["prng-key-reuse"])
        assert rep.findings == []

    def test_negative_fold_in_distinct_data(self):
        # the documented-safe compress.py idiom: per-rank/per-phase
        # fold_ins of ONE key with DISTINCT data (ISSUE 13 fix — this
        # false-positived the first time the quantized program was
        # analyzed)
        def f(k):
            a = jax.random.uniform(jax.random.fold_in(k, 1), (2,))
            b = jax.random.uniform(jax.random.fold_in(k, 2), (2,))
            return a + b

        rep = run_passes(f, jax.random.key(0), passes=["prng-key-reuse"])
        assert rep.findings == []

    def test_positive_fold_in_same_data_twice(self):
        def f(k):
            a = jax.random.uniform(jax.random.fold_in(k, 7), (2,))
            b = jax.random.normal(jax.random.fold_in(k, 7), (2,))
            return a + b

        rep = run_passes(f, jax.random.key(0), passes=["prng-key-reuse"])
        assert len(rep.errors) == 1

    def test_positive_sink_mixed_with_fold(self):
        # a raw sink consumption of a key that is ALSO folded stays a
        # finding (the review-caught false-negative window)
        def f(k):
            a = jax.random.uniform(k, (2,))
            b = jax.random.uniform(jax.random.fold_in(k, 3), (2,))
            return a + b

        rep = run_passes(f, jax.random.key(0), passes=["prng-key-reuse"])
        assert len(rep.errors) == 1
        assert "random_fold_in" in rep.errors[0].message

    def test_negative_distinct_slices_of_split(self):
        # the canonical dropout chain: keys[0] / keys[1] are different
        # slices of one split — aliases must not be conflated
        def f(k):
            keys = jax.random.split(k, 4)
            return (jax.random.uniform(keys[0], (2,))
                    + jax.random.uniform(keys[1], (2,))
                    + jax.random.uniform(keys[2], (2,)))

        rep = run_passes(f, jax.random.key(0), passes=["prng-key-reuse"])
        assert rep.findings == []

    def test_negative_traced_index_selection(self):
        # keys[i] / keys[j] with TRACED indices: value-dependent selection
        # must stay conservative (distinct identities), never a
        # false-positive error on correct code
        def f(k, i, j):
            keys = jax.random.split(k, 4)
            return (jax.random.uniform(keys[i], (2,))
                    + jax.random.uniform(keys[j], (2,)))

        rep = run_passes(f, jax.random.key(0), jnp.int32(0), jnp.int32(1),
                         passes=["prng-key-reuse"])
        assert rep.findings == []

    def test_positive_same_slice_twice(self):
        def f(k):
            keys = jax.random.split(k, 4)
            return (jax.random.uniform(keys[0], (2,))
                    + jax.random.normal(keys[0], (2,)))

        rep = run_passes(f, jax.random.key(0), passes=["prng-key-reuse"])
        assert len(rep.errors) == 1


class TestPrngConstKey:
    def test_positive_baked_key(self):
        k = jax.random.key(7)   # closed over -> baked trace constant

        def f(x):
            return x + jax.random.uniform(k, (3,))

        rep = run_passes(f, jnp.ones(3), passes=["prng-const-key"])
        assert len(rep.warnings) == 1
        assert "baked" in rep.warnings[0].message

    def test_negative_threaded_key(self):
        def f(k, x):
            return x + jax.random.uniform(k, (3,))

        rep = run_passes(f, jax.random.key(0), jnp.ones(3),
                         passes=["prng-const-key"])
        assert rep.findings == []


class TestDtypePromotion:
    def test_positive_bf16_widening(self):
        def f(x):
            return x.astype(jnp.float32) * 2.0

        rep = run_passes(f, jnp.ones(3, jnp.bfloat16),
                         passes=["dtype-promotion"])
        assert len(rep.warnings) == 1
        assert "bfloat16->float32" in rep.warnings[0].message

    def test_negative_same_width(self):
        def f(x):
            return x.astype(jnp.int32) + 1

        rep = run_passes(f, jnp.ones(3, jnp.float32),
                         passes=["dtype-promotion"])
        assert rep.findings == []

    def test_aggregated_count(self):
        def f(x, y):
            return x.astype(jnp.float32) + y.astype(jnp.float32)

        rep = run_passes(f, jnp.ones(3, jnp.bfloat16),
                         jnp.ones(3, jnp.bfloat16),
                         passes=["dtype-promotion"])
        assert len(rep.warnings) == 1       # one finding per (src, dst)
        assert "x2" in rep.warnings[0].message


class TestDeadCode:
    def test_positive(self):
        def f(x):
            dead = jnp.sin(x) * 2.0  # noqa: F841 — deliberately unused
            return x + 1

        rep = run_passes(f, jnp.ones(3), passes=["dead-code"])
        assert len(rep.findings) == 1
        assert "sin" in rep.findings[0].message

    def test_negative(self):
        rep = run_passes(lambda x: jnp.sin(x) + 1, jnp.ones(3),
                         passes=["dead-code"])
        assert rep.findings == []


class TestRecompileHazard:
    def test_positive_scalar_const(self):
        c = jnp.float32(3.0)   # 0-d array closed over -> trace const

        def f(x):
            return x * c

        rep = run_passes(f, jnp.ones(3), passes=["recompile-hazard"])
        assert len(rep.findings) == 1
        assert "scalar" in rep.findings[0].message

    def test_positive_large_baked_array(self):
        w = jnp.ones((64, 64))

        def f(x):
            return x @ w

        rep = run_passes(f, jnp.ones((2, 64)), passes=["recompile-hazard"],
                         large_threshold=1024)
        assert len(rep.warnings) == 1
        assert "closed over" in rep.warnings[0].message

    def test_negative_args_only(self):
        rep = run_passes(lambda x, w: x @ w, jnp.ones((2, 4)),
                         jnp.ones((4, 4)), passes=["recompile-hazard"])
        assert rep.findings == []


class TestCollectiveCount:
    def test_positive_psum(self):
        closed = jax.make_jaxpr(lambda x: jax.lax.psum(x, "i"),
                                axis_env=[("i", 2)])(1.0)
        rep = run_passes(closed, passes=["collective-count"])
        assert len(rep.findings) == 1
        assert "all-reduce" in rep.findings[0].message

    def test_negative(self):
        rep = run_passes(lambda x: x + 1, jnp.ones(3),
                         passes=["collective-count"])
        assert rep.findings == []

    def test_hlo_counter_format(self):
        # the exact-count machinery the perf-budget gate shares
        hlo = ("%a = all-reduce(x), %b = all-gather-start(y), "
               "%c = reduce-scatter(z), %d = all-reduce(w)")
        got = count_hlo_collectives(hlo)
        assert got == {"all-reduce": 2, "all-gather": 1,
                       "reduce-scatter": 1}


class TestQuantizedCollectiveClassifier:
    """count_quantized_collectives: the int8 exchange/gather pair of a
    wire-compressed all-reduce (distributed/compress.py), classified by
    payload dtype so the perf-budget gate can pin exact counts."""

    @staticmethod
    def _pair(dtype):
        def f(x):
            q = x.astype(dtype).reshape(2, -1)
            ex = jax.lax.all_to_all(q, "i", split_axis=0, concat_axis=0)
            return jax.lax.all_gather(ex.reshape(-1)[:4], "i",
                                      tiled=True)

        return jax.make_jaxpr(f, axis_env=[("i", 2)])(jnp.ones(8))

    def test_positive_int8_pair(self):
        from paddle_tpu.analysis.collectives import \
            count_quantized_collectives

        got = count_quantized_collectives(self._pair(jnp.int8).jaxpr)
        assert got == {"quantized-reduce-scatter": 1,
                       "quantized-all-gather": 1}

    def test_negative_fp32_pair_not_classified(self):
        from paddle_tpu.analysis.collectives import \
            count_quantized_collectives

        got = count_quantized_collectives(self._pair(jnp.float32).jaxpr)
        assert got == {"quantized-reduce-scatter": 0,
                       "quantized-all-gather": 0}

    def test_negative_plain_model(self):
        from paddle_tpu.analysis.collectives import \
            count_quantized_collectives

        closed = jax.make_jaxpr(lambda x: x @ x)(jnp.ones((4, 4)))
        got = count_quantized_collectives(closed.jaxpr)
        assert sum(got.values()) == 0

    def test_pass_emits_classification(self):
        rep = run_passes(self._pair(jnp.int8),
                         passes=["collective-count"])
        msgs = [f.message for f in _by_pass(rep, "collective-count")]
        assert any("quantized reduce family" in m for m in msgs), msgs

    def test_pass_silent_without_quantized_ops(self):
        closed = jax.make_jaxpr(lambda x: jax.lax.psum(x, "i"),
                                axis_env=[("i", 2)])(1.0)
        rep = run_passes(closed, passes=["collective-count"])
        msgs = [f.message for f in _by_pass(rep, "collective-count")]
        assert msgs and not any("quantized" in m for m in msgs)


class TestImplicitReplication:
    """The ISSUE 13 upgrade of unsharded-large-tensor: spec propagation
    with provenance — only replication MATERIALIZED in-graph fires."""

    def _mesh(self, n=2):
        return jax.sharding.Mesh(np.array(jax.devices()[:n]), ("dp",))

    def test_positive_materialized_with_provenance(self):
        mesh = self._mesh()

        def f(x):
            big = jnp.broadcast_to(jnp.arange(64, dtype=jnp.float32),
                                   (64, 64))
            return x + big.sum()

        from jax.sharding import NamedSharding, PartitionSpec as P

        cj = jax.make_jaxpr(jax.jit(
            f, in_shardings=NamedSharding(mesh, P("dp"))))(jnp.ones((8,)))
        rep = run_passes(cj, passes=["implicit-replication"], mesh=mesh,
                         large_threshold=1024)
        assert len(rep.warnings) == 1
        msg = rep.warnings[0].message
        assert "materialized replicated" in msg
        assert "provenance:" in msg and "broadcast_in_dim" in msg

    def test_negative_derived_from_sharded_input(self):
        mesh = self._mesh()
        from jax.sharding import NamedSharding, PartitionSpec as P

        def f(x):
            return (x @ x.T).sum()

        cj = jax.make_jaxpr(jax.jit(
            f, in_shardings=NamedSharding(mesh, P("dp"))))(
                jnp.ones((64, 64)))
        rep = run_passes(cj, passes=["implicit-replication"], mesh=mesh,
                         large_threshold=1024)
        assert rep.findings == []

    def test_negative_declared_replicated_input_is_intentional(self):
        mesh = self._mesh()
        from jax.sharding import NamedSharding, PartitionSpec as P

        def f(w):
            return w * 0.01   # dp-replicated weight-decay-style math

        cj = jax.make_jaxpr(jax.jit(
            f, in_shardings=NamedSharding(mesh, P())))(jnp.ones((64, 64)))
        rep = run_passes(cj, passes=["implicit-replication"], mesh=mesh,
                         large_threshold=1024)
        assert rep.findings == []

    def test_negative_no_mesh(self):
        def f(x):
            return jnp.broadcast_to(jnp.arange(64, dtype=jnp.float32),
                                    (64, 64)).sum() + x

        rep = run_passes(f, jnp.ones(()),
                         passes=["implicit-replication"],
                         large_threshold=1024)
        assert rep.findings == []

    def test_negative_constrained_value_not_flagged(self):
        mesh = self._mesh()
        from jax.sharding import NamedSharding, PartitionSpec as P

        def f(x):
            big = jnp.broadcast_to(jnp.arange(64, dtype=jnp.float32),
                                   (64, 64))
            big = jax.lax.with_sharding_constraint(
                big, NamedSharding(mesh, P("dp")))
            return x + big.sum()

        cj = jax.make_jaxpr(jax.jit(
            f, in_shardings=NamedSharding(mesh, P("dp"))))(jnp.ones((8,)))
        rep = run_passes(cj, passes=["implicit-replication"], mesh=mesh,
                         large_threshold=1024)
        assert rep.findings == []


class TestDonationMiss:
    def test_positive_info_when_unknown(self):
        def f(state, x):
            return state + x, jnp.sum(x)

        rep = run_passes(f, jnp.ones((64, 64)), jnp.ones((64, 64)),
                         passes=["donation-miss"], large_threshold=1024)
        assert len(rep.findings) == 1
        assert rep.findings[0].severity == "info"

    def test_positive_warning_with_known_donation(self):
        def f(state, x):
            return state + x

        rep = run_passes(f, jnp.ones((64, 64)), jnp.ones((64, 64)),
                         passes=["donation-miss"], large_threshold=1024,
                         donated=set())
        assert [f.severity for f in rep.findings].count("warning") == 1

    def test_negative_donated(self):
        def f(state, x):
            return state + x

        rep = run_passes(f, jnp.ones((64, 64)), jnp.ones((64, 64)),
                         passes=["donation-miss"], large_threshold=1024,
                         donated={0, 1})
        assert rep.findings == []


# ---------------------------------------------------------------------------
# source-lint rules
# ---------------------------------------------------------------------------


class TestSourceLint:
    def test_np_random_positive(self):
        src = ("import numpy as np\n"
               "def op(x):\n"
               "    return x + np.random.randn(3)\n")
        fs = lint_source(src, "nn/functional/fake.py", traced=True)
        assert [f.pass_name for f in fs] == ["np-random-in-traced-code"]
        assert fs[0].severity == "error"
        assert fs[0].where == "nn/functional/fake.py:3"

    def test_np_random_init_exempt(self):
        src = ("import numpy as np\n"
               "class L:\n"
               "    def __init__(self):\n"
               "        self.w = np.random.randn(3)\n")
        assert lint_source(src, "nn/x.py", traced=True) == []

    def test_np_random_untraced_module_exempt(self):
        src = ("import numpy as np\n"
               "def sample(x):\n"
               "    return np.random.permutation(x)\n")
        assert lint_source(src, "io/sampler.py", traced=False) == []

    def test_suppression_comment(self):
        src = ("import numpy as np\n"
               "def op(x):\n"
               "    r = np.random.RandomState(0)  "
               "# lint: allow(np-random-in-traced-code)\n"
               "    return x\n")
        assert lint_source(src, "nn/x.py", traced=True) == []

    def test_time_in_traced_code(self):
        src = ("import time\n"
               "def fwd(x):\n"
               "    return x * time.time()\n")
        fs = lint_source(src, "models/x.py", traced=True)
        assert [f.pass_name for f in fs] == ["time-in-traced-code"]
        assert fs[0].severity == "warning"

    def test_mutable_default_positive(self):
        src = ("class MyBlock(nn.Layer):\n"
               "    def forward(self, x, hooks=[]):\n"
               "        return x\n")
        fs = lint_source(src, "nn/layer/fake.py", traced=True)
        assert [f.pass_name for f in fs] == ["mutable-default-arg"]
        assert fs[0].severity == "error"

    def test_mutable_default_non_layer_exempt(self):
        src = ("class Helper:\n"
               "    def run(self, x, hooks=[]):\n"
               "        return x\n")
        assert lint_source(src, "nn/layer/fake.py", traced=True) == []

    def test_private_model_import_in_serving_positive(self):
        # both module-level and function-level imports are caught
        src = ("from ..models.gpt import _decode_fns\n"
               "def build():\n"
               "    from ..models.gpt import _tp_wrap, GPTConfig\n")
        fs = lint_source(src, "inference/serving.py", traced=False)
        assert [f.pass_name for f in fs] == \
            ["private-model-import-in-serving"] * 2
        assert all(f.severity == "error" for f in fs)
        assert fs[0].where == "inference/serving.py:1"
        # the serving/ package is covered too
        fs = lint_source("from ..models.bert import _x\n",
                         "serving/router.py", traced=False)
        assert [f.pass_name for f in fs] == \
            ["private-model-import-in-serving"]

    def test_private_model_import_public_and_elsewhere_exempt(self):
        # public names are the supported surface
        assert lint_source("from ..models.gpt import GPTForCausalLM\n",
                           "inference/predictor.py", traced=False) == []
        # model modules may use their own privates (adapter registration)
        assert lint_source("from .gpt import _decode_fns\n",
                           "models/zoo.py", traced=True) == []
        # non-serving packages are out of scope for this rule
        assert lint_source("from ..models.gpt import _decode_fns\n",
                           "hapi/model.py", traced=False) == []

    def test_private_model_import_allow_marker(self):
        src = ("from ..models.gpt import _x  "
               "# lint: allow(private-model-import-in-serving)\n")
        assert lint_source(src, "inference/serving.py", traced=False) == []


class TestNonreducedClientOutput:
    """ISSUE 8 lint satellite: a client_map result must not escape a
    federated/ API without passing through a federated_* reduce (or carry
    an explicit `# lint: allow(client_output)` marker)."""

    def test_positive_assigned_then_returned(self):
        src = ("def api(xs):\n"
               "    vals = client_map(fn, xs)\n"
               "    return vals\n")
        fs = lint_source(src, "federated/primitives.py", traced=False)
        assert [f.pass_name for f in fs] == ["nonreduced-client-output"]
        assert fs[0].severity == "error"
        assert "federated_sum" in fs[0].message

    def test_positive_direct_return(self):
        src = ("def api(xs):\n"
               "    return client_map(fn, xs)\n")
        fs = lint_source(src, "federated/averaging.py", traced=False)
        assert [f.pass_name for f in fs] == ["nonreduced-client-output"]

    def test_positive_in_tuple_return(self):
        src = ("def api(xs):\n"
               "    vals = client_map(fn, xs)\n"
               "    total = federated_sum(other(xs))\n"
               "    return total, vals\n")
        fs = lint_source(src, "federated/x.py", traced=False)
        assert [f.pass_name for f in fs] == ["nonreduced-client-output"]

    def test_negative_value_fed_through_reduce_expression(self):
        """A name consumed INSIDE a reduce's argument expression counts
        as reduced (the heuristic clears every name the reduce saw)."""
        src = ("def api(xs):\n"
               "    vals = client_map(fn, xs)\n"
               "    return federated_sum(vals * 2)\n")
        assert lint_source(src, "federated/x.py", traced=False) == []

    def test_negative_reduced_before_return(self):
        src = ("def api(xs):\n"
               "    vals = client_map(fn, xs)\n"
               "    return federated_mean(vals)\n")
        assert lint_source(src, "federated/primitives.py",
                           traced=False) == []

    def test_negative_client_reduce_chokepoint(self):
        src = ("def api(xs):\n"
               "    vals = client_map(fn, xs)\n"
               "    out = _coll.client_reduce(vals)\n"
               "    return out\n")
        assert lint_source(src, "federated/primitives.py",
                           traced=False) == []

    def test_negative_rebound_name(self):
        src = ("def api(xs):\n"
               "    vals = client_map(fn, xs)\n"
               "    vals = federated_sum(vals)\n"
               "    return vals\n")
        assert lint_source(src, "federated/x.py", traced=False) == []

    def test_allow_marker_short_and_full(self):
        src = ("def api(xs):\n"
               "    vals = client_map(fn, xs)\n"
               "    return vals  # lint: allow(client_output)\n")
        assert lint_source(src, "federated/primitives.py",
                           traced=False) == []
        src2 = ("def api(xs):\n"
                "    vals = client_map(fn, xs)\n"
                "    return vals  # lint: allow(nonreduced-client-output)\n")
        assert lint_source(src2, "federated/primitives.py",
                           traced=False) == []

    def test_rule_scoped_to_federated_modules(self):
        src = ("def api(xs):\n"
               "    vals = client_map(fn, xs)\n"
               "    return vals\n")
        assert lint_source(src, "distributed/spmd.py", traced=False) == []
        assert lint_source(src, "nn/layer/common.py", traced=True) == []

    def test_repo_federated_package_is_clean(self):
        """paddle_tpu's own federated/ modules hold the bar the rule
        sets (any deliberate client-placed return carries the marker)."""
        import os

        import paddle_tpu.federated as fed

        root = os.path.dirname(os.path.abspath(fed.__file__))
        for fn in sorted(os.listdir(root)):
            if not fn.endswith(".py"):
                continue
            with open(os.path.join(root, fn), encoding="utf-8") as f:
                src = f.read()
            fs = lint_source(src, f"federated/{fn}", traced=False)
            assert [f_ for f_ in fs
                    if f_.pass_name == "nonreduced-client-output"] == []


# ---------------------------------------------------------------------------
# analysis hooks: static Program and inference Predictor
# ---------------------------------------------------------------------------


class TestAnalysisHooks:
    def test_program_analysis_jaxpr(self):
        import paddle_tpu as paddle
        import paddle_tpu.static as static

        paddle.enable_static()
        try:
            main, startup = static.Program(), static.Program()
            with static.program_guard(main, startup):
                x = static.data("x", [None, 8], "float32")
                w = paddle.ones([8, 4])
                w.persistable = True
                y = paddle.nn.functional.relu(paddle.matmul(x, w))
            exe = static.Executor()
            exe.run(startup)
            exe.run(main, feed={"x": np.ones((2, 8), np.float32)},
                    fetch_list=[y])
            closed = main.analysis_jaxpr(
                feed={"x": np.ones((2, 8), np.float32)})
            assert closed.jaxpr.eqns, "expected a non-empty replay jaxpr"
            rep = run_passes(closed, name="static_program")
            assert rep.errors == []
        finally:
            paddle.disable_static()

    def test_program_analysis_jaxpr_train_form(self):
        # a program with an optimizer attached traces the TRAIN step —
        # the graph Executor.run actually executes for it (fwd + grads +
        # update), not the eval forward
        import paddle_tpu as paddle
        import paddle_tpu.static as static

        paddle.enable_static()
        try:
            main, startup = static.Program(), static.Program()
            with static.program_guard(main, startup):
                x = static.data("x", [None, 4], "float32")
                w = paddle.ones([4, 1])
                w.persistable = True
                loss = paddle.mean(paddle.matmul(x, w))
                opt = paddle.optimizer.SGD(learning_rate=0.1)
                opt.minimize(loss)
            exe = static.Executor()
            exe.run(startup)
            eval_closed = main.clone(for_test=True).analysis_jaxpr(
                feed={"x": np.ones((2, 4), np.float32)})
            train_closed = main.analysis_jaxpr(
                feed={"x": np.ones((2, 4), np.float32)})
            # train step takes (params, opt_state, lr, feed) and computes
            # grads + the update — strictly more work than the eval form
            assert len(train_closed.jaxpr.eqns) > len(
                eval_closed.jaxpr.eqns)
            assert run_passes(train_closed, name="train_prog").errors == []
        finally:
            paddle.disable_static()

    def test_program_analysis_jaxpr_empty_program(self):
        import paddle_tpu.static as static

        with pytest.raises(ValueError, match="empty program"):
            static.Program().analysis_jaxpr()

    def test_predictor_analysis_jaxpr(self, tmp_path):
        import paddle_tpu as paddle
        from paddle_tpu import jit as pjit
        from paddle_tpu.inference.predictor import Config, create_predictor
        from paddle_tpu.jit import InputSpec

        m = paddle.nn.Linear(8, 4)
        path = str(tmp_path / "lin")
        pjit.save(m, path, input_spec=[InputSpec([None, 8], "float32")])
        pred = create_predictor(Config(path))
        closed = pred.analysis_jaxpr(
            inputs=[np.ones((2, 8), np.float32)])
        assert closed.jaxpr.eqns
        assert run_passes(closed, name="predictor").errors == []

    def test_predictor_surplus_input_does_not_poison(self, tmp_path):
        # an accidental extra positional input fails ITS call (the layer
        # rejects the arity) but must not persist into later calls
        import paddle_tpu as paddle
        from paddle_tpu import jit as pjit
        from paddle_tpu.inference.predictor import Config, create_predictor
        from paddle_tpu.jit import InputSpec

        m = paddle.nn.Linear(8, 4)
        path = str(tmp_path / "lin")
        pjit.save(m, path, input_spec=[InputSpec([None, 8], "float32")])
        pred = create_predictor(Config(path))
        x = np.ones((2, 8), np.float32)
        with pytest.raises(TypeError):
            pred.run([x, np.ones((2, 8), np.float32)])
        assert pred.get_input_names() == ["input_0"]
        (out,) = pred.run([x])
        assert out.shape == (2, 4)


class TestToHostFlag:
    def test_error_mode_names_the_sync(self):
        import paddle_tpu as paddle

        paddle.set_flags({"trace_host_sync": "error"})
        try:
            def f(x):
                return paddle.to_tensor(x).numpy()

            with pytest.raises(RuntimeError, match="host sync"):
                jax.jit(f)(np.ones(3, np.float32))
        finally:
            paddle.set_flags({"trace_host_sync": "silent"})

    def test_warn_mode_warns_then_jax_raises(self):
        import paddle_tpu as paddle

        paddle.set_flags({"trace_host_sync": "warn"})
        try:
            def f(x):
                return paddle.to_tensor(x).item()

            with pytest.warns(UserWarning, match="host sync"):
                with pytest.raises(Exception):
                    jax.jit(f)(np.ones((), np.float32))
        finally:
            paddle.set_flags({"trace_host_sync": "silent"})

    def test_eager_unaffected(self):
        import paddle_tpu as paddle

        t = paddle.to_tensor([1.0, 2.0])
        assert t.numpy().tolist() == [1.0, 2.0]
        assert paddle.to_tensor(3.5).item() == 3.5


class TestStepLoopHostSync:
    """ISSUE 11: per-step host pulls inside the trainer/serving hot
    paths are errors unless they carry the allow-marker."""

    HOT = ("import numpy as np\n"
           "class SpmdTrainer:\n"
           "    def _train_step_impl(self, x):\n"
           "        return np.asarray(x)\n")

    def test_positive_np_asarray_in_hot_path(self):
        fs = lint_source(self.HOT,
                         os.path.join("distributed", "spmd.py"))
        assert [f.pass_name for f in fs] == ["step-loop-host-sync"]
        assert fs[0].severity == "error"

    def test_positive_item_and_block_until_ready(self):
        src = ("class ServingEngine:\n"
               "    def _step_inner(self, toks):\n"
               "        toks.block_until_ready()\n"
               "        return toks.item()\n")
        fs = lint_source(src, os.path.join("inference", "serving.py"))
        assert [f.pass_name for f in fs] == ["step-loop-host-sync"] * 2

    def test_positive_nested_closure_in_hot_path_counts(self):
        src = ("import numpy as np\n"
               "class SpmdTrainer:\n"
               "    def _drain_verdicts(self, vals):\n"
               "        def inner(v):\n"
               "            return np.asarray(v)\n"
               "        return [inner(v) for v in vals]\n")
        fs = lint_source(src, os.path.join("distributed", "spmd.py"))
        assert [f.pass_name for f in fs] == ["step-loop-host-sync"]

    def test_negative_allow_marker(self):
        src = ("import numpy as np\n"
               "class SpmdTrainer:\n"
               "    def _train_step_impl(self, x):\n"
               "        return np.asarray(x)"
               "  # lint: allow(step-loop-host-sync)\n")
        assert lint_source(src,
                           os.path.join("distributed", "spmd.py")) == []

    def test_negative_outside_hot_functions_and_files(self):
        src = ("import numpy as np\n"
               "class SpmdTrainer:\n"
               "    def stats(self, x):\n"
               "        return np.asarray(x)\n")
        assert lint_source(src,
                           os.path.join("distributed", "spmd.py")) == []
        assert lint_source(self.HOT, "nn/layer/fake.py",
                           traced=False) == []

    def test_repo_hot_paths_are_clean(self):
        # the ISSUE 11 satellite: after the deferred-guard fix, the
        # live spmd/serving hot paths carry ONLY allow-marked syncs
        from paddle_tpu.analysis.source_lint import lint_path

        fs = [f for f in lint_path()
              if f.pass_name == "step-loop-host-sync"]
        assert fs == [], [f.where for f in fs]

    def test_repo_allow_markers_still_present(self):
        # the deliberate syncs double as documentation: the windowed
        # drain fetch, the benchmark sync, the decode token fetch
        for rel, needle in (
                ("paddle_tpu/distributed/spmd.py", "device_get"),
                ("paddle_tpu/inference/serving.py", "np.asarray"),
        ):
            src = open(os.path.join(REPO, rel)).read()
            marked = [ln for ln in src.splitlines()
                      if "lint: allow(step-loop-host-sync)" in ln]
            assert any(needle in ln for ln in marked), (rel, needle)


# ---------------------------------------------------------------------------
# regression assertions for the real findings the passes surfaced
# ---------------------------------------------------------------------------


class TestRepoRegressions:
    def test_model_position_ids_are_int32(self):
        # the passes' first real catch: all four position embeddings
        # requested arange(dtype="int64"), truncated with a per-call
        # UserWarning (x64 off). Pinned here via the trace-warnings
        # channel: tracing each bundled model must be warning-clean.
        from paddle_tpu.analysis import analyze_model

        for name in ("gpt", "bert", "ernie"):
            rep = analyze_model(name)
            assert _by_pass(rep, "trace-warnings") == [], (
                f"{name}: tracing the forward raised python warnings "
                f"again: {[f.message for f in rep.findings]}")
            assert rep.errors == []

    def test_no_unsuppressed_np_random_in_traced_code(self):
        # the two deliberate eager-host samplers (nce, tdm_sampler) carry
        # `# lint: allow(...)` markers; anything NEW fails here
        from paddle_tpu.analysis.source_lint import lint_path

        fs = [f for f in lint_path()
              if f.pass_name == "np-random-in-traced-code"]
        assert fs == [], [f.where for f in fs]

    def test_allow_markers_still_present(self):
        # the suppressions double as documentation — removing the comment
        # (or the guard it documents) must trip the gate, not pass silently
        for rel in ("paddle_tpu/nn/functional/extension.py",
                    "paddle_tpu/nn/functional/loss.py"):
            src = open(os.path.join(REPO, rel)).read()
            assert "lint: allow(np-random-in-traced-code)" in src, rel


# ---------------------------------------------------------------------------
# ISSUE 12: contract-auditor passes (flag / import / observability / thread)
# ---------------------------------------------------------------------------

from paddle_tpu.analysis import allowlist  # noqa: E402
from paddle_tpu.analysis import flag_audit  # noqa: E402
from paddle_tpu.analysis import import_graph  # noqa: E402
from paddle_tpu.analysis import obs_audit  # noqa: E402
from paddle_tpu.analysis.source_lint import (  # noqa: E402
    THREAD_SHARED_MODULES, lint_thread_discipline)


def _flag_findings(sources, **kw):
    kw.setdefault("hot_paths", {})
    kw.setdefault("lazy_modules", ())
    return flag_audit.audit_inventory(flag_audit.collect(sources), **kw)


def _rules_of(findings):
    return {f.pass_name for f in findings}


class TestFlagAudit:
    def test_orphan_flag_unread_planted(self):
        fs = _flag_findings({"m.py": 'define_flag("dead_probe", 0, "h")\n'})
        assert _rules_of(fs) == {"orphan-flag-unread"}
        assert fs[0].severity == "error"
        assert "dead_probe" in fs[0].message

    def test_read_flag_is_not_orphan(self):
        fs = _flag_findings({
            "m.py": 'define_flag("live_probe", 0, "h")\n',
            "n.py": 'x = get_flag("live_probe", 0)\n'})
        assert fs == []

    def test_orphan_flag_undefined_planted(self):
        fs = _flag_findings({"m.py": 'x = get_flag("never_defined")\n'})
        assert _rules_of(fs) == {"orphan-flag-undefined"}

    def test_missing_help_planted(self):
        fs = _flag_findings({
            "m.py": 'define_flag("helpless", 1)\n'
                    'y = get_flag("helpless")\n'})
        assert _rules_of(fs) == {"flag-missing-help"}

    def test_conflicting_default_planted(self):
        fs = _flag_findings({
            "a.py": 'define_flag("dup", 1, "h")\nga = get_flag("dup")\n',
            "b.py": 'define_flag("dup", 2, "h")\n'})
        assert "flag-default-conflict" in _rules_of(fs)

    def test_default_drift_warns(self):
        fs = _flag_findings({
            "a.py": 'define_flag("drifty", 8, "h")\n',
            "b.py": 'x = get_flag("drifty", 4)\n'})
        assert _rules_of(fs) == {"flag-default-drift"}
        assert all(f.severity == "warning" for f in fs)

    def test_structural_key_miss_planted(self):
        src = ('define_flag("structural_probe", False, "h")\n'
               'def consume(self):\n'
               '    self._sp = get_flag("structural_probe", False)\n')
        fs = _flag_findings({"m.py": src},
                            structural=("structural_probe",))
        assert "structural-flag-key-miss" in _rules_of(fs)

    def test_structural_flag_reaching_exec_key_is_clean(self):
        src = ('define_flag("structural_ok", False, "h")\n'
               'def consume(self):\n'
               '    self._sp = get_flag("structural_ok", False)\n'
               'def _exec_key(self, sig):\n'
               '    return (sig, self._sp)\n')
        fs = _flag_findings({"m.py": src}, structural=("structural_ok",))
        assert fs == []

    def test_structural_flag_via_carrier_hop_is_clean(self):
        # the spmd.py shape: _resolve() consumes the flag, its result is
        # assigned to self._q, and self._q joins the key
        src = ('define_flag("structural_hop", False, "h")\n'
               'def _resolve(self):\n'
               '    return get_flag("structural_hop", False)\n'
               'def __init__(self):\n'
               '    self._q = self._resolve()\n'
               'def _exec_key(self, sig):\n'
               '    return (sig, self._q)\n')
        fs = _flag_findings({"m.py": src},
                            structural=("structural_hop",))
        assert fs == []

    def test_hot_path_flag_read_planted(self):
        src = ('define_flag("hot_probe", False, "h")\n'
               'def train_step(self):\n'
               '    if get_flag("hot_probe", False):\n'
               '        pass\n')
        fs = _flag_findings({"m.py": src}, structural=("hot_probe",),
                            hot_paths={"m.py": {"train_step"}})
        assert "hot-path-flag-read" in _rules_of(fs)

    def test_active_checker_read_is_sanctioned(self):
        src = ('define_flag("hot_ok", False, "h")\n'
               'def _guard_active(self):\n'
               '    return get_flag("hot_ok", False) == self._g\n'
               'def _exec_key(self, sig):\n'
               '    return (sig, self._guard_active())\n')
        fs = _flag_findings({"m.py": src}, structural=("hot_ok",),
                            hot_paths={"m.py": {"_guard_active"}})
        assert fs == []

    def test_allow_marker_suppresses_orphan(self):
        fs = _flag_findings({
            "m.py": 'define_flag("stub", 0, "h")'
                    '  # lint: allow(orphan-flag)\n'})
        assert fs == []

    def test_repo_flags_are_clean(self):
        assert flag_audit.audit_package() == []

    def test_repo_structural_flags_all_reach_keys(self):
        # every declared structural flag exists AND joins a key — the
        # acceptance-criterion form of the pass over the real tree
        scans = flag_audit.collect(flag_audit.package_sources())
        defined = set()
        for s in scans.values():
            defined |= {n for n, _, _, _ in s.defines}
        assert set(flag_audit.STRUCTURAL_FLAGS) <= defined


class TestImportGraphAudit:
    def _graph(self, sources):
        return import_graph.build_graph(sources=sources)

    def test_eager_leak_planted(self):
        g = self._graph({
            "pkg": "",
            "pkg.core": "from . import heavy\n",
            "pkg.heavy": "",
        })
        fs = import_graph.audit_graph(g, manifest=("pkg.heavy",),
                                      roots=("pkg.core",))
        assert [f.pass_name for f in fs] == ["lazy-module-leak"]
        assert "pkg.core -> pkg.heavy" in fs[0].message

    def test_function_local_import_is_lazy(self):
        g = self._graph({
            "pkg": "",
            "pkg.core": "def go():\n    from . import heavy\n",
            "pkg.heavy": "",
        })
        fs = import_graph.audit_graph(g, manifest=("pkg.heavy",),
                                      roots=("pkg.core",))
        assert fs == []

    def test_allow_marked_module_level_import_is_conditional(self):
        g = self._graph({
            "pkg": "",
            "pkg.core": "from . import heavy"
                        "  # lint: allow(lazy-import)\n",
            "pkg.heavy": "",
        })
        fs = import_graph.audit_graph(g, manifest=("pkg.heavy",),
                                      roots=("pkg.core",))
        assert fs == []

    def test_transitive_leak_reports_chain(self):
        g = self._graph({
            "pkg": "",
            "pkg.a": "from . import b\n",
            "pkg.b": "from . import heavy\n",
            "pkg.heavy": "",
        })
        fs = import_graph.audit_graph(g, manifest=("pkg.heavy",),
                                      roots=("pkg.a",))
        assert len(fs) == 1
        assert "pkg.a -> pkg.b -> pkg.heavy" in fs[0].message

    def test_subtree_manifest_entry(self):
        g = self._graph({
            "pkg": "",
            "pkg.core": "from .fed import avg\n",
            "pkg.fed": "",
            "pkg.fed.avg": "",
        })
        fs = import_graph.audit_graph(g, manifest=("pkg.fed",),
                                      roots=("pkg.core",))
        leaked = {f.where for f in fs}
        assert "pkg.fed.avg" in leaked and "pkg.fed" in leaked

    def test_stale_manifest_entry(self):
        g = self._graph({"pkg": "", "pkg.core": ""})
        fs = import_graph.audit_graph(g, manifest=("pkg.ghost",),
                                      roots=("pkg.core",))
        assert [f.pass_name for f in fs] == ["lazy-manifest-stale"]

    def test_repo_manifest_modules_exist(self):
        g = import_graph.build_graph()
        for entry in import_graph.LAZY_MODULES:
            assert g.expand(entry), entry

    def test_repo_plain_closure_is_clean(self):
        # the one generated check unifying the ten subprocess no-import
        # pins: every manifest-lazy module stays out of the closure
        assert import_graph.audit_package() == []

    def test_repo_closure_is_nontrivial(self):
        # guard against the checker trivially passing on a broken graph
        g = import_graph.build_graph()
        closure = g.eager_closure(import_graph.PLAIN_CLOSURE_ROOTS)
        assert len(closure) > 50
        assert "paddle_tpu.distributed.spmd" in closure
        assert "paddle_tpu.monitor" in closure


_OBS_DOC = """
# doc

## Metric family reference

| family | kind |
|---|---|
| `good_total` | counter |

## Span name reference

| span | subsystem |
|---|---|
| `phase` | app |
| `collective/<op>` | collective |
"""


class TestObsAudit:
    def test_clean_inventory(self):
        srcs = {"m.py": '_C = _monitor.counter("good_total", "h")\n'
                        'with _trace.span("phase"):\n    pass\n'}
        assert obs_audit.audit_inventory(srcs, _OBS_DOC) == []

    def test_undocumented_metric_planted(self):
        srcs = {"m.py": '_C = _monitor.counter("good_total", "h")\n'
                        'with _trace.span("phase"):\n    pass\n'
                        '_D = _monitor.gauge("rogue_gauge", "h")\n'}
        fs = obs_audit.audit_inventory(srcs, _OBS_DOC)
        assert [f.pass_name for f in fs] == ["metric-undocumented"]
        assert "rogue_gauge" in fs[0].message

    def test_doc_stale_metric(self):
        fs = obs_audit.audit_inventory({"m.py": "x = 1\n"}, _OBS_DOC)
        assert "metric-doc-stale" in {f.pass_name for f in fs}

    def test_undocumented_span_planted(self):
        srcs = {"m.py": '_C = _monitor.counter("good_total", "h")\n'
                        'sp = _trace.start_span("rogue_span")\n'}
        fs = obs_audit.audit_inventory(srcs, _OBS_DOC)
        assert "span-undocumented" in {f.pass_name for f in fs}

    def test_dynamic_span_row_accepted(self):
        # collective/<op> has no literal call site; DYNAMIC_SPANS covers it
        srcs = {"m.py": '_C = _monitor.counter("good_total", "h")\n'
                        'with _trace.span("phase"):\n    pass\n'}
        fs = obs_audit.audit_inventory(srcs, _OBS_DOC)
        assert "span-doc-stale" not in {f.pass_name for f in fs}

    def test_stale_span_row(self):
        doc = _OBS_DOC + "| `gone_span` | app |\n"
        srcs = {"m.py": '_C = _monitor.counter("good_total", "h")\n'
                        'with _trace.span("phase"):\n    pass\n'}
        fs = obs_audit.audit_inventory(srcs, doc)
        assert "span-doc-stale" in {f.pass_name for f in fs}

    def test_required_family_gone_planted(self):
        dump = '_REQUIRED = {"train": ("good_total", "vanished_total")}\n'
        srcs = {"m.py": '_C = _monitor.counter("good_total", "h")\n'
                        'with _trace.span("phase"):\n    pass\n'}
        fs = obs_audit.audit_inventory(srcs, _OBS_DOC, dump_source=dump)
        assert [f.pass_name for f in fs] == ["required-family-gone"]
        assert "vanished_total" in fs[0].message

    def test_required_series_families_checked(self):
        dump = ('_REQUIRED_SERIES = {"q": (("lost_total", "op", "x"),)}\n')
        srcs = {"m.py": '_C = _monitor.counter("good_total", "h")\n'
                        'with _trace.span("phase"):\n    pass\n'}
        fs = obs_audit.audit_inventory(srcs, _OBS_DOC, dump_source=dump)
        assert "required-family-gone" in {f.pass_name for f in fs}

    def test_allow_marker_suppresses_undocumented(self):
        srcs = {"m.py": '_C = _monitor.counter("good_total", "h")\n'
                        'with _trace.span("phase"):\n    pass\n'
                        '_P = _monitor.gauge("private_g", "h")'
                        '  # lint: allow(undocumented-metric)\n'}
        fs = obs_audit.audit_inventory(srcs, _OBS_DOC)
        assert fs == []

    def test_harvest_is_receiver_scoped(self):
        # only the telemetry module aliases register: a bare emit()
        # helper (the analysis passes' own finding emitters) or a
        # foreign .counter() must not be harvested
        srcs = {"m.py": 'emit("deadcode", scan, 1, "msg")\n'
                        'scan.counter("not_a_metric", 2)\n'
                        'sp.span("not_a_span")\n'}
        assert obs_audit.code_span_names(srcs) == {}
        assert obs_audit.code_metric_families(srcs) == {}

    def test_repo_observability_is_clean(self):
        assert obs_audit.audit_package() == []


_THREADED_BAD = """
import threading
_LOCK = threading.Lock()
_STATE = {}
_COUNT = [0]

def worker():
    _STATE["k"] = 1
    _COUNT[0] += 1

threading.Thread(target=worker, daemon=True).start()
"""

_THREADED_GOOD = """
import threading
_LOCK = threading.Lock()
_STATE = {}

def worker():
    local = {}
    local["k"] = 1
    with _LOCK:
        _STATE["k"] = 1

threading.Thread(target=worker, daemon=True).start()
"""


class TestThreadDisciplineLint:
    def test_unlocked_write_planted(self):
        fs = lint_thread_discipline(_THREADED_BAD, "m.py", "_LOCK")
        assert {f.pass_name for f in fs} == {"unlocked-thread-shared-write"}
        assert len(fs) == 2   # _STATE and _COUNT

    def test_locked_and_local_writes_are_clean(self):
        assert lint_thread_discipline(_THREADED_GOOD, "m.py",
                                      "_LOCK") == []

    def test_thread_subclass_run_is_a_root(self):
        src = ("import threading\n"
               "_LOCK = threading.Lock()\n"
               "_S = {}\n"
               "class W(threading.Thread):\n"
               "    def run(self):\n"
               "        _S['x'] = 1\n")
        fs = lint_thread_discipline(src, "m.py", "_LOCK")
        assert len(fs) == 1 and fs[0].pass_name == \
            "unlocked-thread-shared-write"

    def test_reachable_callee_is_policed(self):
        src = ("import threading\n"
               "_LOCK = threading.Lock()\n"
               "_S = {}\n"
               "def helper():\n"
               "    _S['x'] = 1\n"
               "def body():\n"
               "    helper()\n"
               "threading.Thread(target=body).start()\n")
        fs = lint_thread_discipline(src, "m.py", "_LOCK")
        assert len(fs) == 1

    def test_unreachable_function_not_policed(self):
        src = ("import threading\n"
               "_LOCK = threading.Lock()\n"
               "_S = {}\n"
               "def not_a_thread():\n"
               "    _S['x'] = 1\n"
               "def body():\n"
               "    pass\n"
               "threading.Thread(target=body).start()\n")
        assert lint_thread_discipline(src, "m.py", "_LOCK") == []

    def test_allow_marker_suppresses(self):
        src = ("import threading\n"
               "_LOCK = threading.Lock()\n"
               "_ON = [False]\n"
               "def body():\n"
               "    _ON[0] = True  # lint: allow(thread-shared-write)\n"
               "threading.Thread(target=body).start()\n")
        assert lint_thread_discipline(src, "m.py", "_LOCK") == []

    def test_nested_function_param_shadows_global(self):
        # a nested def's parameter named like a module global is LOCAL —
        # writing through it must not be flagged
        src = ("import threading\n"
               "_LOCK = threading.Lock()\n"
               "_STATE = {}\n"
               "def worker():\n"
               "    def fmt(_STATE):\n"
               "        _STATE['k'] = 1\n"
               "    fmt({})\n"
               "threading.Thread(target=worker).start()\n")
        assert lint_thread_discipline(src, "m.py", "_LOCK") == []

    def test_missing_designated_lock_is_loud(self):
        src = "import threading\n_S = {}\n"
        fs = lint_thread_discipline(src, "m.py", "_MISSING_LOCK")
        assert len(fs) == 1
        assert "appears nowhere" in fs[0].message

    def test_repo_thread_modules_are_clean(self):
        for rel, lock in THREAD_SHARED_MODULES.items():
            src = open(os.path.join(REPO, "paddle_tpu", rel)).read()
            assert lint_thread_discipline(src, rel, lock) == [], rel


class TestAllowlistConsolidation:
    def test_every_rule_has_spellings(self):
        from paddle_tpu.analysis import contract_rules

        for rule in contract_rules():
            sp = allowlist.spellings(rule)
            assert sp[0] == rule

    def test_aliases_resolve(self):
        lines = ["x = 1  # lint: allow(client_output)"]
        assert allowlist.allowed(lines, 1, "nonreduced-client-output")
        assert not allowlist.allowed(lines, 1, "orphan-flag-unread")

    def test_source_lint_shares_the_table(self):
        # the old private copy is gone: source_lint re-exports the shared
        # alias table object
        from paddle_tpu.analysis import source_lint

        assert source_lint._RULE_ALIASES is allowlist.RULE_ALIASES


# ---------------------------------------------------------------------------
# ISSUE 13: sharding-flow passes (planted pos/neg per rule)
# ---------------------------------------------------------------------------


def _smap():
    return jax.shard_map


def _mesh4(names=("dp",)):
    import math

    n = 4 if len(names) == 1 else 4
    devs = np.array(jax.devices()[:n])
    if len(names) > 1:
        devs = devs.reshape((2, 2))
    return jax.sharding.Mesh(devs, names)


class TestCollectiveAxisMismatch:
    def _traced_psum(self, axis="dp"):
        from jax.sharding import PartitionSpec as P

        mesh = _mesh4()

        def g(x):
            return jax.lax.psum(x, axis)

        return jax.make_jaxpr(_smap()(g, mesh=mesh, in_specs=P("dp"),
                                      out_specs=P(),
                                      check_vma=False))(jnp.ones((8,)))

    def test_positive_axis_absent_from_deployment_mesh(self):
        other = jax.sharding.Mesh(np.array(jax.devices()[:4]), ("x",))
        rep = run_passes(self._traced_psum(),
                         passes=["collective-axis-mismatch"], mesh=other)
        msgs = [f.message for f in rep.errors]
        assert any("'dp' absent from the deployment mesh" in m
                   for m in msgs), msgs
        assert any("shard_map binds axis 'dp'" in m for m in msgs)

    def test_positive_mesh_axis_size_mismatch(self):
        bigger = jax.sharding.Mesh(
            np.array(jax.devices()[:8]), ("dp",))
        rep = run_passes(self._traced_psum(),
                         passes=["collective-axis-mismatch"], mesh=bigger)
        assert any("size" in f.message for f in rep.errors), \
            [f.message for f in rep.errors]

    def test_negative_matching_mesh(self):
        rep = run_passes(self._traced_psum(),
                         passes=["collective-axis-mismatch"],
                         mesh=_mesh4())
        assert rep.findings == []

    def test_negative_no_deployment_mesh(self):
        # self-consistent program, no mesh to check against
        rep = run_passes(self._traced_psum(),
                         passes=["collective-axis-mismatch"])
        assert rep.findings == []


class TestPpermuteMalformed:
    def _traced(self, perm):
        from jax.sharding import PartitionSpec as P

        mesh = _mesh4()

        def g(x):
            return jax.lax.ppermute(x, "dp", perm)

        return jax.make_jaxpr(_smap()(g, mesh=mesh, in_specs=P("dp"),
                                      out_specs=P("dp"),
                                      check_vma=False))(jnp.ones((8,)))

    def test_positive_non_bijective(self):
        rep = run_passes(self._traced([(0, 1), (1, 1)]),
                         passes=["ppermute-malformed"], mesh=_mesh4())
        assert any("not a bijection" in f.message for f in rep.errors), \
            [f.message for f in rep.errors]

    def test_positive_self_referential(self):
        rep = run_passes(self._traced([(0, 0), (1, 2)]),
                         passes=["ppermute-malformed"], mesh=_mesh4())
        assert any("self-referential" in f.message for f in rep.errors)

    def test_positive_out_of_range(self):
        from paddle_tpu.analysis.sharding_flow import check_permutation

        problems = check_permutation(((0, 7),), axis_size=4)
        assert any("outside the axis size" in p for p in problems)

    def test_negative_ring(self):
        ring = [(i, (i + 1) % 4) for i in range(4)]
        rep = run_passes(self._traced(ring),
                         passes=["ppermute-malformed"], mesh=_mesh4())
        assert rep.findings == []

    def test_check_permutation_unit(self):
        from paddle_tpu.analysis.sharding_flow import check_permutation

        assert check_permutation([(0, 1), (1, 0)]) == []
        assert check_permutation([(0, 1), (0, 2)])      # dup source
        assert check_permutation([(1, 1)])              # self edge


class TestBranchCollectiveMismatch:
    def _traced(self, both_arms):
        from jax.sharding import PartitionSpec as P

        mesh = _mesh4()

        def taken(v):
            return jax.lax.psum(v, "dp")

        def other(v):
            return taken(v) if both_arms else v * 2.0

        def g(x):
            return jax.lax.cond(x[0] > 0, taken, other, x)

        return jax.make_jaxpr(_smap()(g, mesh=mesh, in_specs=P("dp"),
                                      out_specs=P("dp"),
                                      check_vma=False))(jnp.ones((8,)))

    def test_positive_one_arm_collective(self):
        rep = run_passes(self._traced(both_arms=False),
                         passes=["branch-collective-mismatch"],
                         mesh=_mesh4())
        assert len(rep.errors) == 1
        assert "different collective sequences" in rep.errors[0].message
        assert "arm[0]" in rep.errors[0].message

    def test_negative_matched_arms(self):
        rep = run_passes(self._traced(both_arms=True),
                         passes=["branch-collective-mismatch"],
                         mesh=_mesh4())
        assert rep.findings == []

    def test_while_predicate_collective_warns(self):
        from jax.sharding import PartitionSpec as P

        mesh = _mesh4()

        def g(x):
            def cond(c):
                return jax.lax.psum(c.sum(), "dp") < 10.0

            def body(c):
                return c + 1.0

            return jax.lax.while_loop(cond, body, x)

        cj = jax.make_jaxpr(_smap()(g, mesh=mesh, in_specs=P("dp"),
                                    out_specs=P("dp"),
                                    check_vma=False))(jnp.ones((8,)))
        rep = run_passes(cj, passes=["branch-collective-mismatch"],
                         mesh=_mesh4())
        assert len(rep.warnings) == 1
        assert "while-loop predicate" in rep.warnings[0].message

    def test_fori_loop_negative(self):
        # counter-predicate loops (the pipeline schedule) stay silent
        from jax.sharding import PartitionSpec as P

        mesh = _mesh4()

        def g(x):
            return jax.lax.fori_loop(
                0, 4, lambda i, c: jax.lax.ppermute(
                    c, "dp", [(j, (j + 1) % 4) for j in range(4)]), x)

        cj = jax.make_jaxpr(_smap()(g, mesh=mesh, in_specs=P("dp"),
                                    out_specs=P("dp"),
                                    check_vma=False))(jnp.ones((8,)))
        rep = run_passes(cj, passes=["branch-collective-mismatch"],
                         mesh=_mesh4())
        assert rep.findings == []


class TestReshardingChurn:
    def test_positive_spec_flip(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = _mesh4()

        def f(x):
            y = jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P("dp")))
            return jax.lax.with_sharding_constraint(
                y * 1.0, NamedSharding(mesh, P(None)))

        cj = jax.make_jaxpr(jax.jit(f))(jnp.ones((64, 64)))
        rep = run_passes(cj, passes=["resharding-churn"], mesh=mesh,
                         large_threshold=1024)
        assert len(rep.warnings) == 1
        msg = rep.warnings[0].message
        assert "re-constrained" in msg and "all-gather" in msg

    def test_negative_same_spec_twice(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = _mesh4()

        def f(x):
            y = jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P("dp")))
            return jax.lax.with_sharding_constraint(
                y * 1.0, NamedSharding(mesh, P("dp")))

        cj = jax.make_jaxpr(jax.jit(f))(jnp.ones((64, 64)))
        rep = run_passes(cj, passes=["resharding-churn"], mesh=mesh,
                         large_threshold=1024)
        assert rep.findings == []

    def test_negative_small_tensor(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = _mesh4()

        def f(x):
            y = jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P("dp")))
            return jax.lax.with_sharding_constraint(
                y * 1.0, NamedSharding(mesh, P(None)))

        cj = jax.make_jaxpr(jax.jit(f))(jnp.ones((8, 8)))
        rep = run_passes(cj, passes=["resharding-churn"], mesh=mesh,
                         large_threshold=1024)
        assert rep.findings == []


# ---------------------------------------------------------------------------
# ISSUE 13: handoff schemas (planted drift + validation matrix)
# ---------------------------------------------------------------------------


class TestHandoffSchema:
    def _schema(self):
        return {
            "edge": "test_edge",
            "producer": "paddle_tpu/serving/disagg.py::PrefillWorker.prefill",
            "consumer": ("paddle_tpu/inference/serving.py::"
                         "ServingEngine.admit_prefilled"),
            "payload": {
                "kc": {"shape": ("L", 1, "T"), "dtype": "$cache",
                       "quantizable": True},
                "logits": {"shape": ("V",), "dtype": "float32"},
            },
        }

    def test_validate_good_payload_binds_dims(self):
        from paddle_tpu.analysis import handoff_schema as hs

        binds = hs.validate(self._schema(),
                            {"kc": np.zeros((2, 1, 8), np.float32),
                             "logits": np.zeros((16,), np.float32)})
        assert binds == {"L": 2, "T": 8, "V": 16}

    def test_validate_cross_leaf_consistency(self):
        from paddle_tpu.analysis import handoff_schema as hs

        sch = self._schema()
        sch["payload"]["vc"] = {"shape": ("L", 1, "T"),
                                "dtype": "float32"}
        with pytest.raises(hs.HandoffMismatch, match="'L'"):
            hs.validate(sch, {"kc": np.zeros((2, 1, 8), np.float32),
                              "vc": np.zeros((3, 1, 8), np.float32),
                              "logits": np.zeros((16,), np.float32)})

    def test_validate_quantized_pair(self):
        from paddle_tpu.analysis import handoff_schema as hs

        vals = np.zeros((2, 1, 8), np.int8)
        scales = np.zeros((2, 1, 1), np.float32)
        hs.validate(self._schema(),
                    {"kc": (vals, scales),
                     "logits": np.zeros((16,), np.float32)},
                    dtypes={"cache": "int8"})
        # scales must be f32
        with pytest.raises(hs.HandoffMismatch, match="scales"):
            hs.validate(self._schema(),
                        {"kc": (vals, scales.astype(np.float16)),
                         "logits": np.zeros((16,), np.float32)})
        # the VALUES dtype honors the declaration too: a producer built
        # with a different cache codec must fail, not corrupt the cache
        with pytest.raises(hs.HandoffMismatch, match=r"kc\.values"):
            hs.validate(self._schema(),
                        {"kc": (vals.astype(np.uint8), scales),
                         "logits": np.zeros((16,), np.float32)},
                        dtypes={"cache": "int8"})

    def test_validate_missing_leaf_and_wrong_rank(self):
        from paddle_tpu.analysis import handoff_schema as hs

        with pytest.raises(hs.HandoffMismatch, match="missing leaf"):
            hs.validate(self._schema(),
                        {"kc": np.zeros((2, 1, 8), np.float32)})
        with pytest.raises(hs.HandoffMismatch, match="rank"):
            hs.validate(self._schema(),
                        {"kc": np.zeros((2, 1), np.float32),
                         "logits": np.zeros((16,), np.float32)})

    def test_wildcard_trailing_dims(self):
        from paddle_tpu.analysis import handoff_schema as hs

        sch = {"edge": "e", "producer": "p", "consumer": "c",
               "payload": {"act": {"shape": ("mb", "..."),
                                   "dtype": "float32"}}}
        hs.validate(sch, {"act": np.zeros((4, 7, 9), np.float32)},
                    dims={"mb": 4})
        with pytest.raises(hs.HandoffMismatch, match="'mb'"):
            hs.validate(sch, {"act": np.zeros((5, 7, 9), np.float32)},
                        dims={"mb": 4})

    def test_planted_drift_detected(self):
        from paddle_tpu.analysis import handoff_schema as hs

        decl = self._schema()
        base = {"edges": {"test_edge": hs.fingerprint(decl)}}
        assert hs.check_baseline({"test_edge": decl}, base) == []

        drifted = dict(decl, payload={
            "kc": {"shape": ("L", 1, "T"), "dtype": "bfloat16",
                   "quantizable": True},
            "logits": {"shape": ("V",), "dtype": "float32"}})
        fs = hs.check_baseline({"test_edge": drifted}, base)
        assert len(fs) == 1 and fs[0].pass_name == "handoff-schema-drift"
        assert "kc" in fs[0].message and "bfloat16" in fs[0].message

    def test_unpinned_and_stale_edges(self):
        from paddle_tpu.analysis import handoff_schema as hs

        decl = self._schema()
        fs = hs.check_baseline({"test_edge": decl}, {"edges": {}})
        assert fs[0].pass_name == "handoff-schema-unpinned"
        fs = hs.check_baseline({}, {"edges": {"gone": {}}})
        assert fs[0].pass_name == "handoff-baseline-stale"

    def test_extraction_rejects_non_literal(self, tmp_path):
        from paddle_tpu.analysis import handoff_schema as hs

        mod = tmp_path / "decl.py"
        mod.write_text("X = 1\nHANDOFF_SCHEMA = make_schema()\n")
        with pytest.raises(ValueError, match="pure literal"):
            hs.extract_declaration("decl.py", "HANDOFF_SCHEMA",
                                   pkg_root=str(tmp_path))
        with pytest.raises(ValueError, match="no module-level literal"):
            hs.extract_declaration("decl.py", "OTHER_SCHEMA",
                                   pkg_root=str(tmp_path))

    def test_site_check_catches_unwired_consumer(self, tmp_path):
        from paddle_tpu.analysis import handoff_schema as hs

        mod = tmp_path / "m.py"
        mod.write_text("def produce():\n    pass\n")
        fs = hs._site_check("e", "consumer", "m.py::produce",
                            "HANDOFF_SCHEMA", True, str(tmp_path))
        assert fs and "never references" in fs[0].message
        fs = hs._site_check("e", "consumer", "m.py::missing_fn",
                            "HANDOFF_SCHEMA", False, str(tmp_path))
        assert fs and "not found" in fs[0].message


# ---------------------------------------------------------------------------
# ISSUE 13: pallas kernel budget audit (planted violations)
# ---------------------------------------------------------------------------


class TestPallasAudit:
    def test_planted_vmem_over_budget_names_buffers(self):
        from paddle_tpu.analysis import pallas_audit as pa

        entry = {"kernel": "planted.big", "matmul": False,
                 "grid": {"m": (4096, 2048)},
                 "buffers": [
                     {"name": "x", "block": (2048, 2048),
                      "dtype": "float32"},
                     {"name": "w", "block": (2048, 2048),
                      "dtype": "float32"}]}
        fs = [f for f in pa.audit_entry(entry)
              if f.pass_name == "kernel-vmem-over-budget"]
        assert len(fs) == 1
        msg = fs[0].message
        # per-buffer breakdown, double-buffering accounted
        assert "w=32768KiB" in msg and "x=32768KiB" in msg
        assert "double-buffered" in msg

    def test_planted_int8_accumulator(self):
        from paddle_tpu.analysis import pallas_audit as pa

        entry = {"kernel": "planted.int8", "matmul": True,
                 "in_dtype": "int8", "acc_dtype": "int8",
                 "grid": {}, "buffers": []}
        fs = pa.audit_entry(entry)
        assert any(f.pass_name == "kernel-low-precision-accumulator"
                   and "saturate" in f.message for f in fs)
        # f32 accumulator passes
        entry["acc_dtype"] = "float32"
        assert pa.audit_entry(entry) == []

    def test_planted_ragged_grid(self):
        from paddle_tpu.analysis import pallas_audit as pa

        entry = {"kernel": "planted.ragged", "matmul": False,
                 "grid": {"m": (100, 32)}, "buffers": []}
        fs = pa.audit_entry(entry)
        assert any(f.pass_name == "kernel-grid-indivisible"
                   and "ragged 4-wide tail" in f.message for f in fs)

    def test_planted_sublane_misalignment_warns(self):
        from paddle_tpu.analysis import pallas_audit as pa

        entry = {"kernel": "planted.sub", "matmul": False, "grid": {},
                 "buffers": [{"name": "x", "block": (12, 128),
                              "dtype": "bfloat16"}]}
        fs = pa.audit_entry(entry)
        assert any(f.severity == "warning" and "min tile" in f.message
                   for f in fs)

    def test_double_buffer_accounting(self):
        from paddle_tpu.analysis import pallas_audit as pa

        streamed = {"name": "x", "block": (128, 128), "dtype": "float32"}
        resident = dict(streamed, stream=False)
        assert pa.buffer_bytes(streamed) == 2 * pa.buffer_bytes(resident)

    def test_manifest_derives_from_live_block_tables(self):
        # the audit shapes go through the SAME pick_block the runtime
        # uses — a block-table change flows into the audit
        from paddle_tpu.analysis import pallas_audit as pa
        from paddle_tpu.ops import tpp

        entries = [e for e in pa.collect_manifest()
                   if e["kernel"].startswith("tpp.matmul")]
        assert entries
        for e in entries:
            m, bm = e["grid"]["m"]
            assert bm == tpp.pick_block(m)


# ---------------------------------------------------------------------------
# ISSUE 16: flow summary + wire bytes (the cost model's two data feeds)
# ---------------------------------------------------------------------------


class TestFlowSummary:
    def _psum_program(self, n=4):
        from jax.sharding import PartitionSpec as P

        mesh = jax.sharding.Mesh(np.array(jax.devices()[:n]), ("dp",))

        def g(x):
            return jax.lax.psum(x, "dp")

        return jax.make_jaxpr(_smap()(g, mesh=mesh, in_specs=P("dp"),
                                      out_specs=P(),
                                      check_vma=False))(jnp.ones((8,)))

    def test_reduce_bytes_ring_factored(self):
        from paddle_tpu.analysis.sharding_flow import flow_summary

        s = flow_summary(self._psum_program(n=4))
        # one psum over a (2,) f32 shard (8 elems / 4 devices): payload
        # 8 bytes x the 2(n-1)/n = 1.5 reduce ring factor
        assert s["collective_counts"] == {"reduce": 1, "exchange": 0,
                                          "permute": 0}
        assert s["collective_bytes"]["reduce"] == pytest.approx(12.0)
        assert s["collective_bytes_total"] == pytest.approx(12.0)

    def test_plain_program_has_no_collectives(self):
        from paddle_tpu.analysis.sharding_flow import flow_summary

        s = flow_summary(jax.make_jaxpr(lambda x: x * 2.0)(
            jnp.ones((8,))))
        assert s["collective_bytes_total"] == 0.0
        assert s["resharding_events"] == 0

    def test_sharding_summaries_cover_the_battery(self):
        from paddle_tpu.analysis.sharding_flow import sharding_summaries

        out = sharding_summaries(targets=["gpt_train"])
        assert set(out) == {"gpt_train"}
        s = out["gpt_train"]
        assert set(s) >= {"collective_bytes", "collective_counts",
                          "collective_bytes_total",
                          "resharding_churn_bytes", "resharding_events"}


class TestWireBytes:
    DIMS = {"mb": 2, "t": 16, "d": 64}

    def test_dense_activation_edge(self):
        from paddle_tpu.analysis.handoff_schema import wire_bytes

        assert wire_bytes("mpmd_activation", self.DIMS) == 2 * 16 * 64 * 4

    def test_compressed_matches_measured_ratio(self):
        # the 4 / (1 + 4/D) int8-row-codec wire ratio StageEdge measures
        from paddle_tpu.analysis.handoff_schema import wire_bytes

        dense = wire_bytes("mpmd_activation", self.DIMS)
        comp = wire_bytes("mpmd_activation", self.DIMS, compress=8)
        assert comp < dense
        assert dense / comp == pytest.approx(4.0 / (1.0 + 4.0 / 64))

    def test_grad_edge_never_compresses(self):
        # grad edge declares no quantizable leaves: compress is a no-op
        from paddle_tpu.analysis.handoff_schema import wire_bytes

        assert wire_bytes("mpmd_grad", self.DIMS, compress=8) == \
            wire_bytes("mpmd_grad", self.DIMS)

    def test_unbound_dim_raises(self):
        from paddle_tpu.analysis.handoff_schema import wire_bytes

        with pytest.raises(ValueError, match="unbound dim"):
            wire_bytes("mpmd_activation", {"mb": 2, "t": 16})

    def test_unknown_edge_and_bad_compress_raise(self):
        from paddle_tpu.analysis.handoff_schema import wire_bytes

        with pytest.raises(ValueError):
            wire_bytes("no_such_edge", {})
        with pytest.raises(ValueError, match="compress"):
            wire_bytes("mpmd_activation", self.DIMS, compress=4)


# ---------------------------------------------------------------------------
# ISSUE 16: plan verifier (planted bad plans -> the NAMED analyzer pass)
# ---------------------------------------------------------------------------


def _fake_profile(**kw):
    from paddle_tpu.analysis.cost_model import ModelProfile

    base = dict(name="fake", n_layers=2, hidden=64, seq=16, vocab=256,
                step_flops=1e9, step_bytes=1e8, param_bytes=1 << 19,
                opt_bytes=1 << 20, qar_eligible_bytes=1 << 18,
                supports_pipeline=True, supports_mp=True)
    base.update(kw)
    return ModelProfile(**base)


def _passes_of(errs):
    return sorted({e.pass_name for e in errs})


class TestPlanVerifier:
    def _verify(self, plan, profile=None, **kw):
        from paddle_tpu.analysis.plan_search import verify_plan

        errs, _ = verify_plan(plan, profile or _fake_profile(),
                              devices=8, trace_classes=False, **kw)
        return errs

    def test_mp_axis_larger_than_mesh_rejected_by_sharding_pass(self):
        # dp2 x mp8 wants 16 devices on an 8-device pool: the deployment
        # mesh can only give mp 4 — the EXISTING collective-axis-mismatch
        # pass rejects it, not a crash and not a planner-private check
        from paddle_tpu.analysis.cost_model import Plan

        errs = self._verify(Plan(dp=2, mp=8))
        assert _passes_of(errs) == ["collective-axis-mismatch"]
        assert "size 8" in errs[0].message and "4" in errs[0].message

    def test_vmem_busting_stage_rejected_by_pallas_pass(self):
        from paddle_tpu.analysis.cost_model import Plan

        errs = self._verify(Plan(pp=2, n_micro=2),
                            profile=_fake_profile(hidden=1 << 22))
        assert "kernel-vmem-over-budget" in _passes_of(errs)
        assert any("16 MiB" in e.message for e in errs)

    def test_grad_edge_compress_rejected_by_handoff_validator(self):
        # pipeline grad edges are declared dense; a plan that tries to
        # quantize one is caught by the schema validator, wrapped as
        # plan-handoff-mismatch with the validator's own message
        from paddle_tpu.analysis.cost_model import Plan

        errs = self._verify(Plan(pp=2, n_micro=2,
                                 compress_grad_edge=True))
        assert _passes_of(errs) == ["plan-handoff-mismatch"]
        assert "mpmd_grad" in errs[0].message

    def test_hbm_over_budget_rejected(self):
        from paddle_tpu.analysis.cost_model import CostModel, Plan

        errs = self._verify(Plan(dp=2), cm=CostModel(hbm_bytes=1 << 20))
        assert _passes_of(errs) == ["plan-hbm-over-budget"]

    def test_config_nonsense_rejected(self):
        from paddle_tpu.analysis.cost_model import Plan

        # dp=3 does not divide the global batch of 16
        errs = self._verify(Plan(dp=3))
        assert _passes_of(errs) == ["plan-invalid-config"]
        # quantized allreduce needs dp > 1
        errs = self._verify(Plan(dp=1, quantized_allreduce=True))
        assert _passes_of(errs) == ["plan-invalid-config"]

    def test_valid_plan_scores_finite_and_emits_runnable_config(self):
        from paddle_tpu.analysis.cost_model import CostModel, Plan
        from paddle_tpu.analysis.plan_search import emit

        prof = _fake_profile()
        plan = Plan(dp=2)
        assert self._verify(plan) == []
        score = CostModel().score(plan, prof)
        assert np.isfinite(score["total_s"]) and score["total_s"] > 0
        cfg = emit(plan, prof)
        assert cfg["kind"] == "spmd"
        assert cfg["mesh"] == {"shape": [2], "axes": ["dp"]}
        assert cfg["flags"] == {"quantized_allreduce": False}

    def test_pipeline_plan_emits_stage_graph_config(self):
        from paddle_tpu.analysis.cost_model import Plan
        from paddle_tpu.analysis.plan_search import emit

        cfg = emit(Plan(pp=2, n_micro=4, edge_compress=8),
                   _fake_profile())
        assert cfg["kind"] == "stage_graph"
        assert cfg["flags"] == {"mpmd": True}
        assert cfg["pipeline"]["n_micro"] == 4
        assert cfg["pipeline"]["stage_layers"] == [[0], [1]]
        assert cfg["pipeline"]["compress"] == 8


class TestCostModelMonotonicity:
    def test_more_dp_means_less_hbm_per_device(self):
        # fixed global batch: activations shrink with dp (strong scaling)
        from paddle_tpu.analysis.cost_model import CostModel, Plan

        cm, prof = CostModel(), _fake_profile()
        mems = [cm.score(Plan(dp=d), prof)["mem_bytes_per_device"]
                for d in (2, 4, 8)]
        assert mems[0] > mems[1] > mems[2]

    def test_edge_compress_means_fewer_wire_bytes(self):
        from paddle_tpu.analysis.cost_model import CostModel, Plan

        cm, prof = CostModel(), _fake_profile()
        dense, _ = cm.comm_terms(Plan(pp=2, n_micro=4), prof)
        comp, _ = cm.comm_terms(Plan(pp=2, n_micro=4, edge_compress=8),
                                prof)
        assert 0 < comp["edge_wire_bytes"] < dense["edge_wire_bytes"]

    def test_quantized_allreduce_means_fewer_sync_bytes(self):
        from paddle_tpu.analysis.cost_model import CostModel, Plan

        cm, prof = CostModel(), _fake_profile()
        dense, _ = cm.comm_terms(Plan(dp=8), prof)
        quant, _ = cm.comm_terms(Plan(dp=8, quantized_allreduce=True),
                                 prof)
        assert 0 < quant["dp_sync_bytes"] < dense["dp_sync_bytes"]
