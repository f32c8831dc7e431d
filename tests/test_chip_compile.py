"""Compile the Pallas kernels of paddle_tpu/ops/ for a TPU v5e that is described,
not attached (the on-chip-measurement guide's rehearsal 3, kept as tests).

Interpret mode cannot show what the chip's compiler refuses: a contraction
Mosaic cannot parse, a primitive with no TPU lowering, a kernel XLA cannot
partition. Each test lowers a kernel at a real width for one chip of a
`v5e:2x2` topology and asserts the compiled program holds the
`tpu_custom_call` — or, for the one op that cannot be compiled, that it raises
by name instead of quietly taking another path. Nothing runs; a compile that
passes is not a chip run.

The topology is described inside a module-scoped fixture, never at import, and
every compile happens in this process and this one file: only one process may
load the TPU's library, and under xdist only the worker handed this file does.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from paddle_tpu.ops import flash_attention as fa
from paddle_tpu.ops import nms_pallas, tpp

BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topology = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around these tests
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topology
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def chip(topo):
    """shape, dtype -> ShapeDtypeStruct on the topology's first chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])

    def struct(shape, dtype=BF16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return struct


def _custom_calls(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text().count(
        "tpu_custom_call")


def _flash_loss(window=None, mesh=None):
    def loss(q, k, v):
        out = fa.flash_attention(q, k, v, causal=True, interpret=False,
                                 window=window, mesh=mesh)
        return out.astype(jnp.float32).sum()

    return loss


class TestFlash:
    """The main path's kernels: GPT-2-small's attention at batch 16 x 1024,
    and the 16k windowed long-context shape."""

    def test_fwd(self, chip):
        q = chip((16, 1024, 12, 64))
        assert _custom_calls(
            lambda q, k, v: fa.flash_attention(q, k, v, causal=True,
                                               interpret=False),
            q, q, q) == 1

    def test_fwd_bwd(self, chip):
        q = chip((16, 1024, 12, 64))
        # fwd, dq, dkv
        assert _custom_calls(jax.grad(_flash_loss(), argnums=(0, 1, 2)),
                             q, q, q) == 3

    def test_windowed_16k_fwd_bwd(self, chip):
        q = chip((1, 16384, 12, 64))
        assert _custom_calls(
            jax.grad(_flash_loss(window=4096), argnums=(0, 1, 2)),
            q, q, q) == 3

    def test_fwd_bwd_under_a_dp_mp_mesh(self, topo):
        """XLA refuses to partition a Mosaic kernel; under a mesh the call
        shard_maps itself (batch over dp, heads over mp) and compiles."""
        mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("dp", "mp"))
        q = jax.ShapeDtypeStruct(
            (16, 1024, 12, 64), BF16,
            sharding=NamedSharding(mesh, P("dp", None, "mp", None)))
        assert _custom_calls(
            jax.grad(_flash_loss(mesh=mesh), argnums=(0, 1, 2)),
            q, q, q) == 3


class TestTpp:
    """The TPP registry at the GPT-2-small MLP's shapes (m = 16 x 1024 rows,
    768 -> 3072 -> 768)."""

    M, H, I = 16384, 768, 3072

    def test_ln_matmul(self, chip):
        assert _custom_calls(
            lambda *a: tpp.ln_matmul(*a, False),
            chip((self.M, self.H)), chip((self.H,)), chip((self.H,)),
            chip((self.H, self.I)), chip((self.I,))) == 1

    def test_mlp_tail_tanh_gelu(self, chip):
        """ln2+fc1 then gelu+fc2 — the two kernels FLAGS_tpp_kernels puts
        in a GPT block — in the form the chip compiles (tanh GELU)."""
        assert _custom_calls(
            lambda *a: tpp.fused_mlp(*a, True, False),
            chip((self.M, self.H)), chip((self.H, self.I)),
            chip((self.I,)), chip((self.I, self.H)), chip((self.H,))) == 2

    @pytest.mark.parametrize("call", [
        lambda x, w, b: tpp.matmul(x, w, bias=b, act="gelu",
                                   interpret=False),
        lambda x, w, b: tpp.fused_mlp(x, w, b, w.T, b[:768], False, False),
        lambda x, w, b: tpp.bias_act(x @ w, b, "gelu", interpret=False),
    ], ids=["matmul", "fused_mlp", "bias_act"])
    def test_exact_gelu_raises_by_name(self, chip, call):
        """Pallas TPU lowers neither erf nor erfc: the exact-GELU epilogue
        raises NotImplementedError naming the op and the remedy — it never
        reaches Mosaic, and never returns None for a dense fallback."""
        with pytest.raises(NotImplementedError,
                           match=r"tpp\.\w+: exact \(erf\) GELU"):
            jax.jit(call).lower(chip((self.M, self.H)),
                                chip((self.H, self.I)), chip((self.I,)))

    def test_softmax_rows(self, chip):
        assert _custom_calls(
            lambda x: tpp.softmax_rows(x, interpret=False),
            chip((512, 768))) == 1

    def test_masked_reduce(self, chip):
        assert _custom_calls(
            lambda x, m: tpp.masked_reduce(x, m, interpret=False),
            chip((512, 768)), chip((512, 768), jnp.int32)) == 1

    @pytest.mark.parametrize("quantized", [False, True],
                             ids=["bf16", "int8"])
    def test_paged_attention(self, chip, quantized):
        """GPT-2-small's heads over 32-deep pages. The query rides a unit
        dimension through both contractions (Mosaic's dot needs a
        non-contracting lhs dimension)."""
        B, H, hd, bs, maxb, NB = 8, 12, 64, 32, 32, 512
        pages = chip((NB, H, bs, hd), jnp.int8 if quantized else BF16)
        scales = (chip((NB, H, bs, 1), jnp.float32),) * 2 if quantized \
            else ()
        assert _custom_calls(
            lambda *a: tpp.paged_attention(*a, interpret=False),
            chip((B, H, hd)), pages, pages, chip((B, maxb), jnp.int32),
            chip((B,), jnp.int32), *scales) == 1


def test_nms_4096(chip):
    assert _custom_calls(
        lambda boxes: nms_pallas.nms_keep_mask_pallas(boxes, 0.5,
                                                      interpret=False),
        chip((4096, 4), jnp.float32)) == 1
