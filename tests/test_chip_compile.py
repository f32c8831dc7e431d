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
import re

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


def _flash_loss(window=None, mesh=None, causal=True):
    def loss(q, k, v):
        out = fa.flash_attention(q, k, v, causal=causal, interpret=False,
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

    @pytest.mark.parametrize("shape,dtype,causal", [
        ((4, 1024, 16, 64), BF16, True),
        ((4, 1024, 16, 64), BF16, False),
        ((2, 2048, 8, 128), BF16, True),
        ((4, 1024, 16, 64), jnp.float32, True),
    ], ids=["the_training_cell", "non_causal", "heads_of_128", "float32"])
    def test_every_arm_of_the_dtype_and_block_rules(self, chip, shape, dtype,
                                                    causal):
        """gpt2-medium.train-1k's own call, and the arms it does not take:
        operands as loaded (bfloat16) or float32, the causal block rule and
        the non-causal one, a head as wide as the MXU. Forward and backward,
        with the blocks the rule picks: 3 custom calls."""
        q = chip(shape, dtype)
        assert _custom_calls(
            jax.grad(_flash_loss(causal=causal), argnums=(0, 1, 2)),
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


class TestDecodeStep:
    """The serving engine's decode steps at gpt2-large's widths (1280 / 20
    heads / vocabulary 50304; 2 layers, 32 rows, T 1024, bf16, caches
    donated), compiled for the described chip. The chip keeps a cache leaf
    T-minor, and a per-step update that slices a layer out of the cache costs
    relayouts of the layer and a loop over the rows (docs/SERVING.md "The
    dense cache on the chip"): the compiled step may hold neither, by either
    form of the store — the in-place kernel a TPU takes (ops/kv_store.py),
    and the select every other shape and platform takes. On a TPU the
    attention of a plain cache reads it through a kernel too, and that
    kernel is the store as well (ops/decode_attention.py): a layer is one
    call, and no operation of XLA's own takes a cache layer as an
    operand."""

    LAYERS, ROWS, T = 2, 32, 1024

    @staticmethod
    def _reads_of_a_layer(text, heads):
        """The fusions, products and convolutions of the compiled step's
        entry computation with an operand the shape of a cache layer (or
        of the whole leaf), in either order of T and hd."""
        import re

        entry = re.search(r"^ENTRY .*?^\}", text, re.S | re.M).group(0)
        layer = re.compile(r"\[(\d+,)?32,%d,(1024,64|64,1024)\]" % heads)
        kind = {}
        for line in entry.splitlines():
            m = re.match(r"\s*(?:ROOT )?(%\S+) = (\(.*?\)|\S+) ", line)
            if m:
                kind[m.group(1)] = m.group(2)
        found = []
        for line in entry.splitlines():
            m = re.match(r"\s*(?:ROOT )?(%\S+) = .*? "
                         r"(fusion|dot|convolution)\((.*?)\)", line)
            if m and any(layer.search(kind.get(o.strip(), ""))
                         for o in m.group(3).split(",")):
                found.append(line.strip()[:160])
        return found

    @pytest.mark.parametrize("store,kind,cache_dtype", [
        ("kernel", "greedy", None), ("kernel", "sample", None),
        ("kernel", "greedy", "int8"), ("select", "greedy", None),
        ("select", "sample", None), ("select", "greedy", "int8")])
    def test_no_pass_over_a_cache_layer_but_the_needed(
            self, chip, monkeypatch, store, kind, cache_dtype):
        import re

        import paddle_tpu as paddle
        from paddle_tpu.inference.serving import ServingEngine
        from paddle_tpu.models import GPTConfig, GPTForCausalLM
        from paddle_tpu.ops import decode_attention, kv_store

        # this process sees the CPU: steer the platform tests of the store
        # and of the read here (an engine a case, since jit keeps what it
        # traced)
        monkeypatch.setattr(kv_store, "on_tpu", lambda: store == "kernel")
        monkeypatch.setattr(decode_attention, "on_tpu",
                            lambda: store == "kernel")
        paddle.seed(0)
        model = GPTForCausalLM(GPTConfig(
            vocab_size=50304, hidden_size=1280, num_layers=self.LAYERS,
            num_heads=20, max_seq_len=self.T, dropout=0.0))
        model.eval()
        eng = ServingEngine(model, max_batch=self.ROWS, dtype="bfloat16",
                            cache_dtype=cache_dtype)

        def on_chip(tree):
            return jax.tree_util.tree_map(
                lambda a: chip(a.shape, a.dtype), tree)

        rows = (self.ROWS,)
        args = [on_chip(eng._params), on_chip(eng._kc), on_chip(eng._vc),
                chip(rows, jnp.int32), chip(rows, jnp.int32)]
        step = eng._step_greedy
        if kind == "sample":
            step = eng._step_sample
            args += [chip(rows, jnp.float32), chip(rows, jnp.int32),
                     chip(rows, jnp.float32), chip(rows, jnp.int32)]
        compiled = step.lower(*args).compile()

        text = compiled.as_text()
        entry = re.search(r"^ENTRY .*?^\}", text, re.S | re.M).group(0)
        # a cache layer, or the whole leaf, in either order of T and hd
        layer = re.compile(r"\[(\d+,)?32,20,(1024,64|64,1024)\]")
        results = (re.match(r"\s*(?:ROOT )?\S+ = (.*?) (?:copy|while)\(", line)
                   for line in entry.splitlines())
        assert [m.group(0)[:160] for m in results
                if m and layer.search(m.group(1))] == []
        # over a plain cache the attention's kernel, which stores K and V
        # too; over an int8 cache the store of K's and of V's values (the
        # scales take the select)
        calls = {"select": 0, "kernel": 1 if cache_dtype is None else 2}
        assert text.count("tpu_custom_call") == calls[store] * self.LAYERS
        if calls[store] == 1:
            assert self._reads_of_a_layer(text, 20) == []
        else:       # the einsums: the check above finds what it looks for
            assert self._reads_of_a_layer(text, 20)

        def nbytes(tree):
            return sum(a.size * a.dtype.itemsize
                       for a in jax.tree_util.tree_leaves(tree))

        weights, cache = nbytes(eng._params), nbytes((eng._kc, eng._vc))
        accessed = compiled.cost_analysis()["bytes accessed"]
        # XLA's count: the select reads 1.20 x, the one kernel 0.37 x (0.7 x
        # while the store was a call of its own), the store the select
        # replaced 3.7 x. It charges an int8 cache's dequantizing read
        # (_load, not the store) several times its bytes: 1.9-2.3 x there,
        # 4.2 x before
        room = 2.5 if cache_dtype else 1.4 if store == "select" else 0.5
        if kind == "sample":
            room += 0.25         # two sorts of the [32, 50304] logits
        assert accessed <= room * (weights + 3 * cache), (
            accessed / (weights + 3 * cache))
        mem = compiled.memory_analysis()
        # (an int8 cache is half the bytes, and its temporaries are the
        # relaid scales, 21 MB here)
        assert mem.temp_size_in_bytes < cache / (10 if cache_dtype is None
                                                 else 5)
        assert mem.alias_size_in_bytes == cache      # donated, in place

    @pytest.mark.parametrize("kind", ["greedy", "sample"])
    def test_the_steps_token_vector_stays_on_the_chip(self, chip, kind):
        """The decode loop keeps one step in flight: a step's tokens are the
        next step's input as they lie on the chip, and an admission lays its
        first token over them in its own program (`pick1_put`). Both compile
        for the described chip from each other's output: a `[32]` int32
        vector, no host transfer in either."""
        import paddle_tpu as paddle
        from paddle_tpu.inference.serving import ServingEngine
        from paddle_tpu.models import GPTConfig, GPTForCausalLM

        paddle.seed(0)
        model = GPTForCausalLM(GPTConfig(
            vocab_size=50304, hidden_size=64, num_layers=1, num_heads=2,
            max_seq_len=128, dropout=0.0))
        model.eval()
        eng = ServingEngine(model, max_batch=self.ROWS, dtype="bfloat16")
        rows, f32, i32 = (self.ROWS,), jnp.float32, jnp.int32
        knobs = [chip((), f32), chip((), i32), chip((), f32), chip((), i32),
                 chip((), i32)]
        if kind == "greedy":
            knobs[0] = np.float32(0.0)      # as _activate hands them over
        put = eng._pick1_put.lower(
            chip(rows, i32), chip((), i32), chip((50304,), f32),
            *knobs).compile()
        tok, toks = put.out_info
        assert (tok.shape, toks.shape, toks.dtype) == ((), rows, i32)

        def on_chip(tree):
            return jax.tree_util.tree_map(
                lambda a: chip(a.shape, a.dtype), tree)

        args = [on_chip(eng._params), on_chip(eng._kc), on_chip(eng._vc),
                chip(rows, i32), chip(rows, i32)]
        step = eng._step_greedy
        if kind == "sample":
            step = eng._step_sample
            args += [chip(rows, f32), chip(rows, i32), chip(rows, f32),
                     chip(rows, i32)]
        compiled = step.lower(*args).compile()
        for text in (put.as_text(), compiled.as_text()):
            assert "outfeed" not in text and "infeed" not in text
            assert " send(" not in text and " recv(" not in text
        out = jax.tree_util.tree_leaves(compiled.out_info)[0]
        assert (out.shape, out.dtype) == (rows, i32)

    def test_tensor_parallel_step_over_four_chips(self, topo, monkeypatch):
        """The engine's greedy step as tensor-parallel serving runs it
        (shard_map over `mp`, 5 of gpt2-large's 20 heads a chip): each
        shard stores and reads its heads through the one kernel, and
        nothing else of the compiled step takes a shard's cache layer."""
        import paddle_tpu as paddle
        from paddle_tpu.models import GPTConfig, GPTForCausalLM, gpt
        from paddle_tpu.ops import decode_attention, kv_store

        monkeypatch.setattr(kv_store, "on_tpu", lambda: True)
        monkeypatch.setattr(decode_attention, "on_tpu", lambda: True)
        mesh = Mesh(np.array(topo.devices), ("mp",))
        paddle.seed(0)
        model = GPTForCausalLM(GPTConfig(
            vocab_size=50304, hidden_size=1280, num_layers=self.LAYERS,
            num_heads=20, max_seq_len=self.T, dropout=0.0))
        model.eval()
        _, _, params = gpt._decode_params(model, "the model")
        axis, size, params, specs = gpt._tp_setup(mesh, model.cfg, params)
        fwd, logits_of, _ = gpt._decode_fns(model.cfg, False, False,
                                            tp_axis=axis, tp_size=size)

        def step_greedy(p, kc, vc, last_toks, pos_vec):   # the engine's
            x, kc, vc = fwd(p, last_toks[:, None], pos_vec, kc, vc)
            logits = logits_of(p, x[:, 0]).astype(jnp.float32)
            return jnp.argmax(logits, -1).astype(jnp.int32), kc, vc

        cs = P(None, None, "mp", None, None)
        step = gpt._tp_wrap(step_greedy, mesh, specs, 0, (P(), cs, cs),
                            in_specs=(specs, cs, cs, P(), P()),
                            donate=(1, 2))

        def on_chips(shape, dtype, spec):
            return jax.ShapeDtypeStruct(
                shape, dtype, sharding=NamedSharding(mesh, spec))

        cache = on_chips((self.LAYERS, self.ROWS, 20, self.T, 64), BF16, cs)
        rows = on_chips((self.ROWS,), jnp.int32, P())
        compiled = step.lower(
            {n: on_chips(v.shape, BF16, specs[n]) for n, v in params.items()},
            cache, cache, rows, rows).compile()
        text = compiled.as_text()
        assert text.count("tpu_custom_call") == self.LAYERS
        assert self._reads_of_a_layer(text, 5) == []
        mem = compiled.memory_analysis()
        shard = 2 * cache.size * 2 // 4              # K and V, a chip
        assert mem.alias_size_in_bytes == shard
        assert mem.temp_size_in_bytes < shard / 10


@pytest.mark.parametrize("kvh,hd,t_max,dtype", [
    (5, 64, 1024, BF16),              # gpt2-large's heads over four chips
    (2, 32, 256, BF16),               # a GQA draft model: 64 lanes of values
    (12, 64, 384, jnp.float32),
    (20, 64, 1024, jnp.int8), (8, 96, 512, jnp.float8_e4m3fn),
], ids=["tp_local_heads", "narrow_gqa", "f32", "int8", "fp8"])
def test_kv_store_columns(chip, kvh, hd, t_max, dtype):
    """The in-place store outside gpt2-large's shape: the kernel transposes
    KVh * hd lanes of new values, whatever their number (heads short of a
    lane tile: from 128 on `fits` refuses, PR 34)."""
    from paddle_tpu.ops import kv_store

    leaf, val = chip((3, 8, kvh, t_max, hd), dtype), chip((8, kvh, 1, hd),
                                                          dtype)
    assert kv_store.fits(leaf, val)
    assert _custom_calls(
        lambda c, v, pos: kv_store.store_columns(c, v, 1, pos,
                                                 interpret=False),
        leaf, val, chip((8,), jnp.int32)) == 1


_decode_attention_shapes = pytest.mark.parametrize("kvh,hd,t_max,dtype", [
    (20, 64, 1024, BF16),             # gpt2-large
    (5, 64, 1024, BF16),              # its heads over four chips: 320 lanes
    (4, 32, 256, BF16),               # a narrow draft model
    (12, 64, 384, jnp.float32),
], ids=["gpt2_large", "tp_local_heads", "narrow", "f32"])


@_decode_attention_shapes
def test_decode_attention(chip, kvh, hd, t_max, dtype):
    """The decode step's read of the live tiles alone: one kernel, and no
    copy or relayout of the cache around it."""
    from paddle_tpu.ops import decode_attention as da

    leaf, q = chip((3, 8, kvh, t_max, hd), dtype), chip((8, kvh, 1, hd),
                                                        dtype)
    assert da.fits(leaf, q)
    text = jax.jit(
        lambda k, v, q, pos: da.decode_attention(k, v, q, 1, pos,
                                                 interpret=False)
    ).lower(leaf, leaf, q, chip((8,), jnp.int32)).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert f"[3,8,{kvh},{t_max},{hd}]" not in "".join(
        line for line in text.splitlines() if " copy(" in line)


@_decode_attention_shapes
def test_decode_attention_store(chip, kvh, hd, t_max, dtype):
    """The read that stores the step's column into the tile it holds, over
    `test_decode_attention`'s shapes: one kernel, both leaves donated
    through it in place, no copy of the cache and no temporary."""
    from paddle_tpu.ops import decode_attention as da

    leaf, q = chip((3, 8, kvh, t_max, hd), dtype), chip((8, kvh, 1, hd),
                                                        dtype)
    assert da.fits(leaf, q)
    compiled = jax.jit(
        lambda k, v, q, k_new, v_new, pos: da.decode_attention_store(
            k, v, q, k_new, v_new, 1, pos, interpret=False),
        donate_argnums=(0, 1),
    ).lower(leaf, leaf, q, q, q, chip((8,), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert f"[3,8,{kvh},{t_max},{hd}]" not in "".join(
        line for line in text.splitlines() if " copy(" in line)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 2 * leaf.size * leaf.dtype.itemsize
    assert mem.temp_size_in_bytes == 0


@pytest.mark.parametrize("width, kernel", [(640, True), (640, False),
                                           (576, False)])
def test_latent_step_keeps_no_copy_of_its_cache(chip, monkeypatch, width,
                                                kernel):
    """One layer of models/axk1.py's absorbed decode step at its cell's widths
    (128 rows x 8,192 columns, 64 heads, latent 512 + 64). With the cache's
    minor axis in whole lanes (640) the step writes its column in place and
    reads the row through ops/latent_decode_attention.py (a `tpu_custom_call`,
    no temporaries to speak of) or, the kernel refused, through einsums whose
    temporaries are the float32 scores and little else. Handed the 576 values
    as they are, the chip's compiler unpacks the whole leaf into a padded copy
    inside the step (why `AXK1Config.cache_width` rounds up)."""
    from paddle_tpu.distributed import moe
    from paddle_tpu.models import axk1
    from paddle_tpu.ops import latent_decode_attention as lda

    monkeypatch.setattr(moe, "on_tpu", lambda: True)    # bf16 x bf16 -> f32
    monkeypatch.setattr(lda, "on_tpu", lambda: kernel)
    cfg = axk1.AXK1Config(num_hidden_layers=1, max_seq_len=8192)
    B, T, H = 128, 8192, cfg.num_heads
    r, dn, dr, dv = (cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim, cfg.v_head_dim)

    def step(lat, new, q_nope, q_rope, w_kvb, at):
        lat = lat.at[0, jnp.arange(B), at].set(new)
        o = axk1._attend_absorbed(q_nope, q_rope, lat, 0, w_kvb, at, cfg)
        return lat, o

    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        chip((1, B, T, width)), chip((B, width)), chip((B, H, dn)),
        chip((B, H, dr)), chip((r, H * (dn + dv))),
        chip((B,), jnp.int32)).compile()
    leaf = B * T * width * 2
    temp = compiled.memory_analysis().temp_size_in_bytes
    scores = B * H * T * 4
    assert ("tpu_custom_call" in compiled.as_text()) == kernel
    if kernel:
        assert temp < scores // 4, temp
    elif width % 128:
        assert temp > leaf
    else:
        assert temp < 3 * scores < leaf, (temp, scores)


@pytest.mark.parametrize("kernel", [True, False])
def test_latent_prefill_chunk_keeps_its_scores_in_fast_memory(
        chip, monkeypatch, kernel):
    """The prefill-chunk program of a small A.X-K1 (one layer, the dense
    one, a vocabulary of 1,024; the cell's attention widths: 64 heads, a
    chunk of 1,024 queries at a traced offset over a row of 8,192 latent
    columns). With ops/latent_prefill_attention.py taken (one
    `tpu_custom_call` a layer) the program holds no float32 `[1, 64, 1024,
    1024]` scores, its temporaries are under half the einsum loop's and
    its code no larger (a kernel that raises its fast-memory limit
    turns on XLA's prefetches across the program, +5 MB of code a chunk
    program, which `memory_peak_bytes` counts: PERF.md, PR 39); refused,
    the loop's scores are there. This is what would catch the loop
    quietly coming back."""
    from paddle_tpu.distributed import moe
    from paddle_tpu.models import axk1
    from paddle_tpu.ops import latent_prefill_attention as lpa

    monkeypatch.setattr(moe, "on_tpu", lambda: True)    # bf16 x bf16 -> f32
    cfg = axk1.AXK1Config(num_hidden_layers=1, vocab_size=1024,
                          n_routed_experts=12, max_seq_len=8192)
    fwd, _, _ = axk1._decode_fns(cfg)
    params = {n: chip(shape) for n, (shape, _) in
              axk1.param_shapes(cfg).items()}

    def compiled(take):
        def chunk(p, toks, offset, lat):    # a function of its own a choice
            x, kv, _ = fwd(p, toks, offset, {"latent": lat}, {})
            return x, kv["latent"]

        monkeypatch.setattr(lpa, "on_tpu", lambda: take)
        return jax.jit(chunk, donate_argnums=(3,)).lower(
            params, chip((1, 1024), jnp.int32), chip((), jnp.int32),
            chip((1, 1, 8192, 640))).compile()

    got = compiled(kernel)
    text = got.as_text()
    scores = "f32[1,64,1024,1024]"
    assert text.count("tpu_custom_call") == int(kernel)
    assert (scores in text) == (not kernel)
    if kernel:
        mem, loop = got.memory_analysis(), compiled(False).memory_analysis()
        assert mem.temp_size_in_bytes < loop.temp_size_in_bytes // 2
        # (a raised limit read +2.0 MB here; the kernel's own code is
        # what the loop's was, within 0.02 MB)
        assert mem.generated_code_size_in_bytes \
            < loop.generated_code_size_in_bytes + (1 << 20)


#: the A.X-K1 prefill-chunk program at its cell's widths, compiled for the
#: described chip: its code (resident in HBM, which `memory_peak_bytes`
#: counts, and within 3 GB of the chip in that cell) and the compiler's
#: prefetches into fast memory (a program that gets them grows by megabytes
#: of code, PERF.md)
CHUNK_CODE_BYTES = 13_459_456
CHUNK_COPY_STARTS = 1


def test_latent_prefill_chunk_program_holds_its_code_and_prefetches(
        chip, monkeypatch):
    """The benchmark cell's configuration (5 layers, the dense one and four
    expert layers of 12 held of 192 experts, a vocabulary of 20,480), a chunk
    of 1,024 tokens at a traced offset into a row of 8,192 latent columns:
    the attention kernel a layer and the combine's kernel an expert layer
    are there, the expert layers hold no buffer of the worst case's padded
    rows (9,728) by the width and sort once a layer, and the program's code
    and prefetches are no more than this file holds."""
    import json
    import os

    from benchmark.runners.serve_latent import program_config
    from paddle_tpu.distributed import moe
    from paddle_tpu.models import axk1
    from paddle_tpu.ops import latent_prefill_attention as lpa
    from paddle_tpu.ops import moe_rows

    for mod in (moe, lpa, moe_rows):
        monkeypatch.setattr(mod, "on_tpu", lambda: True)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", "a.x-k1.json")) as f:
        cfg = program_config(json.load(f), 8192)
    fwd, logits_of, _ = axk1._decode_fns(cfg)
    params = {n: chip(shape) for n, (shape, _) in
              axk1.param_shapes(cfg).items()}

    def chunk(p, toks, offset, lat, last):
        x, kv, _ = fwd(p, toks, offset, {"latent": lat}, {})
        x_last = jax.lax.dynamic_slice_in_dim(x, last, 1, axis=1)[:, 0]
        return kv["latent"], logits_of(p, x_last).astype(jnp.float32)[0]

    got = jax.jit(chunk, donate_argnums=(3,)).lower(
        params, chip((1, 1024), jnp.int32), chip((), jnp.int32),
        chip((5, 1, 8192, cfg.cache_width)), chip((), jnp.int32)).compile()
    text = got.as_text()
    assert text.count("tpu_custom_call") == 5 + 4
    assert text.count("moe_scatter_add_rows/pallas_call") == 4
    # the loops carry the result a token a slab into the kernel, which adds in
    # place: the program holds no copy of it
    carry = re.compile(r"= f32\[1024,56,128\]\{[^}]*\} copy\(")
    assert "f32[1024,56,128]" in text and not carry.search(text)
    assert "[9728,7168]" not in text
    assert text.count(" sort(") == 4 * 2      # one a layer, and its top-k's
    assert got.memory_analysis().generated_code_size_in_bytes \
        <= CHUNK_CODE_BYTES
    assert text.count("copy-start(") == CHUNK_COPY_STARTS
