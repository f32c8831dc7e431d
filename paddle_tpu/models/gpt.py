"""GPT-2 style causal LM — the flagship model (BASELINE.json config #4: GPT-2 medium /
ERNIE-class pretraining).

Built entirely from paddle_tpu.nn; tensor-parallel variants use the distributed.split
layers so SpmdTrainer shards the matmuls over 'mp'. Attention goes through
F.scaled_dot_product_attention (Pallas flash kernel on TPU when shapes tile).

Reference parity: the reference trains ERNIE/GPT through fleet on the same Transformer
building blocks (python/paddle/nn/layer/transformer.py); there is no gpt model file in
the reference tree — this is the framework's own model zoo.
"""
import math
import re

import jax
import numpy as np

from .. import nn
from ..core.tensor import Tensor
from ..nn import functional as F
from ..serving import decode_model as _decode_model


class GPTConfig:
    def __init__(self, vocab_size=50304, hidden_size=768, num_layers=12, num_heads=12,
                 max_seq_len=1024, intermediate_size=None, dropout=0.1,
                 tensor_parallel=False, use_flash=True,
                 num_experts=0, moe_every=2, moe_k=2, moe_capacity_factor=2.0,
                 moe_aux_weight=0.01, moe_mesh=None,
                 sequence_parallel=False, sp_mesh=None, sp_impl="ring",
                 gelu_approx=False, attention_window=None,
                 num_kv_heads=None):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.max_seq_len = max_seq_len
        self.intermediate_size = intermediate_size or 4 * hidden_size
        self.dropout = dropout
        self.tensor_parallel = tensor_parallel
        self.use_flash = use_flash
        # tanh-approximate gelu (HF GPT-2's gelu_new); False = exact erf
        self.gelu_approx = gelu_approx
        # MoE (num_experts > 0 turns every `moe_every`-th block's MLP into a
        # MoELayer; moe_mesh with an 'ep' axis enables expert parallelism)
        if num_experts > 0 and not (1 <= moe_every <= num_layers):
            raise ValueError(f"moe_every={moe_every} must be in [1, num_layers="
                             f"{num_layers}] when num_experts > 0")
        if num_experts > 0 and tensor_parallel:
            # MoE expert weights are not mp-sharded; combining would silently
            # replicate the dominant parameter mass on every mp rank. Use
            # expert parallelism (moe_mesh with an 'ep' axis) instead.
            raise ValueError("num_experts > 0 with tensor_parallel=True is not "
                             "supported; shard experts with moe_mesh ('ep' axis)")
        self.num_experts = num_experts
        self.moe_every = moe_every
        self.moe_k = moe_k
        self.moe_capacity_factor = moe_capacity_factor
        self.moe_aux_weight = moe_aux_weight
        self.moe_mesh = moe_mesh
        # long-context sequence parallelism (beyond-reference; SURVEY.md §5):
        # sp_mesh with an 'sp' axis shards attention over the sequence dim —
        # 'ring' rotates K/V blocks with ppermute, 'ulysses' all_to_alls
        # seq<->heads. Composes with dp on the same mesh.
        if sequence_parallel:
            if sp_mesh is None or "sp" not in sp_mesh.axis_names:
                raise ValueError("sequence_parallel=True needs sp_mesh with an "
                                 "'sp' axis (otherwise attention silently runs "
                                 "dense and defeats the sharding)")
            if dropout > 0:
                raise ValueError("sequence-parallel attention does not "
                                 "implement attention dropout; set dropout=0.0")
            sp_size = sp_mesh.shape["sp"]
            from ..distributed.long_context import VALID_SP_IMPLS

            if sp_impl not in VALID_SP_IMPLS:
                raise ValueError(f"sp_impl must be one of "
                                 f"{'|'.join(VALID_SP_IMPLS)}, got "
                                 f"{sp_impl!r}")
            if max_seq_len % sp_size != 0:
                raise ValueError(
                    f"sequence parallelism shards seq dim over sp={sp_size}: "
                    f"max_seq_len ({max_seq_len}) must divide evenly")
            if sp_impl.startswith("ulysses") and num_heads % sp_size != 0:
                raise ValueError(f"ulysses needs num_heads ({num_heads}) "
                                 f"divisible by sp={sp_size}")
            if sp_impl == "ring_flash":
                shard = max_seq_len // sp_size
                if max_seq_len % sp_size != 0 or shard % 128 != 0:
                    raise ValueError(
                        f"ring_flash needs the per-rank seq shard "
                        f"({max_seq_len}/{sp_size}={shard}) to be exact "
                        f"and a multiple of the 128 flash block")
            if sp_impl == "ulysses_flash" and max_seq_len % 128 != 0:
                raise ValueError("ulysses_flash needs the full seq "
                                 f"({max_seq_len}) to be a multiple of the "
                                 "128 flash block")
            if sp_impl.endswith("_flash") and \
                    (hidden_size // num_heads) % 64 != 0:
                raise ValueError(f"{sp_impl} needs head_dim % 64 == 0")
        self.sequence_parallel = sequence_parallel
        self.sp_mesh = sp_mesh
        self.sp_impl = sp_impl
        # sliding-window causal attention (Mistral-style): train AND decode
        # attend only to the last W tokens; flash block-skips out-of-band
        # pairs, the KV-cache decode masks the same band
        if attention_window is not None:
            import operator

            if isinstance(attention_window, bool):
                raise ValueError(f"attention_window must be a positive int, "
                                 f"got {attention_window!r}")
            try:
                attention_window = int(operator.index(attention_window))
            except TypeError:
                raise ValueError(
                    f"attention_window must be a positive int, got "
                    f"{attention_window!r}") from None
            if attention_window < 1:
                raise ValueError(f"attention_window must be a positive int, "
                                 f"got {attention_window!r}")
            if sequence_parallel:
                raise ValueError("attention_window does not compose with "
                                 "sequence_parallel yet")
        self.attention_window = attention_window
        # grouped-query attention (GQA): num_kv_heads < num_heads shares
        # each K/V head across a group of query heads — the KV cache (the
        # serving memory bound) shrinks by num_heads/num_kv_heads. Default
        # = num_heads (plain MHA, the packed qkv layout unchanged).
        num_kv_heads = num_kv_heads if num_kv_heads is not None else num_heads
        if (isinstance(num_kv_heads, bool)
                or not (1 <= num_kv_heads <= num_heads)
                or num_heads % num_kv_heads != 0):
            raise ValueError(
                f"num_kv_heads ({num_kv_heads!r}) must divide num_heads "
                f"({num_heads}) and lie in [1, num_heads]")
        if num_kv_heads != num_heads and tensor_parallel:
            raise ValueError("GQA with tensor_parallel layers is not "
                             "supported yet (KV-head sharding)")
        self.num_kv_heads = num_kv_heads

    @staticmethod
    def small():
        return GPTConfig(hidden_size=768, num_layers=12, num_heads=12)

    @staticmethod
    def medium():
        return GPTConfig(hidden_size=1024, num_layers=24, num_heads=16)

    @staticmethod
    def tiny():  # tests / dryrun
        return GPTConfig(vocab_size=1024, hidden_size=64, num_layers=2, num_heads=4,
                         max_seq_len=128, dropout=0.0)


class GPTAttention(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        h = cfg.hidden_size
        self.num_heads = cfg.num_heads
        self.head_dim = h // cfg.num_heads
        # GQA: K/V projections carry num_kv_heads heads; for plain MHA
        # (kv == heads) the packed layout is EXACTLY the historical
        # [h, 3h] — existing checkpoints load unchanged
        self.num_kv_heads = getattr(cfg, "num_kv_heads", cfg.num_heads)
        qkv_out = (self.num_heads + 2 * self.num_kv_heads) * self.head_dim
        self.use_flash = getattr(cfg, "use_flash", True)
        self.window = getattr(cfg, "attention_window", None)
        self.sp_mesh = cfg.sp_mesh if getattr(cfg, "sequence_parallel", False) else None
        self.sp_impl = getattr(cfg, "sp_impl", "ring")
        if cfg.tensor_parallel:
            from ..distributed.split import ColumnParallelLinear, RowParallelLinear

            self.qkv = ColumnParallelLinear(h, qkv_out)
            self.proj = RowParallelLinear(h, h)
        else:
            self.qkv = nn.Linear(h, qkv_out)
            self.proj = nn.Linear(h, h)
        self.dropout = cfg.dropout

    def forward(self, x):
        b, s, h = x.shape
        H, K, hd = self.num_heads, self.num_kv_heads, self.head_dim
        from ..tensor.manipulation import split as tsplit

        # the output projection is the sublayer `proj`: the same scope
        with jax.named_scope("proj"):
            qkv = self.qkv(x)
            # boundary split [q | k | v]: identical to the historical
            # (3, H, hd) unpacking when K == H
            q, k, v = tsplit(qkv, [H * hd, K * hd, K * hd], axis=-1)
            q = q.reshape([b, s, H, hd])
            k = k.reshape([b, s, K, hd])
            v = v.reshape([b, s, K, hd])
            if K != H:
                # expand shared K/V heads across their query groups for the
                # dense/flash attention math (the cache-side decode keeps
                # the compact K heads — that is where GQA's memory win lives)
                from ..tensor.manipulation import repeat_interleave

                k = repeat_interleave(k, H // K, axis=2)
                v = repeat_interleave(v, H // K, axis=2)
        with jax.named_scope("core"):
            out = self._core(q, k, v, s)
        return self.proj(out.reshape([b, s, h]))

    def _core(self, q, k, v, s):
        if self.sp_mesh is not None and "sp" in self.sp_mesh.axis_names:
            from ..core.dispatch import apply
            from ..distributed.long_context import sequence_parallel_attention

            # config validation covers max_seq_len; the RUNTIME seq must
            # satisfy the same constraints (shorter batches are routine)
            sp_size = self.sp_mesh.shape["sp"]
            if s % sp_size != 0:
                raise ValueError(f"seq {s} must divide over sp={sp_size}")
            if self.sp_impl == "ring_flash" and (s // sp_size) % 128 != 0:
                raise ValueError(
                    f"ring_flash needs the per-rank shard ({s}/{sp_size}="
                    f"{s // sp_size}) in 128-token flash blocks: pad the "
                    f"batch to a multiple of {128 * sp_size} or use "
                    f"sp_impl='ring'")
            if self.sp_impl == "ulysses_flash" and s % 128 != 0:
                raise ValueError(
                    f"ulysses_flash needs seq ({s}) in 128-token flash "
                    f"blocks: pad to a multiple of 128 or use "
                    f"sp_impl='ulysses'")
            return apply(
                lambda qv, kv, vv: sequence_parallel_attention(
                    qv, kv, vv, self.sp_mesh, impl=self.sp_impl, causal=True),
                q, k, v)
        return F.scaled_dot_product_attention(
            q, k, v, is_causal=True,
            dropout_p=self.dropout if self.training else 0.0,
            training=self.training,
            use_flash=self.use_flash,
            window=self.window,
        )


class GPTMLP(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        h, i = cfg.hidden_size, cfg.intermediate_size
        if cfg.tensor_parallel:
            from ..distributed.split import ColumnParallelLinear, RowParallelLinear

            self.fc1 = ColumnParallelLinear(h, i)
            self.fc2 = RowParallelLinear(i, h)
        else:
            self.fc1 = nn.Linear(h, i)
            self.fc2 = nn.Linear(i, h)
        self._gelu_approx = getattr(cfg, "gelu_approx", False)

    def forward(self, x):
        return self.fc2(F.gelu(self.fc1(x), approximate=self._gelu_approx))


class GPTBlock(nn.Layer):
    def __init__(self, cfg, layer_idx=0):
        super().__init__()
        self.ln1 = nn.LayerNorm(cfg.hidden_size)
        self.attn = GPTAttention(cfg)
        self.ln2 = nn.LayerNorm(cfg.hidden_size)
        if cfg.num_experts > 0 and (layer_idx + 1) % cfg.moe_every == 0:
            self.mlp = nn.MoELayer(
                cfg.hidden_size, cfg.intermediate_size, cfg.num_experts,
                k=cfg.moe_k, capacity_factor=cfg.moe_capacity_factor,
                mesh=cfg.moe_mesh)
        else:
            self.mlp = GPTMLP(cfg)
        self.drop = nn.Dropout(cfg.dropout)

    def forward(self, x):
        # a sublayer's norm lies under the sublayer's word, as in the
        # decode programs (docs/OBSERVABILITY.md "Device scopes")
        with jax.named_scope("attn"):
            h = self.ln1(x)
        x = x + self.drop(self.attn(h))
        y = self._tpp_mlp(x)
        if y is None:
            with jax.named_scope("mlp"):
                h = self.ln2(x)
            y = self.mlp(h)
        x = x + self.drop(y)
        return x

    def _tpp_mlp(self, x):
        """FLAGS_tpp_kernels (docs/PERF.md): route ln2+MLP through the
        TPP registry's ported ops — ln_matmul (the layernorm->matmul
        prologue) feeding the fused gelu+projection tail. One get_flag
        when disarmed; the registry module is only imported armed. None
        = dense fallback (flag unset, MoE/tensor-parallel MLPs, or
        shapes the registry can't tile). Kernel path needs functional
        autodiff (SpmdTrainer) — custom_vjp does not ride the eager
        tape, same restriction as every Pallas op here."""
        from .. import flags as _flags

        if not _flags.get_flag("tpp_kernels", False):
            return None
        from .. import nn as _nn

        if not isinstance(self.mlp, GPTMLP) \
                or not isinstance(self.mlp.fc1, _nn.Linear):
            return None
        from ..core.tensor import Tensor
        from ..ops import tpp

        out = tpp.gpt_block_mlp(x._data, self.ln2, self.mlp)
        return None if out is None else Tensor(out)


class GPTModel(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        if cfg.tensor_parallel:
            from ..distributed.split import VocabParallelEmbedding

            self.wte = VocabParallelEmbedding(cfg.vocab_size, cfg.hidden_size)
        else:
            self.wte = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.wpe = nn.Embedding(cfg.max_seq_len, cfg.hidden_size)
        self.drop = nn.Dropout(cfg.dropout)
        self.blocks = nn.LayerList([GPTBlock(cfg, i) for i in range(cfg.num_layers)])
        self.ln_f = nn.LayerNorm(cfg.hidden_size)

    def forward(self, input_ids):
        b, s = input_ids.shape
        import jax.numpy as jnp

        from ..tensor.creation import arange

        with jax.named_scope("embed"):
            pos = arange(s, dtype="int32")  # int32: x64 is off on TPU/CPU — an "int64" request
            # is truncated with a per-call UserWarning (caught by the analysis trace-warnings gate)
            x = self.wte(input_ids) + self.wpe(pos)
            x = self.drop(x)
        for blk in self.blocks:
            x = blk(x)
        with jax.named_scope("head"):
            return self.ln_f(x)


class GPTForCausalLM(nn.Layer):
    """LM head ties to wte (weight sharing, paddle GPT convention)."""

    def __init__(self, cfg):
        super().__init__()
        self.gpt = GPTModel(cfg)
        self.cfg = cfg

    def forward(self, input_ids):
        h = self.gpt(input_ids)
        if getattr(self, "lm_head", None) is not None:
            # untied head installed by pipeline_split: after pipelined training
            # the trained head lives here, not in wte
            with jax.named_scope("head"):
                return self.lm_head(h)
        # tied head: logits = h @ wte^T
        from ..tensor.math import matmul

        with jax.named_scope("head"):
            return matmul(h, self.gpt.wte.weight, transpose_y=True)

    def loss(self, input_ids, labels):
        logits = self.forward(input_ids)
        b, s, v = logits.shape
        loss = F.cross_entropy(logits.reshape([b * s, v]), labels.reshape([b * s]))
        aux = self.moe_aux_loss()
        if aux is not None:
            loss = loss + self.cfg.moe_aux_weight * aux
        return loss

    def generate(self, input_ids, max_new_tokens=32, temperature=1.0,
                 top_k=None, top_p=None, seed=None, eos_token_id=None,
                 num_beams=1, length_penalty=1.0, dtype=None,
                 attention_mask=None, cache_dtype=None, tp_mesh=None):
        """Autoregressive decode with a KV cache, compiled as ONE program
        (prefill + lax.scan; static shapes, dynamic_update_slice cache).
        temperature=0 decodes greedily; otherwise samples — top_k keeps the
        k highest logits and top_p then applies nucleus filtering (smallest
        prefix reaching mass top_p; needs top_p < 1.0 to take effect).
        num_beams>1 runs beam search and returns a (sequences, scores)
        pair — the best beam per batch row plus its joint log-prob
        (PaddleNLP generate convention); sampling knobs (temperature/top_k/
        top_p) do not apply to beam search, which raises if they are set.
        Sequences are [b, prompt + max_new_tokens] ids including the prompt.
        cache_dtype='int8' quantizes the KV cache (per-row absmax scales) —
        half the bf16 cache's HBM traffic in the HBM-bound decode loop;
        composes with dtype='bfloat16' params.
        tp_mesh (a Mesh with an 'mp' axis) serves a DENSE model
        tensor-parallel: heads and the MLP inner dim shard over mp, the KV
        cache holds only local heads, two psums per layer ride the ICI —
        for models too big for one chip's HBM.
        See _gpt_generate/_gpt_beam_search for the TPU design notes."""
        if num_beams > 1:
            if top_p is not None or top_k is not None:
                raise ValueError(
                    "top_k/top_p are sampling knobs; beam search is "
                    "deterministic — drop them or use num_beams=1")
            return _gpt_beam_search(self, input_ids, max_new_tokens,
                                    num_beams, eos_token_id, length_penalty,
                                    dtype=dtype,
                                    attention_mask=attention_mask,
                                    cache_dtype=cache_dtype,
                                    tp_mesh=tp_mesh)
        return _gpt_generate(self, input_ids, max_new_tokens, temperature,
                             top_k, seed, eos_token_id, dtype=dtype,
                             attention_mask=attention_mask, top_p=top_p,
                             cache_dtype=cache_dtype, tp_mesh=tp_mesh)

    def generate_speculative(self, draft_model, input_ids,
                             max_new_tokens=32, k=4, dtype=None,
                             cache_dtype=None, tp_mesh=None,
                             eos_token_id=None):
        """Speculative greedy decoding with a small draft model: identical
        output to greedy `generate` (the acceptance rule is exact) but
        1..k+1 tokens per target forward. Returns (sequences, n_rounds) —
        n_rounds target forwards vs max_new_tokens single-token steps is
        the speedup headroom. Batch 1; greedy only. tp_mesh shards the
        TARGET over 'mp' (the draft stays replicated — it is small by
        design). See _gpt_speculative for the cache-invariant notes."""
        return _gpt_speculative(self, draft_model, input_ids,
                                max_new_tokens, k=k, dtype=dtype,
                                cache_dtype=cache_dtype, tp_mesh=tp_mesh,
                                eos_token_id=eos_token_id)

    def pipeline_split(self, pp_degree):
        """Split into (pre, stages, post_loss) for distributed.pipeline.
        PipelineTrainer. Unties the LM head (see GPTHeadLoss) and installs it
        as self.lm_head so forward()/state_dict() use the trained head after
        PipelineTrainer.sync_to_layer()."""
        return _gpt_pipeline_split(self, pp_degree)

    def moe_aux_loss(self):
        """Sum of MoE load-balance losses from the last forward (None if dense)."""
        aux = None
        for blk in self.gpt.blocks:
            a = getattr(blk.mlp, "aux_loss", None)
            if a is not None:
                aux = a if aux is None else aux + a
        return aux


class GPTPretrainLoss(nn.Layer):
    def forward(self, logits, labels):
        b, s, v = logits.shape
        with jax.named_scope("loss"):
            return F.cross_entropy(logits.reshape([b * s, v]), labels.reshape([b * s]))


# ---------------------------------------------------------------------------
# Autoregressive decoding with a KV cache (the serving path).
# ---------------------------------------------------------------------------

def _cache_map(f, c):
    """Apply f to a cache leaf: a plain array, or an (int8 values, scales)
    pair. Keeps beam-search cache reshuffles codec-agnostic."""
    return tuple(f(x) for x in c) if isinstance(c, tuple) else f(c)


def _row_update(cache_i, val, pos_vec):
    """Row b of `val` [B, KVh, t, hd] lands at its OWN columns pos_vec[b]..
    of cache layer `cache_i` [B, KVh, T, hd] (continuous-batching serving:
    slots sit at different sequence positions). A select on the column
    index: one elementwise pass over the layer in the layout it is stored
    in. The chip keeps the cache T-minor, where a per-row
    dynamic_update_slice costs two relayouts of the layer and a serial loop
    over the rows (docs/SERVING.md "The dense cache on the chip"). It stores
    the values dynamic_update_slice would, with the same clamp of the start."""
    import jax.numpy as jnp

    T, t = cache_i.shape[2], val.shape[2]
    off = jnp.arange(T)[None, :] - jnp.clip(pos_vec, 0, T - t)[:, None]
    for j in range(t):
        cache_i = jnp.where((off == j)[:, None, :, None],
                            val[:, :, j:j + 1], cache_i)
    return cache_i


def _live_tile(kc, q, pos, win, key_valid):
    """The width, in cache columns, of the tiles this step's attention reads
    each row in, up to the row's position and no further, storing the step's
    keys and values into the last of them (ops/decode_attention.py), or None
    where a store of its own runs first and the attention contracts with
    all T columns and masks. Chosen from what can be seen: one query a row,
    each row at its own `pos`, nothing masked but the columns past it, a
    plain cache side of a shape the kernel takes, a TPU."""
    import jax.numpy as jnp

    from ..ops import decode_attention

    if (jnp.ndim(pos) == 1 and q.shape[2] == 1 and win is None
            and key_valid is None and decode_attention.live_only(kc, q)):
        return decode_attention.LANE
    return None


def _decode_fns(cfg, untied, untied_bias, cache_dtype=None, tp_axis=None,
                tp_size=1):
    """Pure-jnp decode math shared by sampling and beam search: returns
    (fwd, logits_of, cache_init). fwd(p, tok_ids [B, t], pos, kc, vc) runs
    the block stack with the KV cache [L, B, H, T, hd] (B is read from the
    input, so beam-expanded batches reuse the same functions).

    cache_dtype='int8' stores the cache as int8 values + per-row (over hd)
    f32 absmax scales, halving the HBM traffic of the cache reads that
    bound the decode loop even vs a bf16 cache; values dequantize blockwise
    into the attention einsums (XLA fuses the multiply into the read).
    cache_dtype='fp8' stores float8_e4m3fn at the same byte footprint —
    scaled casts keep a mantissa instead of integer rounding (native fp8
    on v5e+-class TPUs). No reference analog (the reference has no fused
    KV-cache decode at all) — these are the quantized-KV serving recipes
    from modern LLM inference stacks.

    tp_axis/tp_size: tensor-parallel serving inside shard_map — attention
    heads and the MLP inner dim are sharded over the mesh axis (Megatron
    column/row split), the KV cache holds only the local heads, and one
    psum after attn.proj + one after mlp.fc2 restore replicated
    activations. Param layout in this mode: qkv.weight [h, 3, H_loc, hd],
    qkv.bias [3, H_loc, hd] (see _tp_param_shard)."""
    import jax
    import jax.numpy as jnp

    from ..ops import decode_attention as _decode_attention
    from ..ops import kv_store as _kv_store

    L, Hh = cfg.num_layers, cfg.num_heads
    hd = cfg.hidden_size // Hh
    scale = 1.0 / math.sqrt(hd)
    # quantized cache formats: (storage dtype, qmax, integer rounding).
    # int8 rounds+clips to +-127; fp8 (e4m3fn, max ~448) just casts — the
    # per-row absmax scale puts values inside its representable range, and
    # the cast keeps a mantissa instead of rounding to integers (coarser
    # scale granularity, finer within-row resolution)
    _QUANT = {"int8": (jnp.int8, 127.0, True),
              "fp8": (jnp.float8_e4m3fn, 448.0, False)}
    if cache_dtype is not None and cache_dtype not in _QUANT:
        # the single interpreter of cache_dtype validates it for EVERY
        # entry point (generate, beam, speculative, ServingEngine) — a
        # typo must never silently serve a full-precision cache
        raise ValueError(
            f"cache_dtype must be None, 'int8', or 'fp8', "
            f"got {cache_dtype!r}")
    quant = _QUANT.get(cache_dtype)
    win = getattr(cfg, "attention_window", None)
    KVh = getattr(cfg, "num_kv_heads", Hh)  # GQA: compact K/V heads
    g = Hh // KVh                           # query heads per kv head
    H_loc = Hh // tp_size   # local q heads (== Hh when not tensor-parallel)
    KV_loc = KVh // tp_size  # (GQA+tp rejected at config: KVh==Hh under tp)

    def cache_init(b_, T_, dt):
        # the cache holds only the COMPACT kv heads — the GQA serving win
        shape = (L, b_, KV_loc, T_, hd)
        if quant is None:
            z = jnp.zeros(shape, dt)
            return z, jnp.zeros_like(z)
        vals = jnp.zeros(shape, quant[0])
        scales = jnp.zeros((L, b_, KV_loc, T_, 1), jnp.float32)
        return (vals, scales), (jnp.zeros_like(vals),
                                jnp.zeros_like(scales))

    def _put(leaf, val, i, pos):
        """val [B, KVh, t, *] into layer i of a cache leaf at column pos
        (scalar: the whole batch at one frontier) or pos[b] (per row)."""
        if jnp.ndim(pos) == 1:
            if _kv_store.in_place(leaf, val):
                return _kv_store.store_columns(leaf, val, i, pos)
            val, pos = _row_update(leaf[i], val, pos), 0
        return jax.lax.dynamic_update_slice(leaf, val[None],
                                            (i, 0, 0, pos, 0))

    def _store(c, val, i, pos):
        if quant is None:
            return _put(c, val, i, pos)
        qdt, qmax, integer = quant
        vals, scales = c
        s = jnp.maximum(
            jnp.max(jnp.abs(val), axis=-1, keepdims=True).astype(
                jnp.float32) / qmax, 1e-8)
        q = val.astype(jnp.float32) / s
        if integer:
            q = jnp.clip(jnp.round(q), -qmax, qmax)
        return _put(vals, q.astype(qdt), i, pos), _put(scales, s, i, pos)

    def _load(c, i, like):
        if quant is None:
            return c[i]
        vals, scales = c
        return (vals[i].astype(jnp.float32) * scales[i]).astype(like)

    def ln(x, w, bb):
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + 1e-5) * w + bb

    def block(p, i, x, kc, vc, pos, key_valid=None, lora=None):
        """x [B, t, h] whose first column sits at cache column `pos`.
        key_valid [B, T] (optional): False columns (left-pad slots) are
        masked out of every real query; a pad-position query still sees
        itself so its softmax row is never empty (its lane is garbage that
        no valid query ever reads).

        lora (optional, multi-LoRA serving): per-row ALREADY-GATHERED
        adapter factors — {"_scale": [B], kind: (A [B, L, din, r],
        B [B, L, r, dout])} for kind in qkv|proj|fc1|fc2. Each present
        kind's matmul grows a per-row low-rank delta
        ``(x @ A[:, i]) @ B[:, i] * scale`` batched over rows by ONE
        gathered einsum pair — no per-adapter program, no recompiles."""
        pre = f"gpt.blocks.{i}."
        bb, t = x.shape[0], x.shape[1]
        T = (kc[0] if isinstance(kc, tuple) else kc).shape[3]

        def _ldelta(xin, kind):
            A, Bm = lora[kind]
            d = jnp.einsum("bti,bir->btr", xin, A[:, i])
            d = jnp.einsum("btr,bro->bto", d, Bm[:, i])
            # adapter slot 0 is all-zero (base requests): the delta is an
            # exact-zero add in xin's dtype, never a dtype promotion
            return (d * lora["_scale"][:, None, None]).astype(xin.dtype)

        with jax.named_scope("attn"):
            with jax.named_scope("proj"):
                h_in = ln(x, p[pre + "ln1.weight"], p[pre + "ln1.bias"])
                if tp_axis is not None:
                    # column-parallel qkv over LOCAL heads: weight
                    # [h, 3, H_loc, hd]
                    qkv = jnp.einsum("bti,iknd->btknd",
                                     h_in, p[pre + "attn.qkv.weight"]) \
                        + p[pre + "attn.qkv.bias"]
                    q = jnp.moveaxis(qkv[:, :, 0], 1, 2)  # [B, H_loc, t, hd]
                    k = jnp.moveaxis(qkv[:, :, 1], 1, 2)
                    v = jnp.moveaxis(qkv[:, :, 2], 1, 2)
                else:
                    # boundary split [q | k | v] — identical to the
                    # historical (3, H, hd) unpacking for MHA, compact kv
                    # heads for GQA
                    flat = h_in @ p[pre + "attn.qkv.weight"] \
                        + p[pre + "attn.qkv.bias"]
                    if lora is not None and "qkv" in lora:
                        flat = flat + _ldelta(h_in, "qkv")
                    q = jnp.moveaxis(
                        flat[..., :Hh * hd].reshape(bb, t, Hh, hd), 1, 2)
                    k = jnp.moveaxis(
                        flat[..., Hh * hd:(Hh + KVh) * hd].reshape(
                            bb, t, KVh, hd), 1, 2)
                    v = jnp.moveaxis(
                        flat[..., (Hh + KVh) * hd:].reshape(bb, t, KVh, hd),
                        1, 2)
            # a decode step on the chip leaves the store to its attention
            fused = _live_tile(kc, q, pos, win, key_valid)
            if not fused:
                with jax.named_scope("cache/store"):
                    kc = _store(kc, k, i, pos)
                    vc = _store(vc, v, i, pos)
            with jax.named_scope("core"):
                # causal over cache columns: query row r (column pos+r) sees
                # cache column c iff c <= pos + r. pos is a scalar (whole batch
                # at one frontier) or [B] (per-slot frontiers — continuous
                # batching); one mask construction serves both via a leading
                # 1-or-B dim.
                pos_b = jnp.atleast_1d(pos)
                rows = pos_b[:, None, None] + jnp.arange(t)[None, :, None]
                cols = jnp.arange(T)[None, None, :]
                mask = cols <= rows                            # [1-or-B, t, T]
                if win is not None:  # sliding window: same band as training
                    mask &= (rows - cols) < win
                if key_valid is not None:
                    self_col = cols == rows        # keep self: no NaN rows
                    mask = mask & (key_valid[:, None, :] | self_col)
                if fused:
                    # tiles 0..pos[b] // 128 of row b and no column beyond
                    # them; the last of them takes the new column and is
                    # written back
                    out, kc, vc = _decode_attention.decode_attention_store(
                        kc, vc, q, k, v, i, pos)
                elif g == 1:
                    att = jnp.einsum("bhtd,bhTd->bhtT", q,
                                     _load(kc, i, q.dtype)) * scale
                    att = jnp.where(mask[:, None], att, -jnp.inf)
                    att = jax.nn.softmax(att, axis=-1)
                    out = jnp.einsum("bhtT,bhTd->bhtd", att,
                                     _load(vc, i, att.dtype))
                else:
                    # grouped queries share their kv head: [B, KVh, g, t, *]
                    qg = q.reshape(bb, KVh, g, t, hd)
                    att = jnp.einsum("bkgtd,bkTd->bkgtT", qg,
                                     _load(kc, i, q.dtype)) * scale
                    att = jnp.where(mask[:, None, None], att, -jnp.inf)
                    att = jax.nn.softmax(att, axis=-1)
                    out = jnp.einsum("bkgtT,bkTd->bkgtd", att,
                                     _load(vc, i, att.dtype)).reshape(
                                         bb, Hh, t, hd)
            with jax.named_scope("proj"):
                out = jnp.moveaxis(out, 1, 2).reshape(bb, t, H_loc * hd)
                # row-parallel under tp
                proj = out @ p[pre + "attn.proj.weight"]
                if lora is not None and "proj" in lora:
                    proj = proj + _ldelta(out, "proj")
                if tp_axis is not None:
                    proj = jax.lax.psum(proj, tp_axis)
                x = x + proj + p[pre + "attn.proj.bias"]
        with jax.named_scope("mlp"):
            h2 = ln(x, p[pre + "ln2.weight"], p[pre + "ln2.bias"])
            a1 = h2 @ p[pre + "mlp.fc1.weight"] + p[pre + "mlp.fc1.bias"]
            if lora is not None and "fc1" in lora:
                a1 = a1 + _ldelta(h2, "fc1")
            h2 = jax.nn.gelu(a1,
                             approximate=getattr(cfg, "gelu_approx", False))
            mlp = h2 @ p[pre + "mlp.fc2.weight"]      # row-parallel under tp
            if lora is not None and "fc2" in lora:
                mlp = mlp + _ldelta(h2, "fc2")
            if tp_axis is not None:
                mlp = jax.lax.psum(mlp, tp_axis)
            x = x + mlp + p[pre + "mlp.fc2.bias"]
        return x, kc, vc

    def logits_of(p, x_last):
        with jax.named_scope("head"):
            h = ln(x_last, p["gpt.ln_f.weight"], p["gpt.ln_f.bias"])
            if untied:
                out = h @ p["lm_head.weight"]
                return out + p["lm_head.bias"] if untied_bias else out
            return h @ p["gpt.wte.weight"].T

    def fwd(p, tok_ids, pos, kc, vc, key_valid=None, pos_ids=None,
            lora=None, adapter_ids=None):
        t = tok_ids.shape[1]
        with jax.named_scope("embed"):
            if pos_ids is None:
                if jnp.ndim(pos) == 1:   # per-row pos needs per-row pe too
                    pos_ids = pos[:, None] + jnp.arange(t)[None, :]
                    wpe = jnp.take(p["gpt.wpe.weight"], pos_ids, axis=0)
                else:
                    wpe = jax.lax.dynamic_slice_in_dim(p["gpt.wpe.weight"],
                                                       pos, t)
            else:
                # ragged rows: per-row position ids (left-padding support)
                wpe = jnp.take(p["gpt.wpe.weight"], pos_ids, axis=0)
            x = jnp.take(p["gpt.wte.weight"], tok_ids, axis=0) + wpe
        lg = None
        if lora is not None:
            if tp_axis is not None:
                # the low-rank delta would need its own column/row split
                # and psum placement — unsupported rather than wrong
                raise ValueError(
                    "multi-LoRA decode is not supported under tensor-"
                    "parallel serving (tp_mesh); serve adapters dense")
            # ONE gather per step hoists every row's adapter factors out
            # of the layer loop: [S, L, din, r] -> [B, L, din, r]
            lg = {"_scale": lora["scale"][adapter_ids]}
            for kind in ("qkv", "proj", "fc1", "fc2"):
                if kind in lora:
                    lg[kind] = (lora[kind]["A"][adapter_ids],
                                lora[kind]["B"][adapter_ids])
        for i in range(L):
            x, kc, vc = block(p, i, x, kc, vc, pos, key_valid=key_valid,
                              lora=lg)
        return x, kc, vc

    return fwd, logits_of, cache_init


def _check_decode_config(cfg):
    if cfg.num_experts > 0 or cfg.sequence_parallel or cfg.tensor_parallel:
        raise ValueError(
            "generate() decodes dense single-replica configs; for parallel "
            "variants run the dense copy of the trained weights (state_dict "
            "round-trips) or use BeamSearchDecoder/dynamic_decode")


def _decode_compute_dtype(dtype):
    """None = f32 (exact); 'bfloat16'/'float16' = low-precision serving:
    params and the KV cache cast down (the decode loop is HBM-bound, so the
    cache halving is the win); logits always pick in f32."""
    if dtype is None:
        return None
    import jax.numpy as jnp

    from ..core import dtype as dtype_mod

    d = dtype_mod.convert_dtype(dtype)
    if not jnp.issubdtype(d, jnp.floating):
        raise ValueError(f"generate dtype must be floating, got {dtype!r}")
    if d == jnp.float32:
        return None  # the default path already IS f32 — avoid a dup compile
    return d


def _decode_setup(model, input_ids, max_new_tokens):
    import jax.numpy as jnp

    cfg = model.cfg
    _check_decode_config(cfg)
    ids = input_ids._data if isinstance(input_ids, Tensor) else \
        jnp.asarray(np.asarray(input_ids))
    b, s0 = ids.shape
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    T = s0 + max_new_tokens
    if T > cfg.max_seq_len:
        raise ValueError(f"prompt {s0} + max_new_tokens {max_new_tokens} "
                         f"exceeds max_seq_len {cfg.max_seq_len}")
    untied, untied_bias, params = _decode_params(model, "the model")
    return cfg, ids, b, s0, T, untied, untied_bias, params


def _decode_params(model, who):
    """Name-addressed param snapshot for the decode programs + the shared
    un-merged-LoRA guard and untied-head detection."""
    untied = getattr(model, "lm_head", None) is not None
    params = {n: p._data for n, p in model.named_parameters()}
    if any(".lora_A" in n for n in params):  # any wrap site, any Linear
        raise ValueError(
            f"decoding reads name-addressed params and {who} has un-merged "
            "LoRA adapters: call paddle_tpu.incubate.lora.merge_lora on it "
            "before generating, or use the eager forward for sampling "
            "during fine-tuning")
    # pipeline_split installs the head with bias_attr=False: no bias param
    untied_bias = untied and "lm_head.bias" in params
    return untied, untied_bias, params


def _tp_param_shard(params, cfg):
    """Reshape the packed qkv params for head-sharded serving and build the
    per-name PartitionSpecs (Megatron column/row split). Returns
    (params, specs): qkv.weight [h, 3h] -> [h, 3, H, hd] sharded on H;
    proj/fc2 row-split with the matching psum in the decode block; biases
    of row-parallel layers stay replicated (added once after the psum)."""
    from jax.sharding import PartitionSpec as P

    h, Hh = cfg.hidden_size, cfg.num_heads
    hd = h // Hh
    out, specs = {}, {}
    for n, v in params.items():
        if n.endswith("attn.qkv.weight"):
            v = v.reshape(h, 3, Hh, hd)
            specs[n] = P(None, None, "mp", None)
        elif n.endswith("attn.qkv.bias"):
            v = v.reshape(3, Hh, hd)
            specs[n] = P(None, "mp", None)
        elif n.endswith("attn.proj.weight"):
            specs[n] = P("mp", None)
        elif n.endswith("mlp.fc1.weight"):
            specs[n] = P(None, "mp")
        elif n.endswith("mlp.fc1.bias"):
            specs[n] = P("mp")
        elif n.endswith("mlp.fc2.weight"):
            specs[n] = P("mp", None)
        else:
            specs[n] = P()  # ln/embeddings/head + row-parallel biases
        out[n] = v
    return out, specs


def _tp_setup(tp_mesh, cfg, params):
    """Shared tensor-parallel serving setup: validates the mesh/config and
    reshapes+specs the params. Returns (tp_axis, tp_size, params, specs)."""
    if "mp" not in tp_mesh.axis_names:
        raise ValueError("tp_mesh needs an 'mp' axis")
    if getattr(cfg, "num_kv_heads", cfg.num_heads) != cfg.num_heads:
        raise ValueError("GQA tensor-parallel serving is not supported yet "
                         "(KV-head sharding); serve dense or use MHA")
    tp_size = tp_mesh.shape["mp"]
    Hh, inter = cfg.num_heads, cfg.intermediate_size
    if Hh % tp_size != 0 or inter % tp_size != 0:
        raise ValueError(
            f"tensor-parallel serving needs num_heads ({Hh}) and the "
            f"MLP inner dim ({inter}) divisible by mp={tp_size}")
    params, specs = _tp_param_shard(params, cfg)
    return "mp", tp_size, params, specs


def _tp_wrap(run, tp_mesh, tp_specs, n_extra_in, out_specs, in_specs=None,
             donate=()):
    """jit(shard_map(run)) for TP serving: params sharded per tp_specs and
    the n_extra_in trailing args replicated — or fully explicit in_specs
    (the serving engine passes its head-sharded cache specs); `donate`
    forwards to jit (in-place cache updates)."""
    import jax
    from jax.sharding import PartitionSpec as P

    if in_specs is None:
        in_specs = (tp_specs,) + (P(),) * n_extra_in
    # check_vma OFF: replication inference has no rule for the decode
    # loop's while/scan carries (beam search, speculative), and a CHECKING
    # shard_map turns those decodes into trace-time errors
    mapped = jax.shard_map(run, mesh=tp_mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
    return jax.jit(mapped, donate_argnums=donate)


def _gpt_generate(model, input_ids, max_new_tokens, temperature, top_k,
                  seed, eos_token_id, dtype=None, attention_mask=None,
                  top_p=None, cache_dtype=None, tp_mesh=None):
    """TPU-native autoregressive decode: ONE jitted program — prefill plus a
    lax.scan over decode steps against a static-shape KV cache updated with
    dynamic_update_slice. No per-step retrace, no dynamic shapes; the decode
    math is a pure-jnp mirror of the dense layer stack (parity against the
    cache-free full forward is pinned by tests/test_gpt_generate.py).

    Reference analog: the reference serves decoding via BeamSearchDecoder/
    dynamic_decode (which this framework also has); a fused single-program
    KV-cache loop is the TPU-idiomatic form."""
    import jax
    import jax.numpy as jnp

    cfg, ids, b, s0, T, untied, untied_bias, params = _decode_setup(
        model, input_ids, max_new_tokens)
    L, Hh = cfg.num_layers, cfg.num_heads
    hd = cfg.hidden_size // Hh
    tp_axis, tp_size, tp_specs = None, 1, None
    if tp_mesh is not None:
        tp_axis, tp_size, params, tp_specs = _tp_setup(tp_mesh, cfg, params)
    fwd, logits_of, cache_init = _decode_fns(cfg, untied, untied_bias,
                                             cache_dtype=cache_dtype,
                                             tp_axis=tp_axis,
                                             tp_size=tp_size)
    compute_dtype = _decode_compute_dtype(dtype)
    mask = _left_pad_mask(attention_mask, b, s0)

    def pick(logits, key):
        if temperature == 0.0:
            return jnp.argmax(logits, -1).astype(jnp.int32)
        lg = logits / temperature
        if top_k is not None and top_k > 0:
            kth = jnp.sort(lg, axis=-1)[:, -top_k][:, None]
            lg = jnp.where(lg < kth, -jnp.inf, lg)
        if top_p is not None and top_p < 1.0:
            # nucleus: keep the smallest prefix of the sorted distribution
            # whose mass reaches top_p (the top token always survives)
            srt = jnp.sort(lg, axis=-1)[:, ::-1]
            probs = jax.nn.softmax(srt, axis=-1)
            cum = jnp.cumsum(probs, axis=-1)
            k_keep = jnp.sum(cum - probs < top_p, axis=-1)     # [b]
            cutoff = jnp.take_along_axis(
                srt, jnp.maximum(k_keep - 1, 0)[:, None], axis=-1)
            lg = jnp.where(lg < cutoff, -jnp.inf, lg)
        return jax.random.categorical(key, lg).astype(jnp.int32)

    def run(p, ids_, key, mask_):
        if compute_dtype is not None:
            # serving precision: bf16 params + bf16 KV cache (half the HBM
            # traffic the decode loop is bound by); logits pick in f32
            p = {k: (v.astype(compute_dtype)
                     if jnp.issubdtype(v.dtype, jnp.floating) else v)
                 for k, v in p.items()}
        kc, vc = cache_init(b, T, compute_dtype or jnp.float32)
        lens, key_valid, pos_ids = _ragged_setup(mask_, b, s0, T)
        x, kc, vc = fwd(p, ids_, 0, kc, vc, key_valid=key_valid,
                        pos_ids=pos_ids)
        tok = pick(logits_of(p, x[:, -1]).astype(jnp.float32), key)
        done = jnp.zeros((b,), bool) if eos_token_id is None else \
            (tok == eos_token_id)

        def step(carry, i):
            tok, kc, vc, key, done = carry
            key, sub = jax.random.split(key)
            # the fed token is the (i-1)-th generated one: cache column
            # s0 + i - 1; its POSITION id is per-row (len_i + i - 1) when
            # the batch is ragged
            step_pos = None if lens is None else \
                (lens + (i - 1))[:, None]
            x, kc, vc = fwd(p, tok[:, None], s0 + i - 1, kc, vc,
                            key_valid=key_valid, pos_ids=step_pos)
            nxt = pick(logits_of(p, x[:, 0]).astype(jnp.float32), sub)
            if eos_token_id is not None:
                nxt = jnp.where(done, eos_token_id, nxt)
                done = done | (nxt == eos_token_id)
            return (nxt, kc, vc, key, done), tok

        (last, *_), toks = jax.lax.scan(
            step, (tok, kc, vc, key, done), jnp.arange(1, max_new_tokens))
        return jnp.concatenate([toks.T, last[:, None]], axis=1) \
            if max_new_tokens > 1 else tok[:, None]

    cache_key = (b, s0, max_new_tokens, float(temperature), top_k,
                 eos_token_id, untied, untied_bias, str(compute_dtype),
                 mask is not None, None if top_p is None else float(top_p),
                 cache_dtype,
                 # the Mesh itself (hashable): same-size but different
                 # meshes must not reuse each other's shard_map closure
                 ("tp", tp_mesh) if tp_mesh is not None else None)
    store = model.__dict__.setdefault("_generate_compiled", {})
    if cache_key not in store:
        if tp_mesh is None:
            store[cache_key] = jax.jit(run)
        else:
            from jax.sharding import PartitionSpec as P

            store[cache_key] = _tp_wrap(run, tp_mesh, tp_specs, 3, P())
    if temperature == 0.0:
        key = jax.random.key(0)  # greedy never samples: don't advance the
        # global generator (reproducibility side effect otherwise)
    elif seed is not None:
        key = jax.random.key(seed)
    else:
        from ..core.generator import default_generator

        key = default_generator().split()
    out = store[cache_key](params, ids, key, mask)
    full = jnp.concatenate([ids.astype(out.dtype), out], axis=1)
    return Tensor(full)


def _gpt_speculative(model, draft_model, input_ids, max_new_tokens, k=4,
                     dtype=None, cache_dtype=None, tp_mesh=None,
                     eos_token_id=None):
    """Speculative GREEDY decoding (beyond reference): a small draft model
    proposes k tokens per round; the target verifies all k in ONE forward
    and accepts the longest matching prefix plus its own fix-up token, so
    each round costs k tiny draft steps + one (k+1)-token target step yet
    emits 1..k+1 tokens. Greedy acceptance makes the output equal to the
    target model's own greedy decode whatever the draft quality (up to XLA
    reassociation flipping argmax on exact logit ties — the multi-token
    verify forward and generate()'s single-token steps can round near-ties
    differently; tests pin equality on the test models). The whole loop is
    one jitted lax.while_loop program (trip count is data-dependent:
    better drafts finish in fewer rounds).

    Cache invariant per round: both KV caches hold the accepted prefix
    [0, pos); `cur` is the last accepted token not yet fed. The round feeds
    [cur, p0..p_{k-1}] (target) so stale columns beyond the accepted prefix
    are never read (causal mask) and are overwritten by later rounds.

    Scope: batch 1, greedy only. eos_token_id stops the loop once the
    accepted slice contains eos, filling the tail with eos exactly like
    the dense scan's done-mask — fewer rounds on early termination."""
    import jax
    import jax.numpy as jnp

    cfg, ids, b, s0, T0, untied, untied_bias, params = _decode_setup(
        model, input_ids, max_new_tokens)
    if b != 1:
        raise ValueError(f"speculative decoding is batch-1 (got batch {b}); "
                         "run rows separately, use generate(), or serve "
                         "batches speculatively via inference.serving."
                         "ServingEngine(draft_model=...)")
    if draft_model.cfg.vocab_size != cfg.vocab_size:
        raise ValueError("draft and target must share a vocabulary")
    if not (1 <= k <= 16):
        raise ValueError(f"k must be in [1, 16], got {k}")
    if s0 < 2:
        raise ValueError("speculative decoding needs a prompt of >= 2 tokens")
    _check_decode_config(draft_model.cfg)
    d_cfg = draft_model.cfg
    T = s0 + max_new_tokens + k + 1  # writes can run k past the accepted end
    if T > cfg.max_seq_len or T > d_cfg.max_seq_len:
        raise ValueError(
            f"prompt {s0} + max_new_tokens {max_new_tokens} + draft window "
            f"{k + 1} exceeds a max_seq_len ({cfg.max_seq_len} target, "
            f"{d_cfg.max_seq_len} draft)")
    d_untied, d_untied_bias, params_d = _decode_params(draft_model,
                                                       "the draft model")

    tp_axis, tp_size, tp_specs = None, 1, None
    if tp_mesh is not None:
        # target shards over mp; the (small) draft stays replicated
        tp_axis, tp_size, params, tp_specs = _tp_setup(tp_mesh, cfg, params)
    fwd_t, logits_t, cache_init_t = _decode_fns(cfg, untied, untied_bias,
                                                cache_dtype=cache_dtype,
                                                tp_axis=tp_axis,
                                                tp_size=tp_size)
    fwd_d, logits_d, cache_init_d = _decode_fns(d_cfg, d_untied,
                                                d_untied_bias,
                                                cache_dtype=cache_dtype)
    compute_dtype = _decode_compute_dtype(dtype)

    def run(pt, pd, ids_):
        if compute_dtype is not None:
            cast = lambda p: {n: (v.astype(compute_dtype)
                                  if jnp.issubdtype(v.dtype, jnp.floating)
                                  else v) for n, v in p.items()}
            pt, pd = cast(pt), cast(pd)
        kc_t, vc_t = cache_init_t(1, T, compute_dtype or jnp.float32)
        kc_d, vc_d = cache_init_d(1, T, compute_dtype or jnp.float32)
        # prefill both caches with the prompt MINUS its last token; that
        # last token is `cur` (fed at the head of each round)
        prefix = ids_[:, :s0 - 1]
        _, kc_t, vc_t = fwd_t(pt, prefix, 0, kc_t, vc_t)
        _, kc_d, vc_d = fwd_d(pd, prefix, 0, kc_d, vc_d)
        cur = ids_[:, s0 - 1]                              # [1]
        out_buf = jnp.zeros((1, max_new_tokens + k + 1), jnp.int32)

        eos = -1 if eos_token_id is None else int(eos_token_id)

        def round_body(carry):
            (pos, cur, emitted, out_buf, kc_t, vc_t, kc_d, vc_d, rounds,
             done) = carry
            # --- draft proposes k tokens (k single-token forwards) -------
            props = []
            d_cur = cur
            for j in range(k):
                xd, kc_d, vc_d = fwd_d(pd, d_cur[:, None], pos + j,
                                       kc_d, vc_d)
                d_cur = jnp.argmax(
                    logits_d(pd, xd[:, -1]).astype(jnp.float32),
                    -1).astype(jnp.int32)                  # [1]
                props.append(d_cur)
            # write p_{k-1}'s KV too (logits discarded): when all k
            # proposals are accepted the next round starts PAST this
            # column, and an unwritten (zero) column inside the accepted
            # prefix would poison every later draft query's attention
            _, kc_d, vc_d = fwd_d(pd, d_cur[:, None], pos + k, kc_d, vc_d)
            props_a = jnp.stack(props, axis=1)             # [1, k]
            # --- target verifies in ONE (k+1)-token forward --------------
            seq = jnp.concatenate([cur[:, None], props_a], axis=1)
            xt, kc_t, vc_t = fwd_t(pt, seq, pos, kc_t, vc_t)
            preds = jnp.argmax(
                logits_t(pt, xt).astype(jnp.float32),
                -1).astype(jnp.int32)                      # [1, k+1]
            # longest accepted prefix: p_j must equal the target's argmax
            # after the same prefix (preds[:, j])
            matches = (props_a == preds[:, :k]).astype(jnp.int32)
            m = jnp.cumprod(matches, axis=1).sum(axis=1)[0]  # scalar 0..k
            # emitted this round: p_0..p_{m-1} then the target fix-up
            # preds[m]; tail slots are junk overwritten by later rounds
            j_idx = jnp.arange(k + 1)
            fixup = preds[0, m]
            emit = jnp.where(j_idx < m, jnp.pad(props_a[0], (0, 1)),
                             fixup)                        # [k+1]
            if eos >= 0:
                # dense-generate parity: everything after the first eos in
                # the ACCEPTED slice becomes eos, and the loop stops
                seen = jnp.cumsum((emit == eos) & (j_idx <= m)) > 0
                emit = jnp.where(seen, eos, emit)
                done = done | seen[m]
            out_buf = jax.lax.dynamic_update_slice(out_buf, emit[None],
                                                   (0, emitted))
            return (pos + m + 1, preds[:, m], emitted + m + 1, out_buf,
                    kc_t, vc_t, kc_d, vc_d, rounds + 1, done)

        def cond(carry):
            return (carry[2] < max_new_tokens) & ~carry[-1]

        init = (jnp.int32(s0 - 1), cur, jnp.int32(0), out_buf,
                kc_t, vc_t, kc_d, vc_d, jnp.int32(0),
                jnp.asarray(False))
        (pos, cur, emitted, out_buf, *_, rounds, done) = jax.lax.while_loop(
            cond, round_body, init)
        out = out_buf[:, :max_new_tokens]
        if eos >= 0:
            # early stop leaves the tail unwritten: fill with eos (what the
            # dense scan would have emitted after done)
            out = jnp.where(jnp.arange(max_new_tokens)[None] >= emitted,
                            eos, out)
        return out, rounds

    cache_key = ("spec", b, s0, max_new_tokens, k, untied, untied_bias,
                 d_untied, d_untied_bias, str(compute_dtype), cache_dtype,
                 # value-based draft identity (id() could alias a GC'd
                 # model of a different architecture)
                 d_cfg.num_layers, d_cfg.hidden_size, d_cfg.num_heads,
                 getattr(d_cfg, "num_kv_heads", d_cfg.num_heads),
                 d_cfg.vocab_size, d_cfg.max_seq_len,
                 # the jitted closure also bakes the draft's attention
                 # window and gelu flavor — a second draft sharing the
                 # dims but differing here must NOT reuse the program
                 getattr(d_cfg, "attention_window", None),
                 getattr(d_cfg, "gelu_approx", False), eos_token_id,
                 ("tp", tp_mesh) if tp_mesh is not None else None)
    store = model.__dict__.setdefault("_generate_compiled", {})
    if cache_key not in store:
        if tp_mesh is None:
            store[cache_key] = jax.jit(run)
        else:
            from jax.sharding import PartitionSpec as P

            # run(pt, pd, ids): a bare P() prefix replicates the whole
            # draft-param dict and the ids
            store[cache_key] = _tp_wrap(run, tp_mesh, tp_specs, 2,
                                        (P(), P()))
    out, rounds = store[cache_key](params, params_d, ids)
    full = jnp.concatenate([ids.astype(out.dtype), out], axis=1)
    return Tensor(full), int(rounds)


def _ragged_setup(mask_, b, s0, T):
    """Shared ragged-batch derivation for both decode programs: per-row real
    lengths, the [b, T] key-validity mask (generated columns always valid)
    and the prefill position ids for LEFT-padded prompts."""
    import jax.numpy as jnp

    if mask_ is None:
        return None, None, None
    lens = jnp.sum(mask_, axis=1).astype(jnp.int32)
    key_valid = jnp.concatenate(
        [mask_.astype(bool), jnp.ones((b, T - s0), bool)], axis=1)
    pos_ids = jnp.maximum(jnp.arange(s0)[None, :] - (s0 - lens)[:, None], 0)
    return lens, key_valid, pos_ids


def _left_pad_mask(attention_mask, b, s0):
    """Validate/convert a [b, s0] keep-mask for ragged decode. Rows must be
    LEFT-padded (zeros then ones) so the last column is every row's final
    real token — the position the next-token logits read."""
    if attention_mask is None:
        return None
    import jax.numpy as jnp

    m = attention_mask._data if isinstance(attention_mask, Tensor) else \
        jnp.asarray(np.asarray(attention_mask))
    if m.shape != (b, s0):
        raise ValueError(f"attention_mask shape {tuple(m.shape)} != "
                         f"{(b, s0)}")
    mi = m.astype(jnp.int32)
    host = np.asarray(mi)  # generate() is a host API: masks arrive concrete
    if not np.isin(host, (0, 1)).all():
        raise ValueError("attention_mask must be binary (0 = pad, 1 = "
                         "attend); got other values")
    if not (np.diff(host, axis=1) >= 0).all():
        raise ValueError(
            "attention_mask must be LEFT-padded (0s then 1s per row): "
            "right-padded prompts would put pad tokens at the positions "
            "the decode reads — re-pad with the prompt at the END")
    if not host.any(axis=1).all():
        raise ValueError("attention_mask has an all-pad row")
    return mi


def _gpt_beam_search(model, input_ids, max_new_tokens, num_beams,
                     eos_token_id, length_penalty, dtype=None,
                     attention_mask=None, cache_dtype=None, tp_mesh=None):
    """Beam search over the same fused KV-cache program: prefill once at
    batch b, tile the cache per beam ([L, b*K, H, T, hd]), and lax.scan
    steps that (a) add log-probs, (b) take the joint top-K over K*V
    continuations, (c) reorder the cache by surviving parent beam, and
    (d) record (token, parent) for the reverse-scan backtrace. Finished
    beams (eos) only continue with eos at zero added log-prob. Scores are
    length-normalized by (new_len ** length_penalty) at the final pick."""
    import jax
    import jax.numpy as jnp

    cfg, ids, b, s0, T, untied, untied_bias, params = _decode_setup(
        model, input_ids, max_new_tokens)
    if num_beams < 2:
        raise ValueError("num_beams must be >= 2 for beam search")
    if num_beams > cfg.vocab_size:
        raise ValueError(f"num_beams ({num_beams}) cannot exceed "
                         f"vocab_size ({cfg.vocab_size})")
    L, Hh = cfg.num_layers, cfg.num_heads
    hd = cfg.hidden_size // Hh
    K, V = num_beams, cfg.vocab_size
    tp_axis, tp_size, tp_specs = None, 1, None
    if tp_mesh is not None:
        tp_axis, tp_size, params, tp_specs = _tp_setup(tp_mesh, cfg, params)
    fwd, logits_of, cache_init = _decode_fns(cfg, untied, untied_bias,
                                             cache_dtype=cache_dtype,
                                             tp_axis=tp_axis,
                                             tp_size=tp_size)
    eos = -1 if eos_token_id is None else int(eos_token_id)
    compute_dtype = _decode_compute_dtype(dtype)
    mask = _left_pad_mask(attention_mask, b, s0)

    def run(p, ids_, mask_):
        if compute_dtype is not None:
            # bf16 cache matters MOST here: the cache is K x larger
            p = {k: (v.astype(compute_dtype)
                     if jnp.issubdtype(v.dtype, jnp.floating) else v)
                 for k, v in p.items()}
        kc, vc = cache_init(b, T, compute_dtype or jnp.float32)
        lens, key_valid, pos_ids = _ragged_setup(mask_, b, s0, T)
        x, kc, vc = fwd(p, ids_, 0, kc, vc, key_valid=key_valid,
                        pos_ids=pos_ids)
        logp0 = jax.nn.log_softmax(
            logits_of(p, x[:, -1]).astype(jnp.float32), -1)      # [b, V]
        scores, tok = jax.lax.top_k(logp0, K)                    # [b, K]
        tok = tok.astype(jnp.int32)
        done = tok == eos
        # tile cache per beam: batch-major layout [b*K] = (b0k0, b0k1, ...)
        kc = _cache_map(lambda a: jnp.repeat(a, K, axis=1), kc)
        vc = _cache_map(lambda a: jnp.repeat(a, K, axis=1), vc)
        kv_beam = None if key_valid is None else \
            jnp.repeat(key_valid, K, axis=0)                     # [b*K, T]
        lens_beam = None if lens is None else jnp.repeat(lens, K)
        batch_base = (jnp.arange(b) * K)[:, None]                # [b, 1]

        gen_len = jnp.ones_like(scores)  # per-beam generated length

        def step(carry, i):
            tok, scores, done, gen_len, kc, vc = carry
            step_pos = None if lens_beam is None else \
                (lens_beam + (i - 1))[:, None]
            x, kc, vc = fwd(p, tok.reshape(b * K, 1), s0 + i - 1, kc, vc,
                            key_valid=kv_beam, pos_ids=step_pos)
            logp = jax.nn.log_softmax(
                logits_of(p, x[:, 0]).astype(jnp.float32),
                -1).reshape(b, K, V)
            # finished beams: only eos continues, at no cost
            if eos >= 0:
                frozen = jnp.full((V,), -jnp.inf).at[eos].set(0.0)
                logp = jnp.where(done[:, :, None], frozen[None, None], logp)
            total = scores[:, :, None] + logp                    # [b, K, V]
            scores, sel = jax.lax.top_k(total.reshape(b, K * V), K)
            parent = (sel // V).astype(jnp.int32)                # [b, K]
            tok = (sel % V).astype(jnp.int32)
            parent_done = jnp.take_along_axis(done, parent, axis=1)
            # a beam that was already finished keeps its length; live ones
            # grow to i+1 tokens (GNMT length normalization needs this)
            gen_len = jnp.where(parent_done,
                                jnp.take_along_axis(gen_len, parent, axis=1),
                                i + 1.0) \
                if eos >= 0 else gen_len + 1.0
            done = parent_done | (tok == eos) \
                if eos >= 0 else jnp.zeros_like(tok, bool)
            # reorder beam-expanded cache rows by surviving parent
            rows = (batch_base + parent).reshape(-1)             # [b*K]
            kc = _cache_map(lambda a: a[:, rows], kc)
            vc = _cache_map(lambda a: a[:, rows], vc)
            return (tok, scores, done, gen_len, kc, vc), (tok, parent)

        init_tok, init_scores, init_done = tok, scores, done
        if max_new_tokens == 1:
            best = jnp.argmax(init_scores, -1)
            return jnp.take_along_axis(init_tok, best[:, None], 1), \
                jnp.take_along_axis(init_scores, best[:, None], 1)[:, 0]
        (tok, scores, done, gen_len, _, _), (toks, parents) = jax.lax.scan(
            step, (init_tok, init_scores, init_done, gen_len, kc, vc),
            jnp.arange(1, max_new_tokens))
        # GNMT-style final pick: each beam normalized by ITS generated
        # length (eos-frozen beams keep their shorter length)
        norm = scores / (gen_len ** length_penalty)
        best = jnp.argmax(norm, -1)                              # [b]
        final_score = jnp.take_along_axis(scores, best[:, None], 1)[:, 0]

        # backtrace: walk parents from the last step down to the prefill pick
        def back(beam, t):
            tk = jnp.take_along_axis(toks[t], beam[:, None], 1)[:, 0]
            beam = jnp.take_along_axis(parents[t], beam[:, None], 1)[:, 0]
            return beam, tk

        beam, rev = jax.lax.scan(back, best,
                                 jnp.arange(max_new_tokens - 2, -1, -1))
        first = jnp.take_along_axis(init_tok, beam[:, None], 1)  # [b, 1]
        seq = jnp.concatenate([first, rev.T[:, ::-1]], axis=1)
        return seq, final_score

    cache_key = ("beam", b, s0, max_new_tokens, K, eos, untied, untied_bias,
                 float(length_penalty), str(compute_dtype), mask is not None,
                 cache_dtype,
                 ("tp", tp_mesh) if tp_mesh is not None else None)
    store = model.__dict__.setdefault("_generate_compiled", {})
    if cache_key not in store:
        if tp_mesh is None:
            store[cache_key] = jax.jit(run)
        else:
            from jax.sharding import PartitionSpec as P

            store[cache_key] = _tp_wrap(run, tp_mesh, tp_specs, 2,
                                        (P(), P()))
    out, score = store[cache_key](params, ids, mask)
    full = jnp.concatenate([ids.astype(out.dtype), out], axis=1)
    return Tensor(full), Tensor(score)


# ---------------------------------------------------------------------------
# Pipeline-parallel decomposition (distributed.pipeline.PipelineTrainer model
# protocol: pre / homogeneous stages / post+loss).
# ---------------------------------------------------------------------------

class GPTEmbed(nn.Layer):
    """First pipeline section: token + position embedding (shares the parent
    model's wte/wpe parameter tensors)."""

    def __init__(self, wte, wpe, dropout):
        super().__init__()
        self.wte = wte
        self.wpe = wpe
        self.drop = nn.Dropout(dropout)

    def forward(self, input_ids):
        from ..tensor.creation import arange

        s = input_ids.shape[-1]
        with jax.named_scope("embed"):
            pos = arange(s, dtype="int32")  # int32: x64 is off on TPU/CPU — an "int64" request
            # is truncated with a per-call UserWarning (caught by the analysis trace-warnings gate)
            return self.drop(self.wte(input_ids) + self.wpe(pos))


class GPTStage(nn.Layer):
    """One pipeline stage: a run of consecutive GPTBlocks (shares the parent's
    block sublayers, so parameters stay the same Tensor objects)."""

    def __init__(self, blocks):
        super().__init__()
        self.blocks = nn.LayerList(blocks)

    def forward(self, x):
        for blk in self.blocks:
            x = blk(x)
        return x


class GPTHeadLoss(nn.Layer):
    """Last pipeline section: final LayerNorm + LM head + cross-entropy.

    The head is UNTIED here (initialized from a copy of wte): pipeline splits
    put the embedding on stage 0 and the head on the last stage — the megatron/
    reference convention where tied weights need an extra embedding grad
    all-reduce between first and last stage; we untie instead and document it.
    """

    def __init__(self, ln_f, wte_weight):
        super().__init__()
        self.ln_f = ln_f
        v, h = wte_weight.shape
        self.head = nn.Linear(h, v, bias_attr=False)
        self.head.weight._data = wte_weight._data.T.copy()

    def forward(self, h, labels):
        with jax.named_scope("head"):
            h = self.ln_f(h)
        logits = self.head(h)
        b, s, v = logits.shape
        with jax.named_scope("loss"):
            return F.cross_entropy(logits.reshape([b * s, v]), labels.reshape([b * s]))


def _gpt_pipeline_split(model, pp_degree):
    """Split a GPTForCausalLM into (pre, stages, post_loss) for PipelineTrainer.

    Stage layers share the model's block parameter tensors; each stage gets
    num_layers // pp_degree consecutive blocks (must divide evenly so stages
    are structurally identical — the stacked-params representation needs it).
    """
    cfg = model.cfg
    if cfg.num_layers % pp_degree != 0:
        raise ValueError(f"num_layers={cfg.num_layers} not divisible by "
                         f"pp_degree={pp_degree}")
    per = cfg.num_layers // pp_degree
    gpt = model.gpt
    pre = GPTEmbed(gpt.wte, gpt.wpe, cfg.dropout)
    stages = [GPTStage(list(gpt.blocks)[i * per:(i + 1) * per])
              for i in range(pp_degree)]
    post = GPTHeadLoss(gpt.ln_f, gpt.wte.weight)
    # expose the untied head on the model so its forward path and state_dict
    # reflect pipelined training after sync_to_layer
    model.lm_head = post.head
    return pre, stages, post


def gpt2_small(**kw):
    return GPTForCausalLM(GPTConfig.small())


def gpt2_medium(**kw):
    return GPTForCausalLM(GPTConfig.medium())


class GPTDecodeModel(_decode_model.DecodeModel):
    """The gpt family's DecodeModel adapter (serving/decode_model.py):
    the serving tier's ONLY doorway into this module — every method
    delegates to the same decode helpers generate()/ServingEngine
    historically used, so engine outputs through the registry are
    byte-identical to the direct-import era."""

    name = "gpt"

    def check_config(self, cfg):
        _check_decode_config(cfg)

    def compute_dtype(self, dtype):
        return _decode_compute_dtype(dtype)

    def extract_params(self, model, who):
        untied, untied_bias, params = _decode_params(model, who)
        return params, (untied, untied_bias)

    def decode_fns(self, cfg, aux, cache_dtype=None, tp_axis=None,
                   tp_size=1):
        untied, untied_bias = aux
        return _decode_fns(cfg, untied, untied_bias,
                           cache_dtype=cache_dtype, tp_axis=tp_axis,
                           tp_size=tp_size)

    def kv_read_tile(self, cfg, side, dtype, tp_size=1):
        import jax

        if isinstance(side, tuple):
            return None
        rows, hd = side.shape[1], side.shape[4]
        q = jax.ShapeDtypeStruct((rows, cfg.num_heads // tp_size, 1, hd),
                                 dtype)
        return _live_tile(side, q, np.zeros((rows,), np.int32),
                          getattr(cfg, "attention_window", None), None)

    def kv_tiles_written(self, cfg, side, dtype, tp_size=1):
        # `block` hands the store to the kernel wherever it takes the
        # kernel: a tile of keys and one of values, every layer
        return 2 * cfg.num_layers if self.kv_read_tile(
            cfg, side, dtype, tp_size) else 0

    def tp_setup(self, tp_mesh, cfg, params):
        return _tp_setup(tp_mesh, cfg, params)

    def tp_wrap(self, run, tp_mesh, tp_specs, n_extra_in, out_specs,
                in_specs=None, donate=()):
        return _tp_wrap(run, tp_mesh, tp_specs, n_extra_in, out_specs,
                        in_specs=in_specs, donate=donate)

    def cache_spec(self, cfg):
        KVh = getattr(cfg, "num_kv_heads", None) or cfg.num_heads
        hd = cfg.hidden_size // cfg.num_heads
        side = {"kind": "kv", "slot_axis": 1, "layers": cfg.num_layers}
        return {"kind": "kv_pair",
                "layout": "[L, B, KVh, T, hd]",
                "axes": {"L": cfg.num_layers, "KVh": KVh,
                         "T": cfg.max_seq_len, "hd": hd},
                # the pair as state kinds (serving/decode_model.py): two
                # leaves that grow with the context, slots on axis 1 (a
                # quantized side's values and scales both lie under it)
                "leaves": [dict(side, path=(0,)), dict(side, path=(1,))],
                "quantized": "per-side (values, scales) tuple when the "
                             "engine's cache_dtype is int8/fp8"}

    # multi-LoRA batched decode: the four adapter sites mirror block()'s
    # four matmuls. Every slot carries all four kinds (absent sites are
    # exact zeros) so hot-loading an adapter into a freed slot is one
    # uniform .at[slot].set — no per-site-set program variants.
    _LORA_SITES = {"attn.qkv": "qkv", "attn.proj": "proj",
                   "mlp.fc1": "fc1", "mlp.fc2": "fc2"}

    def _lora_dims(self, cfg):
        Hh = cfg.num_heads
        KVh = getattr(cfg, "num_kv_heads", None) or Hh
        hd = cfg.hidden_size // Hh
        h, inner = cfg.hidden_size, cfg.intermediate_size
        return {"qkv": (h, (Hh + 2 * KVh) * hd), "proj": (h, h),
                "fc1": (h, inner), "fc2": (inner, h)}

    def lora_init(self, cfg, n_slots, rank, dtype=None):
        import jax.numpy as jnp

        dt = dtype or jnp.float32
        L = cfg.num_layers
        pack = {"scale": jnp.zeros((n_slots,), jnp.float32)}
        for kind, (din, dout) in self._lora_dims(cfg).items():
            pack[kind] = {
                "A": jnp.zeros((n_slots, L, din, rank), dt),
                "B": jnp.zeros((n_slots, L, rank, dout), dt)}
        return pack

    def lora_pack(self, cfg, exported, rank):
        L = cfg.num_layers
        r = int(exported["rank"])
        if r > rank:
            raise ValueError(
                f"adapter rank {r} exceeds the engine's lora_rank={rank}; "
                "rebuild the engine with a larger lora_rank")
        dims = self._lora_dims(cfg)
        slot = {"scale": float(exported["scaling"])}
        for kind, (din, dout) in dims.items():
            slot[kind] = {"A": np.zeros((L, din, rank), np.float32),
                          "B": np.zeros((L, rank, dout), np.float32)}
        pat = re.compile(r"(?:^|\.)blocks\.(\d+)\.(attn\.qkv|attn\.proj|"
                         r"mlp\.fc1|mlp\.fc2)$")
        for qual, fac in exported["factors"].items():
            m = pat.search(qual)
            if m is None:
                raise ValueError(
                    f"adapter site {qual!r} has no batched-decode "
                    "injection point (gpt serves LoRA on attn.qkv/"
                    "attn.proj/mlp.fc1/mlp.fc2 only) — merge_lora this "
                    "adapter and serve it dense instead")
            i, kind = int(m.group(1)), self._LORA_SITES[m.group(2)]
            A, B = np.asarray(fac["A"]), np.asarray(fac["B"])
            din, dout = dims[kind]
            if A.shape != (din, r) or B.shape != (r, dout):
                raise ValueError(
                    f"adapter site {qual!r}: factors {A.shape}/{B.shape} "
                    f"do not match the config ({(din, r)}/{(r, dout)})")
            if not 0 <= i < L:
                raise ValueError(f"adapter site {qual!r}: layer {i} out of "
                                 f"range for num_layers={L}")
            slot[kind]["A"][i, :, :r] = A
            slot[kind]["B"][i, :r, :] = B
        return slot

    def matches(self, model):
        return isinstance(model, GPTForCausalLM)


_decode_model.register_decode_model(GPTDecodeModel())
