"""Solar-Open-2 style hybrid decoder: three KDA linear-attention layers to one
gated NoPE GQA layer, a sigmoid-routed expert layer with a shared expert in
every block (`model_type` solar_open2; KDA: arXiv:2510.26692).

One pure-function block over a parameter dict and an explicit state argument
(`_decode_fns.fwd`): the whole-sequence form (prefill, and the Layer's
`forward`) and the one-token step are the same function at t > 1 and t = 1.
The layer pattern comes from the config (`gqa_layers`), nothing is a flag.

    x = x + Mix_l(RMSNorm(x));  x = x + MoE_l(RMSNorm(x))

(the residual stream x is float32 in every compute dtype; what a layer reads
of it is rounded to the compute dtype, the router reads it unrounded)

Mix_l is `_gqa` where l is in `cfg.gqa_layers` (softmax attention, grouped KV
heads, NO positional term, an elementwise sigmoid output gate) and `_kda`
otherwise (short causal convolutions, L2-normalised q and k, a decay for every
key channel through a low-rank projection, beta in (0, 2), a float32 state
[H, dk, dv] a sequence: ops/kda.py). MoE_l routes over all `n_routed_experts`
and computes the part of the result the HELD experts give
(`cfg.held_experts = (first, count)`; default all) plus the shared expert
(distributed/moe.py `moe_dropless_layer`). No bias anywhere.

State, as the serving engine sees it (`SolarOpen2DecodeModel.cache_spec`): a
pair of trees. The first holds what grows with the context, kind `kv`: "k" and
"v" [Lg, B, T, KVh, hd], written at `pos` (T before the heads: a token's keys
of all heads are one contiguous block, so the step's per-row write is a
scatter the chip does in place; with the heads before T the compiled step
relaid the whole cache out and back around it). The second holds what has a fixed
size and is replaced every step: kind `recurrent`, one float32 [B, H, dk, dv]
a KDA layer, and kind `conv`, the last `conv_size - 1` inputs of the three
convolutions, [B, conv_size - 1, 2 H dk + H dv] a KDA layer. A whole-sequence
call is told the sequence's `valid_len`: positions from there on leave the
fixed-size state exactly as it was (prefill pads prompts to a bucket).

Forward only: the expert loop's trip count is data (distributed/moe.py).
"""
import math

import jax

from ..serving import decode_model as _decode_model
from ._functional_lm import (STEP_COUNTS, FunctionalCausalLM, count_vector,
                             dot as _dot, dot32 as _dot32,
                             einsum32 as _einsum32, rms as _rms)

_Q_BLOCK = 256      # queries a block of the whole-sequence softmax attention


class SolarOpen2Config:
    """Keys as the published config.json names them where it has one.
    `held_experts=(first, count)`: the routed experts this model holds of
    `n_routed_experts` (one chip's share of an expert-parallel deployment);
    `kda_rank`: the low-rank width of the decay and gate projections
    (`kda_use_full_proj` false), by default the head size."""

    def __init__(self, vocab_size=196608, hidden_size=4096,
                 num_hidden_layers=48, num_attention_heads=64,
                 num_key_value_heads=8, head_dim=128, gqa_layers=None,
                 kda_num_heads=64, kda_head_dim=128, short_conv_kernel_size=4,
                 kda_rank=None, n_routed_experts=320, held_experts=None,
                 num_experts_per_tok=8, moe_intermediate_size=1280,
                 n_shared_experts=1, norm_topk_prob=True,
                 routed_scaling_factor=1.0, rms_norm_eps=1e-5,
                 max_seq_len=4096, init_std=0.02):
        self.vocab_size = int(vocab_size)
        self.hidden_size = int(hidden_size)
        self.num_layers = int(num_hidden_layers)
        self.num_heads = int(num_attention_heads)
        self.num_kv_heads = int(num_key_value_heads)
        self.head_dim = int(head_dim)
        if gqa_layers is None:
            gqa_layers = range(0, self.num_layers, 4)
        self.gqa_layers = tuple(int(l) for l in gqa_layers
                                if int(l) < self.num_layers)
        self.kda_num_heads = int(kda_num_heads)
        self.kda_head_dim = int(kda_head_dim)
        self.conv_size = int(short_conv_kernel_size)
        self.kda_rank = int(kda_rank or kda_head_dim)
        self.n_routed_experts = int(n_routed_experts)
        first, count = (0, self.n_routed_experts) if held_experts is None \
            else (int(held_experts[0]), int(held_experts[1]))
        if not (0 <= first and 1 <= count
                and first + count <= self.n_routed_experts):
            raise ValueError(f"held_experts={held_experts} is no range of "
                             f"{self.n_routed_experts} experts")
        self.held_experts = (first, count)
        self.num_experts_per_tok = int(num_experts_per_tok)
        self.moe_intermediate_size = int(moe_intermediate_size)
        self.n_shared_experts = int(n_shared_experts)
        self.norm_topk_prob = bool(norm_topk_prob)
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.rms_norm_eps = float(rms_norm_eps)
        self.max_seq_len = int(max_seq_len)
        self.init_std = float(init_std)
        if self.num_heads % self.num_kv_heads:
            raise ValueError("num_attention_heads must be a multiple of "
                             "num_key_value_heads")

    @property
    def kda_layers(self):
        return tuple(l for l in range(self.num_layers)
                     if l not in self.gqa_layers)

    @property
    def conv_channels(self):
        return 3 * self.kda_num_heads * self.kda_head_dim


def param_shapes(cfg):
    """{name: (shape, kind)} of every parameter. Kinds: "matrix", "gain"
    (ones), "zero" (the router's selection bias), "conv" (taps, [conv_size,
    channels]), "a_log" and "dt_bias" (the decay's two vectors)."""
    d, H, hd, KV = cfg.hidden_size, cfg.num_heads, cfg.head_dim, \
        cfg.num_kv_heads
    Hk, dk, r = cfg.kda_num_heads, cfg.kda_head_dim, cfg.kda_rank
    f, E, K = cfg.moe_intermediate_size, cfg.n_routed_experts, cfg.conv_size
    count = cfg.held_experts[1]
    fs = f * cfg.n_shared_experts
    out = {"embed.weight": ((cfg.vocab_size, d), "matrix")}
    for l in range(cfg.num_layers):
        pre = f"layers.{l}."
        out[pre + "norm1.weight"] = ((d,), "gain")
        if l in cfg.gqa_layers:
            a = pre + "attn."
            out.update({a + "q.weight": ((d, H * hd), "matrix"),
                        a + "k.weight": ((d, KV * hd), "matrix"),
                        a + "v.weight": ((d, KV * hd), "matrix"),
                        a + "g.weight": ((d, H * hd), "matrix"),
                        a + "o.weight": ((H * hd, d), "matrix")})
        else:
            a = pre + "kda."
            out.update({a + "q.weight": ((d, Hk * dk), "matrix"),
                        a + "k.weight": ((d, Hk * dk), "matrix"),
                        a + "v.weight": ((d, Hk * dk), "matrix"),
                        a + "q_conv.weight": ((K, Hk * dk), "conv"),
                        a + "k_conv.weight": ((K, Hk * dk), "conv"),
                        a + "v_conv.weight": ((K, Hk * dk), "conv"),
                        a + "b.weight": ((d, Hk), "matrix"),
                        a + "f_down.weight": ((d, r), "matrix"),
                        a + "f_up.weight": ((r, Hk * dk), "matrix"),
                        a + "A_log": ((Hk,), "a_log"),
                        a + "dt_bias": ((Hk * dk,), "dt_bias"),
                        a + "g_down.weight": ((d, r), "matrix"),
                        a + "g_up.weight": ((r, Hk * dk), "matrix"),
                        a + "o_norm.weight": ((dk,), "gain"),
                        a + "o.weight": ((Hk * dk, d), "matrix")})
        out[pre + "norm2.weight"] = ((d,), "gain")
        m = pre + "moe."
        out.update({m + "router.weight": ((d, E), "matrix"),
                    m + "router.bias": ((E,), "zero"),
                    m + "experts.gate": ((count, d, f), "matrix"),
                    m + "experts.up": ((count, d, f), "matrix"),
                    m + "experts.down": ((count, f, d), "matrix")})
        if fs:
            out.update({m + "shared.gate.weight": ((d, fs), "matrix"),
                        m + "shared.up.weight": ((d, fs), "matrix"),
                        m + "shared.down.weight": ((fs, d), "matrix")})
    out["norm.weight"] = ((d,), "gain")
    out["lm_head.weight"] = ((d, cfg.vocab_size), "matrix")
    return out


def _default_init(cfg):
    """normal(0, init_std) matrices, unit gains, a zero selection bias,
    taps uniform in +-1/sqrt(conv_size), A = exp(A_log) uniform in 1..16 and
    a dt_bias whose softplus is log-uniform in 0.001..0.1 (the ranges the
    `fla` layers start from); keys from the framework's generator."""
    import jax.numpy as jnp

    from ..core import generator as _generator

    def init(name, shape, kind, dtype):
        k = _generator.get_rng_key()
        if kind == "gain":
            x = jnp.ones(shape, jnp.float32)
        elif kind == "zero":
            x = jnp.zeros(shape, jnp.float32)
        elif kind == "conv":
            lim = 1.0 / math.sqrt(cfg.conv_size)
            x = jax.random.uniform(k, shape, jnp.float32, -lim, lim)
        elif kind == "a_log":
            x = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
        elif kind == "dt_bias":
            dt = jnp.exp(jax.random.uniform(
                k, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
            x = dt + jnp.log(-jnp.expm1(-dt))       # softplus^-1(dt)
        else:
            x = cfg.init_std * jax.random.normal(k, shape, jnp.float32)
        return x.astype(dtype)

    return init


# -- the pure functions --------------------------------------------------------

def _attend(q, keys, vals, limit, scale):
    """q [B, t, H, hd]; keys, vals [B, S, KVh, hd]; limit [B or 1, t]: query
    i of row b sees columns 0..limit[b, i]. Softmax in float32, queries in
    blocks of _Q_BLOCK so that no [t, S] score tensor of all heads is held
    at once. Returns [B, t, H, hd] in q's dtype."""
    import jax.numpy as jnp

    B, t, H, hd = q.shape
    S, KV = keys.shape[1], keys.shape[2]
    G = H // KV
    qg = q.reshape(B, t, KV, G, hd)
    cols = jnp.arange(S, dtype=jnp.int32)

    def block(qb, lim):                     # qb [B, c, KV, G, hd], lim [., c]
        s = _einsum32("bqkgd,bskd->bkgqs", qb, keys) * scale
        seen = cols[None, None, :] <= lim[:, :, None]       # [., c, S]
        s = jnp.where(seen[:, None, None], s, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1).astype(vals.dtype)
        return _einsum32("bkgqs,bskd->bqkgd", pr, vals).astype(q.dtype)

    if t <= _Q_BLOCK:
        return block(qg, limit).reshape(B, t, H, hd)
    n = -(-t // _Q_BLOCK)
    pad = n * _Q_BLOCK - t
    limit = jnp.broadcast_to(limit, (limit.shape[0], t))
    if pad:     # a padded query sees column 0 and is cut off again
        qg = jnp.pad(qg, [(0, 0), (0, pad), (0, 0), (0, 0), (0, 0)])
        limit = jnp.pad(limit, [(0, 0), (0, pad)])
    qb = jnp.moveaxis(qg.reshape(B, n, _Q_BLOCK, KV, G, hd), 1, 0)
    lb = jnp.moveaxis(limit.reshape(-1, n, _Q_BLOCK), 1, 0)
    o = jax.lax.map(lambda a: block(*a), (qb, lb))          # [n, B, c, ...]
    return jnp.moveaxis(o, 0, 1).reshape(B, n * _Q_BLOCK, H, hd)[:, :t]


def _gqa(p, pre, cfg, h, K, V, gi, pos):
    """The gated NoPE GQA layer. h [B, t, d]; K, V [Lg, B, T, KVh, hd], this
    layer's index gi; pos: a [B] vector (one token a row, each at its own
    column), or a scalar (the whole batch writes columns pos..pos + t).
    Returns (y, K, V)."""
    import jax.numpy as jnp

    B, t, _ = h.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    T = K.shape[2]
    with jax.named_scope("proj"):
        q = _dot(h, p[pre + "q.weight"]).reshape(B, t, H, hd)
        k = _dot(h, p[pre + "k.weight"]).reshape(B, t, KV, hd).astype(K.dtype)
        v = _dot(h, p[pre + "v.weight"]).reshape(B, t, KV, hd).astype(V.dtype)
    with jax.named_scope("cache/store"):
        if jnp.ndim(pos) == 1:
            at = jnp.clip(pos, 0, T - 1)
            rows = jnp.arange(B)
            K = K.at[gi, rows, at].set(k[:, 0])
            V = V.at[gi, rows, at].set(v[:, 0])
        else:
            K = jax.lax.dynamic_update_slice(K, k[None], (gi, 0, pos, 0, 0))
            V = jax.lax.dynamic_update_slice(V, v[None], (gi, 0, pos, 0, 0))
    with jax.named_scope("core"):
        if jnp.ndim(pos) == 1:
            keys, vals, limit = K[gi], V[gi], at[:, None]
        else:
            steps = jnp.arange(t, dtype=jnp.int32)[None, :]
            if isinstance(pos, int) and pos == 0:
                # a sequence from its start attends to itself alone
                keys, vals, limit = k, v, steps
            else:
                keys, vals, limit = K[gi], V[gi], pos + steps
        o = _attend(q, keys, vals, limit, 1.0 / math.sqrt(hd))
    with jax.named_scope("proj"):
        gate = jax.nn.sigmoid(_dot32(h, p[pre + "g.weight"]))
        o = (gate * o.reshape(B, t, H * hd).astype(jnp.float32)).astype(
            h.dtype)
        return _dot32(o, p[pre + "o.weight"]), K, V


def _kda(p, pre, cfg, h, S, conv, valid_len):
    """The KDA layer. h [B, t, d]; S [B, H, dk, dv] float32; conv [B,
    conv_size - 1, 3 H dk]: the inputs of the three convolutions before this
    call. t = 1 takes the recurrence, t > 1 the chunked form, with positions
    from `valid_len` on (None: t) leaving S and conv as they were.
    Returns (y, S, conv)."""
    import jax.numpy as jnp

    from ..ops import kda as _kda_ops

    f32 = jnp.float32
    B, t, _ = h.shape
    H, dk, K = cfg.kda_num_heads, cfg.kda_head_dim, cfg.conv_size
    with jax.named_scope("proj"):
        proj = jnp.concatenate([_dot(h, p[pre + n + ".weight"])
                                for n in ("q", "k", "v")], axis=-1)
    with jax.named_scope("conv"):
        ext = jnp.concatenate([conv.astype(proj.dtype), proj], axis=1)
        taps = jnp.concatenate([p[pre + n + "_conv.weight"]
                                for n in ("q", "k", "v")],
                               axis=-1).astype(f32)
        mixed = sum(taps[j] * ext[:, j:j + t].astype(f32) for j in range(K))
        mixed = jax.nn.silu(mixed)                           # [B, t, 3 H dk]
        if t == 1:
            conv = ext[:, 1:].astype(conv.dtype)
        else:
            n = t if valid_len is None else valid_len
            conv = jax.lax.dynamic_slice_in_dim(
                ext, n, K - 1, axis=1).astype(conv.dtype)
        q, k, v = (x.reshape(B, t, H, dk)
                   for x in jnp.split(mixed, 3, axis=-1))

    def l2(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    with jax.named_scope("proj"):
        q, k = l2(q), l2(k)
        beta = 2.0 * jax.nn.sigmoid(_dot32(h, p[pre + "b.weight"]))  # [B,t,H]
        f = _dot32(_dot(h, p[pre + "f_down.weight"]),
                   p[pre + "f_up.weight"]) + p[pre + "dt_bias"].astype(f32)
        g = -jnp.exp(p[pre + "A_log"].astype(f32))[:, None] \
            * jax.nn.softplus(f).reshape(B, t, H, dk)
    scale = 1.0 / math.sqrt(dk)
    with jax.named_scope("state"):
        if t == 1:
            o, S = _kda_ops.recurrent_step(S, q[:, 0], k[:, 0], v[:, 0],
                                           g[:, 0], beta[:, 0], scale)
            o = o[:, None]
        else:
            if valid_len is not None:
                live = (jnp.arange(t) < valid_len)[None, :, None]
                g = jnp.where(live[..., None], g, 0.0)
                beta = jnp.where(live, beta, 0.0)
            o, S = _kda_ops.chunked(S, q, k, v, g, beta, scale)
    with jax.named_scope("proj"):
        gate = jax.nn.sigmoid(_dot32(
            _dot(h, p[pre + "g_down.weight"]),
            p[pre + "g_up.weight"])).reshape(B, t, H, dk)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                              + cfg.rms_norm_eps)
        o = o * p[pre + "o_norm.weight"].astype(f32) * gate
        y = _dot32(o.reshape(B, t, H * dk).astype(h.dtype),
                   p[pre + "o.weight"])
    return y, S, conv


def _moe(p, pre, cfg, h, h32):
    """The expert layer over h [B, t, d] (h32: the same before it was rounded
    to the compute dtype, for the router): (y float32, counts)."""
    from ..distributed import moe as _moe_ops

    B, t, d = h.shape
    shared = None
    if cfg.n_shared_experts:
        shared = tuple(p[pre + f"shared.{n}.weight"]
                       for n in ("gate", "up", "down"))
    y, counts = _moe_ops.moe_dropless_layer(
        h.reshape(B * t, d), p[pre + "router.weight"], p[pre + "router.bias"],
        p[pre + "experts.gate"], p[pre + "experts.up"],
        p[pre + "experts.down"], cfg.num_experts_per_tok, shared=shared,
        held=cfg.held_experts, normalize=cfg.norm_topk_prob,
        scale=cfg.routed_scaling_factor, router_x=h32.reshape(B * t, d))
    return y.reshape(B, t, d), counts


def _decode_fns(cfg):
    """(fwd, logits_of, cache_init): the functions the Layer's forward and
    the serving engine's programs are made of."""
    import jax.numpy as jnp

    Lg, Lk = len(cfg.gqa_layers), len(cfg.kda_layers)
    H, dk = cfg.kda_num_heads, cfg.kda_head_dim

    def cache_init(b, T, dt):
        kv = (max(Lg, 1), b, T, cfg.num_kv_heads, cfg.head_dim)
        return ({"k": jnp.zeros(kv, dt), "v": jnp.zeros(kv, dt)},
                {"recurrent": tuple(jnp.zeros((b, H, dk, dk), jnp.float32)
                                    for _ in range(Lk)),
                 "conv": tuple(jnp.zeros((b, cfg.conv_size - 1,
                                          cfg.conv_channels), dt)
                               for _ in range(Lk))})

    def fwd(p, toks, pos, kv, fixed, valid_len=None, counts=False):
        """toks [B, t]; pos: the columns the tokens are written at (a [B]
        vector with t = 1, else a scalar); kv, fixed: the two trees of
        `cache_init`; valid_len: how many of the t positions are the
        sequence's own (None: all). Returns (x [B, t, d] float32 before the final
        norm, kv, fixed) and, with `counts`, an int32 vector of STEP_COUNTS."""
        # the residual stream is float32 whatever the compute dtype: the
        # layers' inputs are rounded to it, their sum is not, and the
        # router reads the normalised stream before the rounding
        cdt = p["embed.weight"].dtype
        with jax.named_scope("embed"):
            x = p["embed.weight"][toks].astype(jnp.float32)
        K, V = kv["k"], kv["v"]
        S, conv = list(fixed["recurrent"]), list(fixed["conv"])
        total = jnp.zeros((len(STEP_COUNTS),), jnp.int32)
        gi = ki = 0
        for l in range(cfg.num_layers):
            pre = f"layers.{l}."
            # a layer's norm and residual sum lie under the layer's word
            with jax.named_scope("attn" if l in cfg.gqa_layers else "kda"):
                h = _rms(x, p[pre + "norm1.weight"],
                         cfg.rms_norm_eps).astype(cdt)
                if l in cfg.gqa_layers:
                    y, K, V = _gqa(p, pre + "attn.", cfg, h, K, V, gi, pos)
                    gi += 1
                else:
                    y, S[ki], conv[ki] = _kda(p, pre + "kda.", cfg, h,
                                              S[ki], conv[ki], valid_len)
                    ki += 1
                x = x + y
            with jax.named_scope("moe"):
                h32 = _rms(x, p[pre + "norm2.weight"], cfg.rms_norm_eps)
            y, c = _moe(p, pre + "moe.", cfg, h32.astype(cdt), h32)
            x = x + y
            total = total + count_vector(c)
        out = (x, {"k": K, "v": V},
               {"recurrent": tuple(S), "conv": tuple(conv)})
        return out + (total,) if counts else out

    def logits_of(p, x):
        w = p["lm_head.weight"]
        with jax.named_scope("head"):
            return _dot32(_rms(x, p["norm.weight"],
                               cfg.rms_norm_eps).astype(w.dtype), w)

    return fwd, logits_of, cache_init


class SolarOpen2ForCausalLM(FunctionalCausalLM):
    """The decoder with its untied head (models/_functional_lm.py has the
    constructor's and `forward`'s contract)."""

    param_shapes = staticmethod(param_shapes)
    default_init = staticmethod(_default_init)
    decode_fns = staticmethod(_decode_fns)


class SolarOpen2DecodeModel(_decode_model.DecodeModel):
    """The family's DecodeModel adapter. Its cache is a described tree of
    state kinds (`cache_spec`); its whole-sequence call takes `valid_len`
    and its decode step returns STEP_COUNTS beside the tokens."""

    name = "solar_open2"
    step_counts = STEP_COUNTS
    not_served = {
        "paged_kv": "it keeps fixed-size state (recurrent, conv) beside "
                    "its keys and values; a paged pool holds `kv` pages "
                    "only",
        "draft_model": "a speculative round would have to take back the "
                       "state its rejected tokens changed",
        "tp_mesh": "its experts and state are not sharded over 'mp'",
        "lora": "no LoRA sites",
        "cache_dtype": "its recurrent state is float32 by the "
                       "configuration"}

    def check_config(self, cfg):
        if not isinstance(cfg, SolarOpen2Config):
            raise ValueError("the solar_open2 decode model serves "
                             "SolarOpen2Config models")

    def compute_dtype(self, dtype):
        from .gpt import _decode_compute_dtype

        return _decode_compute_dtype(dtype)

    def extract_params(self, model, who):
        return {n: p._data for n, p in model.named_parameters()}, None

    def decode_fns(self, cfg, aux, cache_dtype=None, tp_axis=None,
                   tp_size=1):
        if cache_dtype is not None or tp_axis is not None:
            raise ValueError(
                "decode model 'solar_open2' serves neither a quantized "
                "cache (cache_dtype=) nor tensor-parallel (tp_mesh=): its "
                "recurrent state is float32 by the configuration and its "
                "experts are not sharded over 'mp'")
        return _decode_fns(cfg)

    def cache_spec(self, cfg):
        Lg, Lk = len(cfg.gqa_layers), len(cfg.kda_layers)
        kv = {"kind": "kv", "slot_axis": 1, "layers": max(Lg, 1),
              "layout": "[L, B, T, KVh, hd]"}
        leaves = [dict(kv, path=(0, "k")), dict(kv, path=(0, "v"))]
        for i in range(Lk):
            leaves.append({"path": (1, "recurrent", i), "kind": "recurrent",
                           "slot_axis": 0, "layers": 1,
                           "layout": "[B, H, dk, dv] float32"})
            leaves.append({"path": (1, "conv", i), "kind": "conv",
                           "slot_axis": 0, "layers": 1,
                           "layout": "[B, conv_size - 1, 3 H dk]"})
        return {"kind": "state_tree", "leaves": leaves,
                "axes": {"Lg": Lg, "Lk": Lk, "KVh": cfg.num_kv_heads,
                         "T": cfg.max_seq_len, "hd": cfg.head_dim,
                         "H": cfg.kda_num_heads, "dk": cfg.kda_head_dim},
                "quantized": None}

    def matches(self, model):
        return isinstance(model, SolarOpen2ForCausalLM)


_decode_model.register_decode_model(SolarOpen2DecodeModel())
