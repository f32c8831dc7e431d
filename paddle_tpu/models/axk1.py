"""A.X-K1 style decoder (`model_type` axk1): multi-head latent attention with a
compressed cache and YaRN rotary, `first_k_dense_replace` leading dense layers,
then sigmoid-routed expert layers with a shared expert (DeepSeek-V3's published
form, whose config keys this family repeats).

Written as models/solar_open2.py is: one pure-function block over a parameter
dict and an explicit state argument (`_decode_fns.fwd`), and a `DecodeModel`
adapter. RMS norm (eps from the config), no bias anywhere, an untied head:

    x = x + Attn_l(RMSNorm(x));  x = x + FFN_l(RMSNorm(x))

(the residual stream x is float32 in every compute dtype; what a layer reads
of it is rounded to the compute dtype, the router reads it unrounded)

Attn_l, with h the normed stream, H heads, r = `kv_lora_rank`, dn / dr / dv =
`qk_nope_head_dim` / `qk_rope_head_dim` / `v_head_dim`:

    c_q = RMSNorm(W_qa h);  [q_nope | q_rope] = W_qb c_q   (H x (dn + dr))
    [c | k_r] = W_kva h  (r + dr);  c_kv = RMSNorm(c);  k_rope = RoPE(k_r, pos)
    q_rope = RoPE(q_rope, pos)          (ops/rope.py: YaRN, rotate-half)

**The cache holds `[c_kv | k_rope]`: r + dr values a token a layer, one row
for all heads.** Two forms of the same attention over it, picked by `t`:

  naive (t > 1: a whole prompt, or a chunk at `offset` over the row's cached
    latents): `[k_nope_i | v_i] = W_kvb,i c_kv` for every head i, scores
    `(q_nope_i . k_nope_i + q_rope_i . k_rope) scale`, causal softmax in
    float32, `o_i = sum p v_i`. The keys are walked in blocks of `_K_BLOCK`
    columns with a running softmax, live blocks only, so a chunk expands
    the columns up to its own last one and never a `[t, T]` score tensor of
    all heads.
  absorbed (t = 1, every row at its own `pos`): with `W_kvb,i = [W_UK,i ;
    W_UV,i]`, `q_lat_i = W_UK,i^T q_nope_i` (r wide), scores `([q_lat_i |
    q_rope_i] . [c_kv | k_rope]) scale`: H heads against ONE shared `[T, r +
    dr]` row, nothing re-expanded; `o_i = W_UV,i (sum p c_kv)`. On the chip
    the row is read through ops/latent_decode_attention.py: its live tiles
    of 512 columns, each once, both products on a tile while it is in fast
    memory; elsewhere masked einsums contract with all T columns.

`scale = (dn + dr)^-1/2 mscale(factor, mscale_all_dim)^2`. `y = W_o [o_i]`.

FFN_l is a gated MLP of width `intermediate_size` where l <
`first_k_dense_replace`, else the expert layer: `s = sigmoid(W_r h)` in
float32 over all `n_routed_experts`, the `num_experts_per_tok` largest scores
THEMSELVES (`topk_method` "none": no selection bias, no group limit), weights
`routed_scaling_factor s / sum s`; the part of the result the HELD experts
give (`cfg.held_experts = (first, count)`; default all) plus the shared expert
(distributed/moe.py `moe_dropless_layer`).

State, as the serving engine sees it (`AXK1DecodeModel.cache_spec`): one leaf
of kind `kv`, `({"latent": [L, B, T, W]}, {})`, written at `pos`. ONE leaf,
not a latent and a rotary one: the absorbed scores are then one contraction,
where two leaves cost a second pass over the `[B, H, T]` float32 scores. W is
r + dr rounded up to the chip's 128 lanes (576 -> 640, the rest zeros): the
chip stores a minor axis in tiles of 128 whatever its length, and handed
`[.., 576]` its compiler kept the cache packed between steps and unpacked ALL
of it into a padded copy inside every step (6.25 GiB of temporaries, compiled
for a described v5e; PERF.md, PR 36). Nothing has a fixed size; the second
half of the pair is empty.

Forward only: the expert loop's trip count is data (distributed/moe.py).
"""
import jax
import numpy as np

from ..ops import rope as _rope
from ..serving import decode_model as _decode_model
from ._functional_lm import (STEP_COUNTS, FunctionalCausalLM, count_vector,
                             dot as _dot, dot32 as _dot32,
                             einsum32 as _einsum32, rms as _rms)

_K_BLOCK = 1024     # cache columns a block of the naive form's running softmax
_LANES = 128        # the cache's minor axis is a whole number of these
_MASKED = -1e30     # a score no query sees (finite: exp(_MASKED - m) is 0)


class AXK1Config:
    """Keys as the published config.json names them. `held_experts=(first,
    count)`: the routed experts this model holds of `n_routed_experts` (one
    chip's share of an expert-parallel deployment)."""

    def __init__(self, vocab_size=163840, hidden_size=7168,
                 intermediate_size=18432, moe_intermediate_size=2048,
                 num_hidden_layers=61, first_k_dense_replace=1,
                 num_attention_heads=64, q_lora_rank=1536, kv_lora_rank=512,
                 qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
                 n_routed_experts=192, held_experts=None,
                 num_experts_per_tok=8, n_shared_experts=1,
                 norm_topk_prob=True, routed_scaling_factor=2.5,
                 scoring_func="sigmoid", topk_method="none",
                 rms_norm_eps=1e-6, rope_theta=10000.0, rope_scaling=None,
                 max_seq_len=4096, init_std=0.02):
        if scoring_func != "sigmoid" or topk_method != "none":
            raise ValueError(
                f"scoring_func {scoring_func!r} / topk_method "
                f"{topk_method!r}: this family's router is written for "
                "sigmoid scores and a plain top-k (no group limit, no "
                "selection bias)")
        self.vocab_size = int(vocab_size)
        self.hidden_size = int(hidden_size)
        self.intermediate_size = int(intermediate_size)
        self.moe_intermediate_size = int(moe_intermediate_size)
        self.num_layers = int(num_hidden_layers)
        self.first_k_dense = min(int(first_k_dense_replace), self.num_layers)
        self.num_heads = int(num_attention_heads)
        self.q_lora_rank = int(q_lora_rank)
        self.kv_lora_rank = int(kv_lora_rank)
        self.qk_nope_head_dim = int(qk_nope_head_dim)
        self.qk_rope_head_dim = int(qk_rope_head_dim)
        self.v_head_dim = int(v_head_dim)
        self.n_routed_experts = int(n_routed_experts)
        first, count = (0, self.n_routed_experts) if held_experts is None \
            else (int(held_experts[0]), int(held_experts[1]))
        if not (0 <= first and 1 <= count
                and first + count <= self.n_routed_experts):
            raise ValueError(f"held_experts={held_experts} is no range of "
                             f"{self.n_routed_experts} experts")
        self.held_experts = (first, count)
        self.num_experts_per_tok = int(num_experts_per_tok)
        self.n_shared_experts = int(n_shared_experts)
        self.norm_topk_prob = bool(norm_topk_prob)
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.rms_norm_eps = float(rms_norm_eps)
        self.rope_theta = float(rope_theta)
        self.rope_scaling = dict(rope_scaling) if rope_scaling else None
        self.max_seq_len = int(max_seq_len)
        self.init_std = float(init_std)
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even (rotary pairs)")

    @property
    def latent_width(self):
        """Values a token a layer costs the cache: c_kv and k_rope."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def cache_width(self):
        """The cache's minor axis: `latent_width` in whole lanes."""
        return -(-self.latent_width // _LANES) * _LANES

    @property
    def softmax_scale(self):
        return _rope.softmax_scale(
            self.qk_nope_head_dim + self.qk_rope_head_dim, self.rope_scaling)


def param_shapes(cfg):
    """{name: (shape, kind)} of every parameter. Kinds: "matrix", "gain"
    (ones)."""
    d, H = cfg.hidden_size, cfg.num_heads
    rq, r = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    f, E = cfg.moe_intermediate_size, cfg.n_routed_experts
    count = cfg.held_experts[1]
    fs = f * cfg.n_shared_experts
    out = {"embed.weight": ((cfg.vocab_size, d), "matrix")}
    for l in range(cfg.num_layers):
        pre = f"layers.{l}."
        a = pre + "attn."
        out.update({pre + "norm1.weight": ((d,), "gain"),
                    a + "q_a.weight": ((d, rq), "matrix"),
                    a + "q_norm.weight": ((rq,), "gain"),
                    a + "q_b.weight": ((rq, H * (dn + dr)), "matrix"),
                    a + "kv_a.weight": ((d, r + dr), "matrix"),
                    a + "kv_norm.weight": ((r,), "gain"),
                    a + "kv_b.weight": ((r, H * (dn + dv)), "matrix"),
                    a + "o.weight": ((H * dv, d), "matrix"),
                    pre + "norm2.weight": ((d,), "gain")})
        if l < cfg.first_k_dense:
            m, w = pre + "mlp.", cfg.intermediate_size
            out.update({m + "gate.weight": ((d, w), "matrix"),
                        m + "up.weight": ((d, w), "matrix"),
                        m + "down.weight": ((w, d), "matrix")})
            continue
        m = pre + "moe."
        out.update({m + "router.weight": ((d, E), "matrix"),
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
    """normal(0, init_std) matrices and unit gains; keys from the
    framework's generator."""
    import jax.numpy as jnp

    from ..core import generator as _generator

    def init(name, shape, kind, dtype):
        if kind == "gain":
            return jnp.ones(shape, dtype)
        return (cfg.init_std * jax.random.normal(
            _generator.get_rng_key(), shape, jnp.float32)).astype(dtype)

    return init


# -- the pure functions --------------------------------------------------------

def _attend_naive(q_nope, q_rope, lat, w_kvb, limit, live, cfg):
    """The naive form. q_nope [B, t, H, dn], q_rope [B, t, H, dr]; lat [B, S,
    W], the latents the queries may see; w_kvb [r, H (dn + dv)]; limit
    [B or 1, t]: query i of row b sees columns 0..limit[b, i]; live: how many
    of the S columns some query sees (a traced scalar, or None: all): the
    blocks of `min(_K_BLOCK, S)` columns past them are not walked. Every
    block up-projects its columns to per-head keys and values and is folded
    into a running float32 softmax.
    Returns [B, t, H, dv] in q_nope's dtype."""
    import jax.numpy as jnp

    B, t, H, dn = q_nope.shape
    r, dr, dv = cfg.kv_lora_rank, cfg.qk_rope_head_dim, cfg.v_head_dim
    S = lat.shape[1]
    kb = min(_K_BLOCK, S)
    total = -(-S // kb)
    if S % kb:
        lat = jnp.pad(lat, [(0, 0), (0, total * kb - S), (0, 0)])
    scale = cfg.softmax_scale
    cols = jnp.arange(kb, dtype=jnp.int32)

    def block(j, carry):
        m, l, acc = carry            # [B, H, t], [B, H, t], [B, t, H, dv]
        part = jax.lax.dynamic_slice_in_dim(lat, j * kb, kb, axis=1)
        kv = _dot(part[..., :r], w_kvb).reshape(B, kb, H, dn + dv)
        s = (_einsum32("bqhd,bshd->bhqs", q_nope, kv[..., :dn])
             + _einsum32("bqhd,bsd->bhqs", q_rope,
                         part[..., r:r + dr])) * scale
        seen = (j * kb + cols)[None, None, :] <= limit[:, :, None]
        s = jnp.where(seen[:, None], s, _MASKED)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        pr = jnp.exp(s - m_new[..., None])
        l = l * alpha + jnp.sum(pr, axis=-1)
        o = _einsum32("bhqs,bshd->bqhd", pr.astype(kv.dtype), kv[..., dn:])
        acc = acc * jnp.moveaxis(alpha, 1, 2)[..., None] + o
        return m_new, l, acc

    # column 0 is in block 0 and every query sees it: from there on m is a
    # real score, and a block a query sees nothing of adds exp(_MASKED - m) = 0
    init = (jnp.full((B, H, t), _MASKED, jnp.float32),
            jnp.zeros((B, H, t), jnp.float32),
            jnp.zeros((B, t, H, dv), jnp.float32))
    n = total if live is None else jnp.minimum((live + kb - 1) // kb, total)
    _, l, acc = jax.lax.fori_loop(0, n, block, init)
    return (acc / jnp.moveaxis(l, 1, 2)[..., None]).astype(q_nope.dtype)


def _attend_absorbed(q_nope, q_rope, lat, l, w_kvb, at, cfg):
    """The absorbed form of a one-token step. q_nope [B, H, dn], q_rope [B, H,
    dr]; lat [L, B, T, W], this layer's index l; at [B]: row b sees columns
    0..at[b]. W_UK goes into the query and W_UV onto the output; the attention
    itself is H heads against the row's latents as they lie in the cache: on
    the chip through ops/latent_decode_attention.py (the live tiles, each
    read once), elsewhere through masked einsums over all T columns. Returns
    [B, H, dv]."""
    import jax.numpy as jnp

    from ..ops import latent_decode_attention as _lda

    B, H, dn = q_nope.shape
    r, dv = cfg.kv_lora_rank, cfg.v_head_dim
    w = w_kvb.reshape(r, H, dn + dv)
    q_lat = _einsum32("bhd,rhd->bhr", q_nope, w[..., :dn]).astype(lat.dtype)
    q = jnp.concatenate([q_lat, q_rope.astype(lat.dtype)], axis=-1)
    q = jnp.pad(q, [(0, 0), (0, 0), (0, lat.shape[-1] - q.shape[-1])])
    if _lda.live_only(lat, q):
        o_lat = _lda.latent_decode_attention(lat, q, l, at,
                                             cfg.softmax_scale)[..., :r]
    else:
        rows = lat[l]
        s = _einsum32("bhc,bsc->bhs", q, rows) * cfg.softmax_scale
        seen = jnp.arange(rows.shape[1], dtype=jnp.int32)[None, :] \
            <= at[:, None]
        s = jnp.where(seen[:, None, :], s, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1).astype(lat.dtype)
        # over all W channels and the rest dropped: a slice of the cache as
        # the product's operand would be a copy of it
        o_lat = _einsum32("bhs,bsc->bhc", pr, rows)[..., :r].astype(
            lat.dtype)
    return _einsum32("bhr,rhd->bhd", o_lat, w[..., dn:]).astype(q_nope.dtype)


def _attn(p, pre, cfg, inv_freq, rot_scale, h, lat, l, pos):
    """The latent-attention layer. h [B, t, d]; lat [L, B, T, W], this
    layer's index l; pos: a [B] vector (one token a row, each at its own
    column: the absorbed form), or a scalar (the whole batch writes columns
    pos..pos + t: the naive form). Returns (y float32, lat)."""
    import jax.numpy as jnp

    B, t, _ = h.shape
    H, r = cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    T = lat.shape[2]
    per_row = jnp.ndim(pos) == 1
    if per_row:
        at = jnp.clip(pos, 0, T - 1)
        where = at[:, None]                                 # [B, 1]
    else:
        where = (pos + jnp.arange(t, dtype=jnp.int32))[None, :]     # [1, t]
    with jax.named_scope("proj"):
        c_q = _rms(_dot32(h, p[pre + "q_a.weight"]),
                   p[pre + "q_norm.weight"], cfg.rms_norm_eps).astype(h.dtype)
        q = _dot(c_q, p[pre + "q_b.weight"]).reshape(B, t, H, dn + dr)
        q_nope = q[..., :dn]
        q_rope = _rope.rotate(q[..., dn:], where[:, :, None], inv_freq,
                              rot_scale).astype(h.dtype)
        kv = _dot32(h, p[pre + "kv_a.weight"])              # [B, t, r + dr]
        new = jnp.concatenate(
            [_rms(kv[..., :r], p[pre + "kv_norm.weight"], cfg.rms_norm_eps),
             _rope.rotate(kv[..., r:], where, inv_freq, rot_scale),
             jnp.zeros((B, t, lat.shape[-1] - r - dr), jnp.float32)],
            axis=-1).astype(lat.dtype)
    w_kvb = p[pre + "kv_b.weight"]
    with jax.named_scope("cache/store"):
        if per_row:
            lat = lat.at[l, jnp.arange(B), at].set(new[:, 0])
        else:
            lat = jax.lax.dynamic_update_slice(lat, new[None],
                                               (l, 0, pos, 0))
    # the up-projections the two forms fold into or around their scores
    # are part of the form: the naive one's inside its loop over blocks
    with jax.named_scope("core"):
        if per_row:
            o = _attend_absorbed(q_nope[:, 0], q_rope[:, 0], lat, l, w_kvb,
                                 at, cfg)[:, None]
        else:
            if isinstance(pos, int) and pos == 0:
                # a sequence from its start attends to itself alone
                seen, live = new, None
            else:
                seen, live = lat[l], pos + t
            o = _attend_naive(q_nope, q_rope, seen, w_kvb, where, live, cfg)
    with jax.named_scope("proj"):
        return _dot32(o.reshape(B, t, H * dv), p[pre + "o.weight"]), lat


def _ffn(p, pre, cfg, l, h, h32):
    """Layer l's feed-forward over h [B, t, d] (h32: the same before it was
    rounded, for the router): (y float32, counts or None)."""
    from ..distributed import moe as _moe_ops

    B, t, d = h.shape
    flat = h.reshape(B * t, d)
    if l < cfg.first_k_dense:
        with jax.named_scope("mlp"):
            y = _moe_ops.gated_mlp(flat, *(p[pre + f"mlp.{n}.weight"]
                                           for n in ("gate", "up", "down")))
        return y.reshape(B, t, d), None
    m = pre + "moe."
    shared = None
    if cfg.n_shared_experts:
        shared = tuple(p[m + f"shared.{n}.weight"]
                       for n in ("gate", "up", "down"))
    y, counts = _moe_ops.moe_dropless_layer(
        flat, p[m + "router.weight"], None, p[m + "experts.gate"],
        p[m + "experts.up"], p[m + "experts.down"], cfg.num_experts_per_tok,
        shared=shared, held=cfg.held_experts, normalize=cfg.norm_topk_prob,
        scale=cfg.routed_scaling_factor, router_x=h32.reshape(B * t, d))
    return y.reshape(B, t, d), counts


def _decode_fns(cfg):
    """(fwd, logits_of, cache_init): the functions the Layer's forward and
    the serving engine's programs are made of."""
    import jax.numpy as jnp

    inv_freq = np.asarray(_rope.yarn_inv_freq(
        cfg.qk_rope_head_dim, cfg.rope_theta, cfg.rope_scaling), np.float32)
    rot_scale = _rope.cos_sin_scale(cfg.rope_scaling)

    def cache_init(b, T, dt):
        return ({"latent": jnp.zeros((cfg.num_layers, b, T,
                                      cfg.cache_width), dt)}, {})

    def fwd(p, toks, pos, kv, rest, valid_len=None, counts=False):
        """toks [B, t]; pos: the columns the tokens are written at (a [B]
        vector with t = 1, else a scalar); kv, rest: the pair of
        `cache_init`. Returns (x [B, t, d] float32 before the final norm, kv,
        rest) and, with `counts`, an int32 vector of STEP_COUNTS. `valid_len`
        is taken and not needed: a latent past a sequence's end is junk
        nobody sees."""
        cdt = p["embed.weight"].dtype
        with jax.named_scope("embed"):
            x = p["embed.weight"][toks].astype(jnp.float32)
        lat = kv["latent"]
        total = jnp.zeros((len(STEP_COUNTS),), jnp.int32)
        for l in range(cfg.num_layers):
            pre = f"layers.{l}."
            # a layer's norm lies under the layer's word
            with jax.named_scope("attn"):
                h = _rms(x, p[pre + "norm1.weight"],
                         cfg.rms_norm_eps).astype(cdt)
                y, lat = _attn(p, pre + "attn.", cfg, inv_freq, rot_scale, h,
                               lat, l, pos)
                x = x + y
            with jax.named_scope("mlp" if l < cfg.first_k_dense else "moe"):
                h32 = _rms(x, p[pre + "norm2.weight"], cfg.rms_norm_eps)
            y, c = _ffn(p, pre, cfg, l, h32.astype(cdt), h32)
            x = x + y
            if c is not None:
                total = total + count_vector(c)
        out = (x, {"latent": lat}, rest)
        return out + (total,) if counts else out

    def logits_of(p, x):
        w = p["lm_head.weight"]
        with jax.named_scope("head"):
            return _dot32(_rms(x, p["norm.weight"],
                               cfg.rms_norm_eps).astype(w.dtype), w)

    return fwd, logits_of, cache_init


class AXK1ForCausalLM(FunctionalCausalLM):
    """The decoder with its untied head (models/_functional_lm.py has the
    constructor's and `forward`'s contract)."""

    param_shapes = staticmethod(param_shapes)
    default_init = staticmethod(_default_init)
    decode_fns = staticmethod(_decode_fns)


class AXK1DecodeModel(_decode_model.DecodeModel):
    """The family's DecodeModel adapter: a described cache of one `kv` leaf
    that is a latent, not a K/V pair of heads; a decode step that returns
    STEP_COUNTS beside the tokens. Served by the dense engine alone."""

    name = "axk1"
    step_counts = STEP_COUNTS
    not_served = {
        "paged_kv": "a paged pool holds pages of K/V heads, [n_blocks, L, "
                    "KVh, block, hd] a side, not latent rows",
        "draft_model": "its verify step would need the naive form at "
                       "per-row positions",
        "tp_mesh": "the latent is one row for all heads and the experts "
                   "are not sharded over 'mp'",
        "lora": "no LoRA sites",
        "cache_dtype": "a quantized latent is not written"}

    def check_config(self, cfg):
        if not isinstance(cfg, AXK1Config):
            raise ValueError("the axk1 decode model serves AXK1Config models")

    def compute_dtype(self, dtype):
        from .gpt import _decode_compute_dtype

        return _decode_compute_dtype(dtype)

    def extract_params(self, model, who):
        return {n: p._data for n, p in model.named_parameters()}, None

    def decode_fns(self, cfg, aux, cache_dtype=None, tp_axis=None,
                   tp_size=1):
        if cache_dtype is not None or tp_axis is not None:
            raise ValueError(
                "decode model 'axk1' serves neither a quantized cache "
                "(cache_dtype=) nor tensor-parallel (tp_mesh=): "
                + self.not_served["cache_dtype"] + "; "
                + self.not_served["tp_mesh"])
        return _decode_fns(cfg)

    def cache_spec(self, cfg):
        return {"kind": "state_tree",
                "leaves": [{"path": (0, "latent"), "kind": "kv",
                            "slot_axis": 1, "layers": cfg.num_layers,
                            "layout": "[L, B, T, W]: c_kv | k_rope | 0"}],
                "axes": {"L": cfg.num_layers, "T": cfg.max_seq_len,
                         "r": cfg.kv_lora_rank, "dr": cfg.qk_rope_head_dim,
                         "W": cfg.cache_width},
                "quantized": None}

    def kv_read_tile(self, cfg, side, dtype, tp_size=1):
        """The tile ops/latent_decode_attention.py walks a row in where the
        absorbed step takes it (a TPU); None where the einsums read all."""
        from ..ops import latent_decode_attention as _lda

        lat = side["latent"]
        q = jax.ShapeDtypeStruct((lat.shape[1], cfg.num_heads, lat.shape[3]),
                                 dtype)
        return _lda.tile_of(lat.shape[2]) if _lda.live_only(lat, q) else None

    def matches(self, model):
        return isinstance(model, AXK1ForCausalLM)


_decode_model.register_decode_model(AXK1DecodeModel())
