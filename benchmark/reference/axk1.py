"""The plain reference of the `axk1` family: A.X-K1's block (multi-head latent
attention with YaRN rotary, a leading dense layer, sigmoid-routed experts with
a shared expert) in straightforward jax.numpy.

Float32, every product at matmul precision "highest", one sequence at a time,
the NAIVE form of the attention only (every token's latent up-projected to
per-head keys and values; no cache, no absorbed form, no running softmax: one
`[s, s]` score matrix a head, heads one after another so that 8,192 tokens
fit), a dense loop over the held experts, no kernel, no batching. It imports
nothing of the program (its helpers are the other reference's) and is handed
the weights the benchmark made (benchmark/latent_weights.py: a flat dict kept
in the dtype it was made in; a matrix is widened to float32 where it is used).

The equations (ISSUE 36; DeepSeek-V3's published form, whose keys the config
repeats; each `assumed` item is in the configuration's file). h is the
RMS-normed stream, H heads, r = kv_lora_rank, dn / dr / dv = qk_nope / qk_rope
/ v head sizes:

  block      x = x + Attn_l(RMSNorm(x));  x = x + FFN_l(RMSNorm(x)); after the
             last layer RMSNorm, then the untied head. No bias anywhere.
  Attn       c_q = RMSNorm(W_qa h); [q_nope | q_rope] = W_qb c_q a head;
             [c | k_r] = W_kva h; c_kv = RMSNorm(c); k_rope = RoPE(k_r), ONE
             for all heads; [k_nope_i | v_i] = W_kvb,i c_kv;
             s = (q_nope_i . k_nope_i + RoPE(q_rope_i) . k_rope) scale, causal
             softmax, o_i = sum p v_i; y = W_o [o_i];
             scale = (dn + dr)^-1/2 m^2, m = 0.1 mscale_all_dim ln(factor) + 1.
  RoPE       YaRN over the dr/2 pairs: f_j = theta^(-2j/dr); low, high = floor,
             ceil of dr ln(original / (2 pi beta)) / (2 ln theta) at beta_fast,
             beta_slow; mask_j = 1 - clip((j - low)/(high - low), 0, 1);
             inv_freq_j = f_j/factor (1 - mask_j) + f_j mask_j; cos and sin
             times mscale(factor, mscale) / mscale(factor, mscale_all_dim);
             channel i turns with channel i + dr/2 (rotate-half).
  FFN        l < first_k_dense_replace: W_down(SiLU(W_gate h) * W_up h), width
             intermediate_size. Else s = sigmoid(W_r h) in float32; the top_k
             largest s themselves (topk_method "none"); w_e = scale s_e / (sum
             of the selected s); y = sum over the selected experts THIS SHARE
             HOLDS of w_e F_e(h), plus F_shared(h). What the experts held
             elsewhere would add is left out, as in the program.

Departures from the published description: the rotary pairing is rotate-half
over the 64 channels where the published code de-interleaves first (with
seeded weights a relabelling of W_qb's and W_kva's columns); `n_group` and
`topk_group` are not read (`topk_method` "none"). Both are under `assumed` in
the configuration's file.

`precision` puts the reference in the program's place at a lower precision
(the control `correct` has to fail): "bfloat16" and "fp8" as
benchmark/reference/solar_open2.py has them.
"""
import collections
import functools
import math

import jax
import jax.numpy as jnp

from benchmark.reference.solar_open2 import _dot, _mlp, _rms

#: the numbers of a configuration the equations need (hashable: a jit key)
Dims = collections.namedtuple("Dims", [
    "layers", "dense_layers", "heads", "q_rank", "kv_rank", "nope", "rope",
    "v", "experts", "held", "top_k", "norm_topk", "routed_scale", "shared",
    "eps", "theta", "yarn"])


def dims_of(cfg):
    """Dims from a configuration file's dict (published keys; `held_experts`
    under `deployment`: [first, count])."""
    dep = cfg.get("deployment", {})
    held = dep.get("held_experts", [0, cfg["n_routed_experts"]])
    if cfg.get("scoring_func", "sigmoid") != "sigmoid" \
            or cfg.get("topk_method", "none") != "none":
        raise ValueError("the axk1 reference is written for sigmoid scores "
                         "and topk_method 'none'")
    sc = cfg.get("rope_scaling")
    yarn = None if not sc else (
        float(sc["factor"]), float(sc["beta_fast"]), float(sc["beta_slow"]),
        float(sc["mscale"]), float(sc["mscale_all_dim"]),
        int(sc["original_max_position_embeddings"]))
    L = int(cfg["num_hidden_layers"])
    return Dims(
        layers=L, dense_layers=min(int(cfg["first_k_dense_replace"]), L),
        heads=int(cfg["num_attention_heads"]),
        q_rank=int(cfg["q_lora_rank"]), kv_rank=int(cfg["kv_lora_rank"]),
        nope=int(cfg["qk_nope_head_dim"]), rope=int(cfg["qk_rope_head_dim"]),
        v=int(cfg["v_head_dim"]),
        experts=int(dep.get("n_routed_experts_published",
                            cfg["n_routed_experts"])),
        held=(int(held[0]), int(held[1])),
        top_k=int(cfg["num_experts_per_tok"]),
        norm_topk=bool(cfg["norm_topk_prob"]),
        routed_scale=float(cfg["routed_scaling_factor"]),
        shared=int(cfg["n_shared_experts"]), eps=float(cfg["rms_norm_eps"]),
        theta=float(cfg["rope_theta"]), yarn=yarn)


def _mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def inv_freq(D):
    """[rope // 2] float32: the pairs' frequencies."""
    dim = D.rope
    j = jnp.arange(dim // 2, dtype=jnp.float32)
    plain = D.theta ** (-2.0 * j / dim)
    if D.yarn is None:
        return plain
    factor, fast, slow, _, _, original = D.yarn

    def pair(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(D.theta))

    low, high = max(math.floor(pair(fast)), 0), \
        min(math.ceil(pair(slow)), dim - 1)
    mask = 1.0 - jnp.clip((j - low) / max(high - low, 1e-3), 0.0, 1.0)
    return plain / factor * (1.0 - mask) + plain * mask


def softmax_scale(D):
    s = 1.0 / math.sqrt(D.nope + D.rope)
    if D.yarn is not None:
        s *= _mscale(D.yarn[0], D.yarn[4]) ** 2
    return s


def _rotate(x, D):
    """x [s, ..., rope], token i at position i."""
    s = x.shape[0]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq(D)   # [s, dr/2]
    ang = ang.reshape((s,) + (1,) * (x.ndim - 2) + (D.rope // 2,))
    m = 1.0 if D.yarn is None else \
        _mscale(D.yarn[0], D.yarn[3]) / _mscale(D.yarn[0], D.yarn[4])
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1) * m
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1) * m
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _attn(P, pre, h, D, precision, faults=()):
    s = h.shape[0]
    H, r, dn, dr, dv = D.heads, D.kv_rank, D.nope, D.rope, D.v
    c_q = _rms(_dot("sd,dr->sr", h, P[pre + "q_a.weight"], precision),
               P[pre + "q_norm.weight"], D.eps)
    q = _dot("sr,rk->sk", c_q, P[pre + "q_b.weight"], precision).reshape(
        s, H, dn + dr)
    q_nope, q_rope = q[..., :dn], _rotate(q[..., dn:], D)
    kv = _dot("sd,dk->sk", h, P[pre + "kv_a.weight"], precision)
    c_kv = kv[:, :r] if "no_kv_norm" in faults else \
        _rms(kv[:, :r], P[pre + "kv_norm.weight"], D.eps)
    k_rope = kv[:, r:] if "no_key_rotary" in faults else _rotate(kv[:, r:], D)
    up = _dot("sr,rk->sk", c_kv, P[pre + "kv_b.weight"], precision).reshape(
        s, H, dn + dv)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scale = softmax_scale(D._replace(yarn=None)) if "no_mscale" in faults \
        else softmax_scale(D)

    def head(_, xs):                # one head; its [s, s] scores alone
        qn, qr, kn, v = xs          # [s, dn], [s, dr], [s, dn], [s, dv]
        att = (_dot("qd,kd->qk", qn, kn, precision)
               + _dot("qd,kd->qk", qr, k_rope, precision)) * scale
        att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), axis=-1)
        return None, _dot("qk,kd->qd", att, v, precision)

    _, o = jax.lax.scan(head, None, (
        jnp.moveaxis(q_nope, 1, 0), jnp.moveaxis(q_rope, 1, 0),
        jnp.moveaxis(up[..., :dn], 1, 0), jnp.moveaxis(up[..., dn:], 1, 0)))
    o = jnp.moveaxis(o, 0, 1).reshape(s, H * dv)
    return _dot("sk,kd->sd", o, P[pre + "o.weight"], precision)


def _moe(P, pre, h, D, precision, faults=()):
    first, count = D.held
    s = jax.nn.sigmoid(_dot("sd,de->se", h, P[pre + "router.weight"],
                            precision))
    w, chosen = jax.lax.top_k(s, D.top_k)
    if D.norm_topk:
        w = w / jnp.sum(w, -1, keepdims=True)
    w = w * (1.0 if "routed_scale_1" in faults else D.routed_scale)

    def expert(y, xs):              # every token through one held expert
        e, gate, up, down = xs
        w_e = jnp.sum(jnp.where(chosen == first + e, w, 0.0), axis=-1)
        return y + w_e[:, None] * _mlp(h, gate, up, down, precision), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                        (jnp.arange(count), P[pre + "experts.gate"],
                         P[pre + "experts.up"], P[pre + "experts.down"]))
    if D.shared:
        y = y + _mlp(h, *(P[pre + f"shared.{n}.weight"]
                          for n in ("gate", "up", "down")), precision)
    return y


def moe_layer(P, pre, h, D, precision="float32"):
    """One expert layer over h [s, d] (the test of the shares calls it)."""
    return _moe(P, pre, h.astype(jnp.float32), D, precision)


def attn_layer(P, pre, h, D, precision="float32"):
    """One attention layer over h [s, d] (the weights' generator walks the
    layers with it)."""
    return _attn(P, pre, h.astype(jnp.float32), D, precision)


def ffn_layer(P, l, h, D, precision="float32", faults=()):
    """Layer l's feed-forward over h [s, d]."""
    pre = f"layers.{l}."
    if l < D.dense_layers:
        return _mlp(h, *(P[pre + f"mlp.{n}.weight"]
                         for n in ("gate", "up", "down")), precision)
    return _moe(P, pre + "moe.", h, D, precision, faults)


@functools.partial(jax.jit, static_argnames=("D", "precision", "faults"))
def sequence_logits(P, ids, D, precision="float32", faults=()):
    """ids [s] -> logits [s, vocabulary rows held] float32. `faults`: parts
    of the mathematics left out on purpose, for the tests that `token_gap`
    catches each: "no_key_rotary", "no_kv_norm", "no_mscale",
    "routed_scale_1"."""
    x = P["embed.weight"][ids].astype(jnp.float32)
    for l in range(D.layers):
        pre = f"layers.{l}."
        h = _rms(x, P[pre + "norm1.weight"], D.eps)
        x = x + _attn(P, pre + "attn.", h, D, precision, faults)
        h = _rms(x, P[pre + "norm2.weight"], D.eps)
        x = x + ffn_layer(P, l, h, D, precision, faults)
    x = _rms(x, P["norm.weight"], D.eps)
    return _dot("sd,dv->sv", x, P["lm_head.weight"], precision)
