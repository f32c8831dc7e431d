"""The plain reference of the `solar_open2` family: the hybrid block of
Solar-Open2-250B in straightforward jax.numpy.

Float32, every product at matmul precision "highest", one sequence at a time,
the KDA recurrence token by token, a dense loop over the held experts, no
kernel, no cache, no batching. It imports nothing of the program and is handed
the weights the benchmark made (benchmark/hybrid_weights.py: a flat dict, kept
in the dtype it was made in; a matrix is widened to float32 where it is used,
an expert at a time, so that 3.3 B bfloat16-valued parameters never stand in
float32 at once).

The equations (ISSUE 34; each `assumed` item is in the configuration's file):

  block      x = x + Mix_l(RMSNorm(x));  x = x + MoE_l(RMSNorm(x));
             Mix_l is GQA where l is in `gqa_layers`, KDA otherwise; after
             the last layer RMSNorm, then the untied head. No bias anywhere.
  GQA        q = W_q h (H x hd), k, v = W_k h, W_v h (KVh x hd), no
             positional term, causal softmax(q k^T / sqrt(hd)) v = o;
             y = W_o (sigmoid(W_g h) * o), the gate elementwise.
  KDA        q, k = L2Norm(SiLU(Conv(W_q h))), same for k; v = SiLU(Conv(W_v
             h)); Conv a causal depthwise convolution of `conv` taps;
             beta = 2 sigmoid(W_b h) a head; for every key channel the decay
             a = exp(-exp(A_log) softplus(W_fup W_fdown h + dt_bias)); a head's
             float32 state S [dk, dv]:
               S_t = (I - beta_t k_t k_t^T) Diag(a_t) S_{t-1} + beta_t k_t v_t^T
               o_t = S_t^T q_t / sqrt(dk)
             y = W_o (sigmoid(W_gup W_gdown h) * RMSNorm_head(o)).
  MoE        s = sigmoid(W_r h) in float32; the top_k experts are the top of
             s + b; w_e = s_e / (sum of the selected s) * scale; y = sum over
             the selected experts THIS SHARE HOLDS of w_e F_e(h), plus
             F_shared(h); F(h) = W_down(SiLU(W_gate h) * W_up h). What the
             experts held elsewhere would add is left out, as in the program.

`precision` puts the reference in the program's place at a lower precision
(the control `correct` has to fail): "bfloat16" rounds both operands of every
product to bfloat16, "fp8" to float8_e4m3fn with one scale a tensor, each
accumulating in float32 (benchmark/reference/gpt.py has the same control).
"""
import collections
import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST

#: the numbers of a configuration the equations need (hashable: a jit key)
Dims = collections.namedtuple("Dims", [
    "layers", "gqa_layers", "heads", "kv_heads", "head_dim", "kda_heads",
    "kda_head_dim", "conv", "experts", "held", "top_k", "norm_topk",
    "routed_scale", "shared", "eps"])


def dims_of(cfg):
    """Dims from a configuration file's dict (published keys; `held_experts`
    under `deployment`: [first, count])."""
    lin = cfg["linear_attn_config"]
    L = int(cfg["num_hidden_layers"])
    held = cfg.get("deployment", {}).get(
        "held_experts", [0, cfg["n_routed_experts"]])
    return Dims(
        layers=L, gqa_layers=tuple(l for l in cfg["gqa_layers"] if l < L),
        heads=int(cfg["num_attention_heads"]),
        kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg["head_dim"]), kda_heads=int(lin["num_heads"]),
        kda_head_dim=int(lin["head_dim"]),
        conv=int(lin["short_conv_kernel_size"]),
        experts=int(cfg.get("deployment", {}).get(
            "n_routed_experts_published", cfg["n_routed_experts"])),
        held=(int(held[0]), int(held[1])),
        top_k=int(cfg["num_experts_per_tok"]),
        norm_topk=bool(cfg["norm_topk_prob"]),
        routed_scale=float(cfg["routed_scaling_factor"]),
        shared=int(cfg["n_shared_experts"]), eps=float(cfg["rms_norm_eps"]))


def _q(x, precision):
    if precision == "bfloat16":
        return x.astype(jnp.bfloat16)
    if precision == "fp8":
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
        low = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
        return (low * scale).astype(jnp.bfloat16)
    raise ValueError(f"unknown precision {precision!r}")


def _dot(eq, a, b, precision):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if precision == "float32":
        return jnp.einsum(eq, a, b, precision=HIGHEST)
    return jnp.einsum(eq, _q(a, precision), _q(b, precision),
                      preferred_element_type=jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _gqa(P, pre, h, D, precision):
    s = h.shape[0]
    H, KV, hd = D.heads, D.kv_heads, D.head_dim
    G = H // KV
    q = _dot("sd,dk->sk", h, P[pre + "q.weight"], precision).reshape(
        s, KV, G, hd)
    k = _dot("sd,dk->sk", h, P[pre + "k.weight"], precision).reshape(s, KV, hd)
    v = _dot("sd,dk->sk", h, P[pre + "v.weight"], precision).reshape(s, KV, hd)
    causal = jnp.tril(jnp.ones((s, s), bool))

    def group(_, xs):               # one KV head and its G query heads
        qg, kg, vg = xs             # [s, G, hd], [s, hd], [s, hd]
        att = _dot("qgd,kd->gqk", qg, kg, precision) / math.sqrt(hd)
        att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), axis=-1)
        return None, _dot("gqk,kd->qgd", att, vg, precision)

    _, o = jax.lax.scan(group, None, (jnp.moveaxis(q, 1, 0),
                                      jnp.moveaxis(k, 1, 0),
                                      jnp.moveaxis(v, 1, 0)))
    o = jnp.moveaxis(o, 0, 1).reshape(s, H * hd)            # [s, KV, G, hd]
    gate = jax.nn.sigmoid(_dot("sd,dk->sk", h, P[pre + "g.weight"],
                               precision))
    return _dot("sk,kd->sd", gate * o, P[pre + "o.weight"], precision)


def _conv(x, taps):
    """Causal depthwise convolution: y_t = sum_j taps[j] x_{t - K + 1 + j}."""
    K, s = taps.shape[0], x.shape[0]
    ext = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), x.dtype), x])
    return sum(taps[j].astype(jnp.float32) * ext[j:j + s] for j in range(K))


def _kda(P, pre, h, D, precision):
    s = h.shape[0]
    H, dk = D.kda_heads, D.kda_head_dim

    def mixed(n):
        return jax.nn.silu(_conv(_dot("sd,dk->sk", h, P[pre + n + ".weight"],
                                      precision),
                                 P[pre + n + "_conv.weight"])).reshape(s, H, dk)

    def l2(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    q, k, v = l2(mixed("q")), l2(mixed("k")), mixed("v")
    beta = 2.0 * jax.nn.sigmoid(_dot("sd,dh->sh", h, P[pre + "b.weight"],
                                     precision))
    f = _dot("sr,rk->sk", _dot("sd,dr->sr", h, P[pre + "f_down.weight"],
                               precision), P[pre + "f_up.weight"], precision)
    g = -jnp.exp(P[pre + "A_log"].astype(jnp.float32))[:, None] \
        * jax.nn.softplus(f + P[pre + "dt_bias"].astype(jnp.float32)).reshape(
            s, H, dk)

    def step(S, xs):                # the recurrence, one token
        q_t, k_t, v_t, g_t, b_t = xs
        S = jnp.exp(g_t)[..., None] * S
        u = b_t[:, None] * (v_t - _dot("hk,hkv->hv", k_t, S, precision))
        S = S + k_t[..., None] * u[:, None, :]
        return S, _dot("hk,hkv->hv", q_t, S, precision) / math.sqrt(dk)

    _, o = jax.lax.scan(step, jnp.zeros((H, dk, dk), jnp.float32),
                        (q, k, v, g, beta))                 # [s, H, dv]
    gate = jax.nn.sigmoid(_dot(
        "sr,rk->sk", _dot("sd,dr->sr", h, P[pre + "g_down.weight"], precision),
        P[pre + "g_up.weight"], precision)).reshape(s, H, dk)
    o = _rms(o, P[pre + "o_norm.weight"], D.eps) * gate
    return _dot("sk,kd->sd", o.reshape(s, H * dk), P[pre + "o.weight"],
                precision)


def _mlp(h, gate, up, down, precision):
    a = jax.nn.silu(_dot("sd,df->sf", h, gate, precision)) \
        * _dot("sd,df->sf", h, up, precision)
    return _dot("sf,fd->sd", a, down, precision)


def _moe(P, pre, h, D, precision):
    first, count = D.held
    s = jax.nn.sigmoid(_dot("sd,de->se", h, P[pre + "router.weight"],
                            precision))
    _, chosen = jax.lax.top_k(s + P[pre + "router.bias"].astype(jnp.float32),
                              D.top_k)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if D.norm_topk:
        w = w / jnp.sum(w, -1, keepdims=True)
    w = w * D.routed_scale

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


@functools.partial(jax.jit, static_argnames=("D", "precision"))
def sequence_logits(P, ids, D, precision="float32"):
    """ids [s] -> logits [s, vocabulary rows held] float32."""
    x = P["embed.weight"][ids].astype(jnp.float32)
    for l in range(D.layers):
        pre = f"layers.{l}."
        h = _rms(x, P[pre + "norm1.weight"], D.eps)
        if l in D.gqa_layers:
            x = x + _gqa(P, pre + "attn.", h, D, precision)
        else:
            x = x + _kda(P, pre + "kda.", h, D, precision)
        h = _rms(x, P[pre + "norm2.weight"], D.eps)
        x = x + _moe(P, pre + "moe.", h, D, precision)
    x = _rms(x, P["norm.weight"], D.eps)
    return _dot("sd,dv->sv", x, P["lm_head.weight"], precision)
