"""Attention functionals.

Reference parity: the reference has no fused attention op (MultiHeadAttention composes
matmuls in python/paddle/nn/layer/transformer.py:83); this module goes beyond it with a
single attention entry point that can route to the Pallas flash-attention kernel
(paddle_tpu/ops/flash_attention.py) on TPU, or the naive XLA path elsewhere.
"""
import math

import numpy as np
import jax
import jax.numpy as jnp

from ...core.dispatch import apply
from ...core.tensor import Tensor


def _t(x):
    return x if isinstance(x, Tensor) else Tensor(np.asarray(x))


def scaled_dot_product_attention(
    query, key, value, attn_mask=None, dropout_p=0.0, is_causal=False, training=True, name=None,
    use_flash=True, window=None,
):
    """query/key/value: [batch, seq, heads, head_dim] (paddle 2.x layout).

    Routes to the Pallas flash kernel when shapes allow (TPU, no mask beyond
    causal/window); falls back to the naive XLA softmax(QK^T)V otherwise.
    window=W (requires is_causal) restricts attention to the last W tokens
    (sliding window) — block-skipped in the flash kernel, masked here.
    """
    if window is not None and not is_causal:
        raise ValueError("window requires is_causal=True")
    args = [_t(query), _t(key), _t(value)]
    mask_val = attn_mask._data if isinstance(attn_mask, Tensor) else attn_mask

    # a pure predicate: an import or lowering failure of the kernel raises
    # — it never quietly becomes the naive path below
    from ...ops import flash_attention as fa

    q = args[0]
    flash_ok = (
        use_flash
        and mask_val is None
        and dropout_p == 0.0
        and fa.supported(tuple(q.shape), str(q.dtype))
    )

    if flash_ok:
        from ...distributed.mesh import current_mesh

        def fn(q, k, v):
            return fa.flash_attention(q, k, v, causal=is_causal,
                                      window=window, mesh=current_mesh())

        return apply(fn, *args)

    def fn_probs(q, k):
        # [b, s, h, d] -> [b, h, s, d]
        q = jnp.swapaxes(q, 1, 2)
        k = jnp.swapaxes(k, 1, 2)
        scale = 1.0 / math.sqrt(q.shape[-1])
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
        if mask_val is not None:
            m = mask_val
            if m.dtype == jnp.bool_:
                scores = jnp.where(m, scores, jnp.asarray(-1e30, scores.dtype))
            else:
                scores = scores + m.astype(scores.dtype)
        if is_causal:
            s_q, s_k = scores.shape[-2], scores.shape[-1]
            keep = jnp.tril(jnp.ones((s_q, s_k), dtype=bool))
            if window is not None:
                qp = jnp.arange(s_q)[:, None]
                kp = jnp.arange(s_k)[None, :]
                keep &= (qp - kp) < window
            scores = jnp.where(keep, scores, jnp.asarray(-1e30, scores.dtype))
        return jax.nn.softmax(scores, axis=-1)

    def fn_out(p_, v):
        return jnp.swapaxes(
            jnp.einsum("bhqk,bhkd->bhqd", p_, jnp.swapaxes(v, 1, 2)), 1, 2)

    if dropout_p and training:
        # attention dropout on the probabilities (reference semantics);
        # routed through F.dropout so the framework RNG (and per-step keys
        # under a jitted trainer) governs the mask
        from .common import dropout as f_dropout

        probs = apply(fn_probs, args[0], args[1])
        probs = f_dropout(probs, p=dropout_p, training=True)
        return apply(fn_out, _t(probs), args[2])
    probs = apply(fn_probs, args[0], args[1])
    return apply(fn_out, _t(probs), args[2])
