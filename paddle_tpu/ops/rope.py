"""Rotary position embedding with YaRN-interpolated frequencies.

The only positional term in the tree besides models/gpt.py's learned table.
Its own module, not a part of models/axk1.py: the frequencies are a function
of the published `rope_scaling` block alone, the benchmark's plain reference
must not import them (it has its own copy of the equations), and the tests
hold them to hand-worked numbers without building a model.

YaRN (arXiv:2309.00071, in the form DeepSeek-V3's modelling code and the
configs that repeat its keys use) over the `dim // 2` pairs of a head's `dim`
rotary channels:

    f_j        = base^(-2j / dim)                      the plain frequencies
    low, high  = floor / ceil of the pair index whose wavelength makes
                 `beta_fast` / `beta_slow` turns in the ORIGINAL context:
                 dim ln(original / (2 pi beta)) / (2 ln base)
    mask_j     = 1 - clip((j - low) / (high - low), 0, 1)
    inv_freq_j = (f_j / factor) (1 - mask_j) + f_j mask_j

Pairs under `low` turn fast and keep their frequency (extrapolation); pairs
over `high` are slowed by `factor` (interpolation); a linear ramp between.
`mscale(factor, m) = 0.1 m ln(factor) + 1` (1 for a factor of at most 1):
cos and sin are multiplied by `mscale(factor, mscale) / mscale(factor,
mscale_all_dim)`, and the softmax scale of an attention that uses them carries
`mscale(factor, mscale_all_dim)` squared (`softmax_scale`).

Channels are paired rotate-half: channel i turns with channel i + dim/2.
"""
import math

import numpy as np


def mscale(factor, m=1.0):
    """YaRN's attention temperature: 0.1 m ln(factor) + 1, or 1."""
    if factor <= 1:
        return 1.0
    return 0.1 * float(m) * math.log(factor) + 1.0


def correction_range(beta_fast, beta_slow, dim, base, original):
    """(low, high): the pair indices between which YaRN ramps from keeping
    a frequency to dividing it by the factor."""

    def pair(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    return max(math.floor(pair(beta_fast)), 0), \
        min(math.ceil(pair(beta_slow)), dim - 1)


def yarn_inv_freq(dim, base=10000.0, scaling=None):
    """float64 [dim // 2]: the frequencies of the pairs. `scaling`: the
    published `rope_scaling` block (`type` "yarn": `factor`, `beta_fast`,
    `beta_slow`, `original_max_position_embeddings`), or None: plain."""
    j = np.arange(dim // 2, dtype=np.float64)
    plain = float(base) ** (-2.0 * j / dim)
    if not scaling:
        return plain
    if scaling.get("type", scaling.get("rope_type")) != "yarn":
        raise ValueError(f"rope_scaling of type {scaling.get('type')!r}: "
                         "only 'yarn' is written here")
    factor = float(scaling["factor"])
    low, high = correction_range(
        scaling.get("beta_fast", 32), scaling.get("beta_slow", 1), dim,
        float(base), scaling["original_max_position_embeddings"])
    span = max(high - low, 1e-3)
    mask = 1.0 - np.clip((j - low) / span, 0.0, 1.0)
    return plain / factor * (1.0 - mask) + plain * mask


def cos_sin_scale(scaling):
    """What YaRN multiplies cos and sin by."""
    if not scaling:
        return 1.0
    f = float(scaling["factor"])
    return mscale(f, scaling.get("mscale", 1.0)) \
        / mscale(f, scaling.get("mscale_all_dim", 0.0))


def softmax_scale(qk_dim, scaling=None):
    """qk_dim^-1/2, times YaRN's `mscale(factor, mscale_all_dim)` squared
    where the scaling names a `mscale_all_dim`."""
    s = 1.0 / math.sqrt(qk_dim)
    if scaling and scaling.get("mscale_all_dim", 0):
        m = mscale(float(scaling["factor"]), scaling["mscale_all_dim"])
        s *= m * m
    return s


def rotate(x, pos, inv_freq, scale=1.0):
    """x [..., t, dim] turned to the positions `pos` (broadcast against x's
    axes before the last: [t], [B, 1], ...), float32 out. Channel i pairs with
    channel i + dim/2: out = x cos + [-x2 | x1] sin."""
    import jax.numpy as jnp

    f32 = jnp.float32
    ang = jnp.asarray(pos, f32)[..., None] * jnp.asarray(inv_freq, f32)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1) * scale
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1) * scale
    x = x.astype(f32)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin
