"""What the pure-function decoder families share (models/solar_open2.py,
models/axk1.py): a Layer whose parameters are a flat table of named leaves
handed to one pure `fwd`, the float32-accumulating products, RMS norm, and the
counts an expert layer's decode step returns."""
import numpy as np

from .. import nn
from ..core.tensor import ParamBase, Tensor

#: the counts a decode step returns beside its tokens, summed over the layers
STEP_COUNTS = ("moe_assignments", "moe_assignments_held", "moe_rows_computed",
               "moe_experts_touched")


def count_vector(c):
    """An expert layer's counts (distributed/moe.py) in STEP_COUNTS' order."""
    import jax.numpy as jnp

    return jnp.stack([c[n[len("moe_"):]] for n in STEP_COUNTS]).astype(
        jnp.int32)


class FunctionalCausalLM(nn.Layer):
    """A decoder with an untied head over a family's three module-level
    functions: `param_shapes(cfg) -> {name: (shape, kind)}`, `default_init(cfg)
    -> initializer` and `decode_fns(cfg) -> (fwd, logits_of, cache_init)`.
    `initializer(name, shape, kind, dtype) -> array` draws each parameter as
    it is created (default: the family's, from the global seed), so a caller
    that brings its own weights never holds two sets; `dtype` is the
    parameters' (default float32). `forward(input_ids [b, s]) -> logits [b, s,
    vocab]`."""

    param_shapes = default_init = decode_fns = None

    def __init__(self, cfg, initializer=None, dtype=None):
        super().__init__()
        import jax.numpy as jnp

        from ..core import dtype as dtype_mod

        self.cfg = cfg
        dt = dtype_mod.convert_dtype(dtype) or jnp.float32
        if initializer is None:
            initializer = type(self).default_init(cfg)
        for name, (shape, kind) in type(self).param_shapes(cfg).items():
            data = initializer(name, tuple(shape), kind, dt)
            if tuple(data.shape) != tuple(shape):
                raise ValueError(f"initializer gave {name} the shape "
                                 f"{tuple(data.shape)}, not {tuple(shape)}")
            *path, leaf = name.split(".")
            at = self
            for part in path:
                if part not in at._sub_layers:
                    setattr(at, part, nn.Layer())
                at = at._sub_layers[part]
            setattr(at, leaf, ParamBase(data, trainable=False))
        self._fns = None

    def forward(self, input_ids):
        """Whole sequences from an empty state: logits at every position."""
        import jax.numpy as jnp

        ids = input_ids._data if isinstance(input_ids, Tensor) \
            else jnp.asarray(np.asarray(input_ids))
        if self._fns is None:
            self._fns = type(self).decode_fns(self.cfg)
        fwd, logits_of, cache_init = self._fns
        p = {n: t._data for n, t in self.named_parameters()}
        b, s = ids.shape
        dt = p["embed.weight"].dtype
        x, _, _ = fwd(p, ids, 0, *cache_init(b, s, dt))
        return Tensor(logits_of(p, x), stop_gradient=True)


def rms(x, w, eps):
    """RMS norm in float32."""
    import jax
    import jax.numpy as jnp

    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return y * w.astype(jnp.float32)


def dot32(a, b):
    from ..distributed.moe import dot_f32

    return dot_f32(a, b)


def dot(a, b):
    return dot32(a, b).astype(a.dtype)


def einsum32(eq, a, b):
    """An einsum accumulated and returned in float32."""
    import jax.numpy as jnp

    from ..distributed.moe import f32_operands

    return jnp.einsum(eq, *f32_operands(a, b),
                      preferred_element_type=jnp.float32)
