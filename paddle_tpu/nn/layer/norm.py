"""Norm layers (python/paddle/nn/layer/norm.py parity): BatchNorm1D/2D/3D, SyncBatchNorm,
LayerNorm, GroupNorm, InstanceNorm1D/2D/3D, LocalResponseNorm, SpectralNorm."""
import jax.numpy as jnp

from ...core.tensor import Tensor
from .. import functional as F
from .. import initializer as I
from .layers import Layer


class _BatchNormBase(Layer):
    def __init__(self, num_features, momentum=0.9, epsilon=1e-5, weight_attr=None,
                 bias_attr=None, data_format="NCHW", use_global_stats=None, name=None):
        super().__init__()
        self._num_features = num_features
        self._momentum = momentum
        self._epsilon = epsilon
        self._data_format = data_format
        self._use_global_stats = use_global_stats
        if weight_attr is False:
            self.weight = None
        else:
            self.weight = self.create_parameter(shape=[num_features], attr=weight_attr, default_initializer=I.Constant(1.0))
        if bias_attr is False:
            self.bias = None
        else:
            self.bias = self.create_parameter(shape=[num_features], attr=bias_attr, is_bias=True)
        self.register_buffer("_mean", Tensor(jnp.zeros(num_features, dtype=jnp.float32)))
        self.register_buffer("_variance", Tensor(jnp.ones(num_features, dtype=jnp.float32)))

    def forward(self, x):
        return F.batch_norm(
            x, self._mean, self._variance, self.weight, self.bias,
            training=self.training, momentum=self._momentum, epsilon=self._epsilon,
            data_format=self._data_format, use_global_stats=self._use_global_stats,
        )


class BatchNorm(_BatchNormBase):
    """fluid/dygraph/nn.py BatchNorm legacy alias."""

    def __init__(self, num_channels, act=None, momentum=0.9, epsilon=1e-5, **kwargs):
        super().__init__(num_channels, momentum=momentum, epsilon=epsilon)
        self._act = act

    def forward(self, x):
        out = super().forward(x)
        if self._act == "relu":
            out = F.relu(out)
        return out


class BatchNorm1D(_BatchNormBase):
    pass


class BatchNorm2D(_BatchNormBase):
    pass


class BatchNorm3D(_BatchNormBase):
    pass


class SyncBatchNorm(_BatchNormBase):
    """operators/sync_batch_norm_op.cu parity.

    TPU-native: inside pjit/shard_map the batch axis is mesh-sharded, and the mean/var
    reductions become cross-replica automatically (XLA inserts the psum); eager
    single-process falls back to local stats. convert_sync_batchnorm mirrors
    python/paddle/nn/layer/norm.py:1059.
    """

    def forward(self, x):
        from ...distributed import collective as C

        if C.in_spmd_context():
            # functional cross-replica stats: psum over the data-parallel axis
            return C.sync_batch_norm(
                x, self._mean, self._variance, self.weight, self.bias,
                training=self.training, momentum=self._momentum,
                epsilon=self._epsilon, data_format=self._data_format,
            )
        return super().forward(x)

    @classmethod
    def convert_sync_batchnorm(cls, layer):
        out = layer
        if isinstance(layer, _BatchNormBase) and not isinstance(layer, SyncBatchNorm):
            out = SyncBatchNorm(layer._num_features, layer._momentum, layer._epsilon,
                                data_format=layer._data_format)
            if layer.weight is not None:
                out.weight.set_value(layer.weight.numpy())
                out.bias.set_value(layer.bias.numpy())
            out._mean.set_value(layer._mean.numpy())
            out._variance.set_value(layer._variance.numpy())
        for name, sub in list(layer._sub_layers.items()):
            layer.add_sublayer(name, cls.convert_sync_batchnorm(sub))
        return out


class LayerNorm(Layer):
    def __init__(self, normalized_shape, epsilon=1e-5, weight_attr=None, bias_attr=None, name=None):
        super().__init__()
        if isinstance(normalized_shape, int):
            normalized_shape = [normalized_shape]
        self._normalized_shape = list(normalized_shape)
        self._epsilon = epsilon
        if weight_attr is False:
            self.weight = None
        else:
            self.weight = self.create_parameter(shape=self._normalized_shape, attr=weight_attr, default_initializer=I.Constant(1.0))
        if bias_attr is False:
            self.bias = None
        else:
            self.bias = self.create_parameter(shape=self._normalized_shape, attr=bias_attr, is_bias=True)

    def forward(self, x):
        return F.layer_norm(x, self._normalized_shape, self.weight, self.bias, self._epsilon)


class GroupNorm(Layer):
    def __init__(self, num_groups, num_channels, epsilon=1e-5, weight_attr=None, bias_attr=None, data_format="NCHW", name=None):
        super().__init__()
        self._num_groups = num_groups
        self._epsilon = epsilon
        self._data_format = data_format
        self.weight = None if weight_attr is False else self.create_parameter(shape=[num_channels], attr=weight_attr, default_initializer=I.Constant(1.0))
        self.bias = None if bias_attr is False else self.create_parameter(shape=[num_channels], attr=bias_attr, is_bias=True)

    def forward(self, x):
        return F.group_norm(x, self._num_groups, self._epsilon, self.weight, self.bias, self._data_format)


class _InstanceNormBase(Layer):
    def __init__(self, num_features, epsilon=1e-5, momentum=0.9, weight_attr=None, bias_attr=None, data_format="NCHW", name=None):
        super().__init__()
        self._epsilon = epsilon
        self.weight = None if weight_attr is False else self.create_parameter(shape=[num_features], attr=weight_attr, default_initializer=I.Constant(1.0))
        self.bias = None if bias_attr is False else self.create_parameter(shape=[num_features], attr=bias_attr, is_bias=True)

    def forward(self, x):
        return F.instance_norm(x, weight=self.weight, bias=self.bias, eps=self._epsilon)


class InstanceNorm1D(_InstanceNormBase):
    pass


class InstanceNorm2D(_InstanceNormBase):
    pass


class InstanceNorm3D(_InstanceNormBase):
    pass


class LocalResponseNorm(Layer):
    def __init__(self, size, alpha=0.0001, beta=0.75, k=1.0, data_format="NCHW", name=None):
        super().__init__()
        self.args = (size, alpha, beta, k, data_format)

    def forward(self, x):
        return F.local_response_norm(x, *self.args)


class SpectralNorm(Layer):
    """layer/norm.py SpectralNorm (power-iteration weight normalization)."""

    def __init__(self, weight_shape, dim=0, power_iters=1, eps=1e-12, name=None):
        super().__init__()
        self._dim = dim
        self._power_iters = power_iters
        self._eps = eps
        import numpy as np

        h = weight_shape[dim]
        w = int(np.prod(weight_shape)) // h
        self.register_buffer("weight_u", Tensor(jnp.asarray(np.random.randn(h).astype(np.float32))))
        self.register_buffer("weight_v", Tensor(jnp.asarray(np.random.randn(w).astype(np.float32))))

    def forward(self, weight):
        from ...core.dispatch import apply

        dim = self._dim
        eps = self._eps
        iters = self._power_iters
        u0 = self.weight_u._data
        v0 = self.weight_v._data

        def fn(w):
            wm = jnp.moveaxis(w, dim, 0).reshape(w.shape[dim], -1)
            u, v = u0, v0
            for _ in range(iters):
                v = wm.T @ u
                v = v / (jnp.linalg.norm(v) + eps)
                u = wm @ v
                u = u / (jnp.linalg.norm(u) + eps)
            sigma = u @ wm @ v
            return w / sigma

        return apply(fn, weight)
