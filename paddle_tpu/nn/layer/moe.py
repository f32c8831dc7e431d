"""MoE Layer — expert-parallel mixture-of-experts FFN.

No reference equivalent (SURVEY.md §2.3: expert parallelism ABSENT in
thisjiang/Paddle); beyond-reference TPU-native capability. The math lives in
paddle_tpu/distributed/moe.py; this Layer holds the parameters (gate + stacked
expert weights, MXU-friendly [E, d, dff] layout) and exposes the single-shard
dense path by default, or the shard_map expert-parallel path when given a mesh
with an 'ep' axis.

`DroplessMoELayer` is the serving form: sigmoid scores with a selection bias,
normalised top-k, gated-MLP experts, an optional shared expert, no capacity
and no dropped token, and `held=(first, count)`: the experts this layer holds
of the `num_experts` it routes over (default all: the uncut layer).
"""
import functools

import jax

from ...core.dispatch import apply
from ...distributed import moe as moe_ops
from .. import initializer as I
from .layers import Layer


class MoELayer(Layer):
    """Top-k gated mixture of expert FFNs over the last dim.

    Input [*, d_model] is flattened to tokens, routed through `num_experts`
    FFNs (d_model -> d_ff -> d_model) with static capacity
    ceil(k*T/E*capacity_factor), and recombined. `self.aux_loss` holds the
    GShard load-balance loss of the last forward (add it to the train loss).
    """

    def __init__(self, d_model, d_ff, num_experts, k=2, capacity_factor=2.0,
                 activation="gelu", mesh=None, ep_axis="ep"):
        super().__init__()
        self.d_model = d_model
        self.d_ff = d_ff
        self.num_experts = num_experts
        self.k = k
        self.capacity_factor = capacity_factor
        self.mesh = mesh
        self.ep_axis = ep_axis
        self._act = {"gelu": jax.nn.gelu, "relu": jax.nn.relu,
                     "silu": jax.nn.silu}[activation]

        self.gate_weight = self.create_parameter(
            [d_model, num_experts], default_initializer=I.XavierNormal(
                fan_in=d_model, fan_out=num_experts))
        self.w1 = self.create_parameter(
            [num_experts, d_model, d_ff], default_initializer=I.XavierNormal(
                fan_in=d_model, fan_out=d_ff))
        self.b1 = self.create_parameter([num_experts, d_ff], is_bias=True)
        self.w2 = self.create_parameter(
            [num_experts, d_ff, d_model], default_initializer=I.XavierNormal(
                fan_in=d_ff, fan_out=d_model))
        self.b2 = self.create_parameter([num_experts, d_model], is_bias=True)
        self.aux_loss = None

    def forward(self, x):
        lead = x.shape[:-1]
        d = x.shape[-1]

        if self.mesh is not None and self.ep_axis in self.mesh.axis_names:
            fn = functools.partial(
                _moe_flat_spmd, mesh=self.mesh, k=self.k,
                capacity_factor=self.capacity_factor, activation=self._act,
                axis_name=self.ep_axis, lead=tuple(lead), d=d)
        else:
            fn = functools.partial(
                _moe_flat_dense, k=self.k, capacity_factor=self.capacity_factor,
                activation=self._act, lead=tuple(lead), d=d)
        out, aux = apply(fn, x, self.gate_weight, self.w1, self.b1, self.w2,
                         self.b2, n_outputs=2)
        self.aux_loss = aux
        return out

    def extra_repr(self):
        return (f"d_model={self.d_model}, d_ff={self.d_ff}, "
                f"num_experts={self.num_experts}, k={self.k}")


def _moe_flat_dense(x, gate_w, w1, b1, w2, b2, *, k, capacity_factor, activation,
                    lead, d):
    xt = x.reshape(-1, d)
    out, aux = moe_ops.moe_dense(xt, gate_w, w1, b1, w2, b2, k=k,
                                 capacity_factor=capacity_factor,
                                 activation=activation)
    return out.reshape(*lead, d), aux


def _moe_flat_spmd(x, gate_w, w1, b1, w2, b2, *, mesh, k, capacity_factor,
                   activation, axis_name, lead, d):
    xt = x.reshape(-1, d)
    out, aux = moe_ops.expert_parallel_moe(
        xt, gate_w, w1, b1, w2, b2, mesh, k=k, capacity_factor=capacity_factor,
        activation=activation, axis_name=axis_name)
    return out.reshape(*lead, d), aux


class DroplessMoELayer(Layer):
    """Sigmoid-routed gated-MLP experts over the last dim, no token dropped.

    Routes every token over `num_experts` (top `k` of score + selection bias,
    weights the scores normalised over the k), computes the part of the
    result that the HELD experts give (`held=(first, count)`; the other
    experts' part is left out, as on one chip of an expert-parallel
    deployment without its exchange) and adds the shared expert's. Forward
    only: the loop over the tiles that exist has a data-dependent length.
    `self.counts` holds the last forward's `assignments`,
    `assignments_held`, `rows_computed`, `experts_touched`, `rows_moved`."""

    def __init__(self, d_model, d_ff, num_experts, k=8, held=None,
                 shared_d_ff=None, normalize=True, scale=1.0):
        super().__init__()
        first, count = (0, num_experts) if held is None else map(int, held)
        if not (0 <= first and count >= 1 and first + count <= num_experts):
            raise ValueError(f"held={held} is no range of {num_experts} experts")
        self.d_model, self.d_ff, self.num_experts, self.k = \
            d_model, d_ff, num_experts, k
        self.held = (first, count)
        self.normalize, self.scale = normalize, scale
        xavier = I.XavierNormal
        self.router_weight = self.create_parameter(
            [d_model, num_experts],
            default_initializer=xavier(fan_in=d_model, fan_out=num_experts))
        self.select_bias = self.create_parameter([num_experts], is_bias=True)
        self.w_gate = self.create_parameter(
            [count, d_model, d_ff],
            default_initializer=xavier(fan_in=d_model, fan_out=d_ff))
        self.w_up = self.create_parameter(
            [count, d_model, d_ff],
            default_initializer=xavier(fan_in=d_model, fan_out=d_ff))
        self.w_down = self.create_parameter(
            [count, d_ff, d_model],
            default_initializer=xavier(fan_in=d_ff, fan_out=d_model))
        self.shared = None
        if shared_d_ff:
            self.shared_gate = self.create_parameter([d_model, shared_d_ff])
            self.shared_up = self.create_parameter([d_model, shared_d_ff])
            self.shared_down = self.create_parameter([shared_d_ff, d_model])
            self.shared = (self.shared_gate, self.shared_up, self.shared_down)
        self.counts = None

    def forward(self, x):
        from ...core.tensor import Tensor

        raw = x._data if isinstance(x, Tensor) else x
        shared = None if self.shared is None else tuple(
            p._data for p in self.shared)
        y, self.counts = moe_ops.moe_dropless_layer(
            raw.reshape(-1, raw.shape[-1]), self.router_weight._data,
            self.select_bias._data, self.w_gate._data, self.w_up._data,
            self.w_down._data, self.k, shared=shared, held=self.held,
            normalize=self.normalize, scale=self.scale)
        return Tensor(y.astype(raw.dtype).reshape(raw.shape),
                      stop_gradient=True)

    def extra_repr(self):
        return (f"d_model={self.d_model}, d_ff={self.d_ff}, "
                f"num_experts={self.num_experts}, k={self.k}, "
                f"held={self.held}")
