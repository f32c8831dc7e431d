"""Layer: the module system.

Reference parity: python/paddle/fluid/dygraph/layers.py (Layer — parameter/buffer/sublayer
registration via __setattr__, state_dict, hooks, train/eval, to_static_state) and
framework.py:5430 ParamBase.

TPU-native addition: `functional_state` / `functional_call` give a pure view
(params+buffers pytree -> outputs) so any Layer drops into jax.jit/grad/pjit unchanged —
this is the bridge between the stateful dygraph API and XLA's functional world.
"""
import collections

import jax
import numpy as np

from ...core import dtype as dtype_mod
from ...core.tensor import ParamBase, Tensor
from .. import initializer as I


class Layer:
    def __init__(self, name_scope=None, dtype="float32"):
        self._parameters = collections.OrderedDict()
        self._sub_layers = collections.OrderedDict()
        self._buffers = collections.OrderedDict()
        self._non_persistable_buffer_names = set()
        self._forward_pre_hooks = collections.OrderedDict()
        self._forward_post_hooks = collections.OrderedDict()
        self.training = True
        self._dtype = dtype_mod.convert_dtype(dtype)
        self._name = name_scope or self.__class__.__name__.lower()
        # the jax.named_scope of __call__: the name the parent registered
        # this layer under (None at the root: the class name)
        self._scope = None

    # ---- registration --------------------------------------------------------
    def __setattr__(self, name, value):
        params = self.__dict__.get("_parameters")
        layers = self.__dict__.get("_sub_layers")
        buffers = self.__dict__.get("_buffers")
        if isinstance(value, ParamBase):
            if params is None:
                raise RuntimeError("call super().__init__() before assigning parameters")
            params[name] = value
            for d in (layers, buffers):
                if d is not None and name in d:
                    del d[name]
            self.__dict__.pop(name, None)
        elif isinstance(value, Layer):
            layers[name] = value
            _registered(self, name, value)
            for d in (params, buffers):
                if d is not None and name in d:
                    del d[name]
            self.__dict__.pop(name, None)
        else:
            for d in (params, layers, buffers):
                if d is not None and name in d:
                    del d[name]
            object.__setattr__(self, name, value)

    def __getattr__(self, name):
        for store in ("_parameters", "_sub_layers", "_buffers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                return d[name]
        raise AttributeError(f"{type(self).__name__!r} has no attribute {name!r}")

    def __delattr__(self, name):
        for store in ("_parameters", "_sub_layers", "_buffers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                del d[name]
                return
        object.__delattr__(self, name)

    def add_parameter(self, name, parameter):
        self._parameters[name] = parameter
        return parameter

    def add_sublayer(self, name, sublayer):
        self._sub_layers[name] = sublayer
        _registered(self, name, sublayer)
        return sublayer

    def register_buffer(self, name, tensor, persistable=True):
        self._buffers[name] = tensor
        if not persistable:
            self._non_persistable_buffer_names.add(name)
        return tensor

    def create_parameter(
        self,
        shape,
        attr=None,
        dtype=None,
        is_bias=False,
        default_initializer=None,
    ):
        """fluid/dygraph/layers.py create_parameter parity (ParamAttr handling)."""
        from .. import ParamAttr

        dtype = dtype_mod.convert_dtype(dtype) or self._dtype
        init = default_initializer
        name = None
        trainable = True
        if isinstance(attr, ParamAttr):
            name = attr.name
            trainable = attr.trainable
            if attr.initializer is not None:
                init = attr.initializer
        elif attr is False:
            return None
        if init is None:
            init = I.Constant(0.0) if is_bias else I.XavierNormal()
        data = init(tuple(shape), dtype)
        p = ParamBase(data, dtype=dtype, name=name, trainable=trainable)
        return p

    def create_tensor(self, name=None, persistable=False, dtype=None):
        import jax.numpy as jnp

        return Tensor(jnp.zeros((), dtype=dtype_mod.convert_dtype(dtype) or self._dtype))

    # ---- traversal -----------------------------------------------------------
    def parameters(self, include_sublayers=True):
        return [p for _, p in self.named_parameters(include_sublayers=include_sublayers)]

    def named_parameters(self, prefix="", include_sublayers=True):
        seen = set()
        for name, p in self._parameters.items():
            if p is not None and id(p) not in seen:
                seen.add(id(p))
                yield (prefix + name if not prefix else prefix + "." + name), p
        if include_sublayers:
            for lname, layer in self._sub_layers.items():
                if layer is None:
                    continue
                sub_prefix = prefix + lname if not prefix else prefix + "." + lname
                for n, p in layer.named_parameters(prefix=sub_prefix):
                    if id(p) not in seen:
                        seen.add(id(p))
                        yield n, p

    def buffers(self, include_sublayers=True):
        return [b for _, b in self.named_buffers(include_sublayers=include_sublayers)]

    def named_buffers(self, prefix="", include_sublayers=True):
        for name, b in self._buffers.items():
            if b is not None:
                yield (prefix + name if not prefix else prefix + "." + name), b
        if include_sublayers:
            for lname, layer in self._sub_layers.items():
                if layer is None:
                    continue
                sub_prefix = prefix + lname if not prefix else prefix + "." + lname
                yield from layer.named_buffers(prefix=sub_prefix)

    def sublayers(self, include_self=False):
        out = [self] if include_self else []
        for _, l in self._sub_layers.items():
            if l is not None:
                out.extend(l.sublayers(include_self=True))
        return out

    def named_sublayers(self, prefix="", include_self=False, layers_set=None):
        if include_self:
            yield prefix, self
        for name, l in self._sub_layers.items():
            if l is None:
                continue
            sub_prefix = prefix + name if not prefix else prefix + "." + name
            yield from l.named_sublayers(prefix=sub_prefix, include_self=True)

    def children(self):
        return [l for _, l in self.named_children()]

    def named_children(self):
        for name, l in self._sub_layers.items():
            if l is not None:
                yield name, l

    def apply(self, fn):
        for l in self.sublayers(include_self=True):
            fn(l)
        return self

    # ---- mode ----------------------------------------------------------------
    def train(self):
        for l in self.sublayers(include_self=True):
            l.training = True
        return self

    def eval(self):
        for l in self.sublayers(include_self=True):
            l.training = False
        return self

    # ---- state dict ----------------------------------------------------------
    def state_dict(self, destination=None, include_sublayers=True, use_hook=True):
        out = destination if destination is not None else collections.OrderedDict()
        for n, p in self.named_parameters(include_sublayers=include_sublayers):
            out[n] = p
        for n, b in self.named_buffers(include_sublayers=include_sublayers):
            leaf = n.rsplit(".", 1)[-1]
            if leaf not in self._non_persistable_buffer_names:
                out[n] = b
        return out

    def set_state_dict(self, state_dict, use_structured_name=True):
        own = self.state_dict()
        missing = []
        for k, v in state_dict.items():
            if k in own:
                arr = v.numpy() if isinstance(v, Tensor) else np.asarray(v)
                own[k].set_value(arr.astype(own[k].numpy().dtype))
            else:
                missing.append(k)
        return missing

    set_dict = set_state_dict
    load_dict = set_state_dict

    # ---- hooks ---------------------------------------------------------------
    def register_forward_pre_hook(self, hook):
        handle = _HookRemoveHelper(self._forward_pre_hooks)
        self._forward_pre_hooks[handle._id] = hook
        return handle

    def register_forward_post_hook(self, hook):
        handle = _HookRemoveHelper(self._forward_post_hooks)
        self._forward_post_hooks[handle._id] = hook
        return handle

    # ---- call ----------------------------------------------------------------
    def forward(self, *inputs, **kwargs):
        raise NotImplementedError

    def __call__(self, *inputs, **kwargs):
        for hook in self._forward_pre_hooks.values():
            result = hook(self, inputs)
            if result is not None:
                inputs = result if isinstance(result, tuple) else (result,)
        # the device's time under the program's own names: metadata of
        # the lowered program, nothing else (docs/OBSERVABILITY.md
        # "Device scopes")
        with jax.named_scope(self._scope or type(self).__name__):
            out = self.forward(*inputs, **kwargs)
        for hook in self._forward_post_hooks.values():
            result = hook(self, inputs, out)
            if result is not None:
                out = result
        return out

    # ---- functional bridge (TPU-native) --------------------------------------
    def functional_state(self):
        """Return (params, buffers) as flat dicts of raw jax arrays."""
        params = {n: p._data for n, p in self.named_parameters()}
        buffers = {n: b._data for n, b in self.named_buffers()}
        return params, buffers

    def functional_call(self, params, inputs, buffers=None, training=None):
        """Run forward with `params` (+buffers) substituted — pure w.r.t. the arrays.

        Safe under jax tracing: original array refs are restored afterwards.
        """
        named_p = dict(self.named_parameters())
        named_b = dict(self.named_buffers())
        saved = {n: t._data for n, t in {**named_p, **named_b}.items()}
        saved_mode = self.training
        try:
            if training is not None:
                self.training = training
                for l in self.sublayers():
                    l.training = training
            for n, v in (params or {}).items():
                if n in named_p:
                    named_p[n]._data = v
            for n, v in (buffers or {}).items():
                if n in named_b:
                    named_b[n]._data = v
            if isinstance(inputs, (list, tuple)):
                return self.forward(*inputs)
            return self.forward(inputs)
        finally:
            for n, t in {**named_p, **named_b}.items():
                t._data = saved[n]
            self.training = saved_mode
            for l in self.sublayers():
                l.training = saved_mode

    def to(self, device=None, dtype=None, blocking=None):
        if dtype is not None:
            d = dtype_mod.convert_dtype(dtype)
            for p in self.parameters():
                p._data = p._data.astype(d)
        return self

    def full_name(self):
        return self._name

    def clear_gradients(self):
        for p in self.parameters():
            p.clear_grad()

    def extra_repr(self):
        return ""

    def __repr__(self):
        extra = self.extra_repr()
        lines = [self.__class__.__name__ + "(" + extra]
        for name, l in self._sub_layers.items():
            rep = repr(l).replace("\n", "\n  ")
            lines.append(f"  ({name}): {rep}")
        return "\n".join(lines) + ")" if len(lines) > 1 else lines[0] + ")"


def _registered(parent, name, layer):
    """Give `layer` the scope it runs under when `parent` registers it as
    `name`. A member of a list (a name of digits) runs under the list's own
    name, so that the same sublayer of every block counts as one:
    `blocks/attn`, not `blocks/7/attn`; a list that is named later hands
    its name down to the members it holds."""
    scope = parent._scope if name.isdigit() else name
    if layer is None or scope is None:
        return
    layer._scope = scope
    for sub_name, sub in layer._sub_layers.items():
        if sub_name.isdigit():
            _registered(layer, sub_name, sub)


class _HookRemoveHelper:
    _next_id = [0]

    def __init__(self, hooks):
        self._hooks = hooks
        self._id = _HookRemoveHelper._next_id[0]
        _HookRemoveHelper._next_id[0] += 1

    def remove(self):
        self._hooks.pop(self._id, None)
