"""Jaxpr traversal helpers shared by the analysis passes.

The onnx converter (onnx/converter.py) walks eqns with per-primitive
handlers because it must LOWER each one; passes here only need to LOOK, so
the traversal is generic: `iter_eqns` yields every eqn at every nesting
depth together with a human-readable provenance path, and `sub_jaxprs`
finds the inner jaxprs of any call-like eqn (pjit/scan/while/cond/custom
vjp/remat) without a primitive table that would rot as jax evolves.
"""


def sub_jaxprs(eqn):
    """Yield (param_name, ClosedJaxpr-or-Jaxpr) for every inner jaxpr the
    eqn carries (pjit's `jaxpr`, cond's `branches` list, scan/while bodies,
    custom_*_call's `call_jaxpr`/`fun_jaxpr`...)."""
    from jax.extend import core as jex_core

    for k, v in eqn.params.items():
        vals = v if isinstance(v, (list, tuple)) else (v,)
        for item in vals:
            if isinstance(item, (jex_core.ClosedJaxpr, jex_core.Jaxpr)):
                yield k, item


def _raw(jaxpr_like):
    return jaxpr_like.jaxpr if hasattr(jaxpr_like, "jaxpr") else jaxpr_like


def iter_eqns(jaxpr, path="", max_depth=32):
    """Depth-first (eqn, provenance_path) over jaxpr and every sub-jaxpr.

    Provenance looks like ``eqns[12]/pjit:_bernoulli/eqns[4]`` — stable
    across runs of the same trace, good enough to locate the offender in a
    printed jaxpr. max_depth guards against pathological nesting.
    """
    if max_depth < 0:
        return
    for i, eqn in enumerate(jaxpr.eqns):
        here = f"{path}eqns[{i}]"
        yield eqn, here
        tag = eqn.params.get("name", "")
        label = f"{eqn.primitive.name}:{tag}" if tag else eqn.primitive.name
        for _, sub in sub_jaxprs(eqn):
            yield from iter_eqns(_raw(sub), f"{here}/{label}/",
                                 max_depth - 1)


def is_key_aval(aval):
    """True when aval is a typed PRNG key (jax.random.key) array."""
    import jax

    try:
        return jax.dtypes.issubdtype(aval.dtype, jax.dtypes.prng_key)
    except Exception:
        return False


def is_literal(atom):
    from jax._src.core import Literal

    return isinstance(atom, Literal)


def fmt_aval(aval):
    try:
        shape = "x".join(str(d) for d in aval.shape)
        return f"{aval.dtype}[{shape}]"
    except Exception:
        return str(aval)


def trace_layer(layer, *example_inputs, training=False):
    """Trace an nn.Layer's forward to a ClosedJaxpr, pure in its params.

    Uses Layer.functional_call (the same functional bridge jit/export
    use) with the autograd tape paused, so tracing never records grad
    nodes or static-Program ops. Nothing is compiled or executed on
    device beyond the trace itself.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..core.tape import global_tape
    from ..core.tensor import Tensor

    params, buffers = layer.functional_state()
    arrs = [a._data if isinstance(a, Tensor) else jnp.asarray(np.asarray(a))
            for a in example_inputs]

    def pure(p, *xs):
        with global_tape().pause():
            out = layer.functional_call(p, [Tensor(x) for x in xs],
                                        buffers=buffers, training=training)
        return jax.tree_util.tree_map(
            lambda v: v._data if isinstance(v, Tensor) else v, out,
            is_leaf=lambda v: isinstance(v, Tensor))

    return jax.make_jaxpr(pure)(params, *arrs)
