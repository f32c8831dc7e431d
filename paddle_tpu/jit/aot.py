"""paddle_tpu.jit.aot — user-facing façade over framework/aot.py.

Ahead-of-time compilation lives in ``paddle_tpu.framework.aot`` (next to
the other process-level framework services); this module is the
jit-namespace surface users reach for::

    from paddle_tpu.jit import aot

    paddle.enable_compile_cache()               # jax's persistent cache
    step = aot.cached_jit(fn, site="user")      # jit + warm()
    step.warm(jax.ShapeDtypeStruct((8, 128), "int32"))   # data-free AOT

See docs/AOT.md for the serve-deploy recipe.
"""
from ..framework.aot import (CachedJit, args_signature,  # noqa: F401
                             cached_jit, compile_cached, mesh_fingerprint)

__all__ = ["CachedJit", "cached_jit", "compile_cached", "args_signature",
           "mesh_fingerprint"]
