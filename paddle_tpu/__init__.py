"""paddle_tpu — a TPU-native deep-learning framework with the PaddlePaddle (~v2.0)
capability surface.

Built on JAX/XLA/Pallas/pjit: eager ("dygraph") Tensors with tape autograd, a
trace-to-XLA `jit.to_static` path, the nn/tensor/optimizer/amp/io/metric API families,
a high-level Model.fit trainer, and a fleet distributed stack over jax.sharding meshes.
See SURVEY.md for the structural analysis of the reference this targets.
"""
__version__ = "0.1.0"

from . import flags as _flags_mod  # noqa: F401
from .core import dtype as _dtype

# dtypes (framework.proto:106 VarType.Type enumeration)
bool = _dtype._NAME_TO_DTYPE["bool"]  # noqa: A001
uint8 = _dtype.uint8
int8 = _dtype.int8
int16 = _dtype.int16
int32 = _dtype.int32
int64 = _dtype.int64
float16 = _dtype.float16
bfloat16 = _dtype.bfloat16
float32 = _dtype.float32
float64 = _dtype.float64
complex64 = _dtype.complex64
complex128 = _dtype.complex128
set_default_dtype = _dtype.set_default_dtype
get_default_dtype = _dtype.get_default_dtype

from .core.device import (  # noqa: E402
    CPUPlace,
    CUDAPinnedPlace,
    CUDAPlace,
    TPUPlace,
    XPUPlace,
    device_count,
    enable_compile_cache,
    get_device,
    is_compiled_with_cuda,
    is_compiled_with_tpu,
    is_compiled_with_xpu,
    set_device,
)
from .core.generator import seed  # noqa: E402
from .core.tape import is_grad_enabled, no_grad  # noqa: E402
from .core.tensor import ParamBase, Tensor, to_tensor  # noqa: E402
from .flags import get_flags, set_flags  # noqa: E402

from .tensor import *  # noqa: E402,F401,F403
from . import tensor  # noqa: E402

# subpackages land progressively; import what exists
from . import nn  # noqa: E402
from . import optimizer  # noqa: E402
from . import io  # noqa: E402
from . import metric  # noqa: E402
from . import amp  # noqa: E402
from . import autograd  # noqa: E402
from . import jit  # noqa: E402
from . import vision  # noqa: E402
from . import text  # noqa: E402
from . import static  # noqa: E402
from . import distributed  # noqa: E402
from . import linalg  # noqa: E402
from . import fft  # noqa: E402
from . import distribution  # noqa: E402
from . import onnx  # noqa: E402
from . import analysis  # noqa: E402
from . import quantization  # noqa: E402
from . import profiler as profiler  # noqa: E402
from . import monitor  # noqa: E402
# the dotted import FIRST: it forces the tracing subpackage to load and
# replaces the 'trace' attr (the tensor-star math op) with the CALLABLE
# module — paddle.trace(x) keeps the op API, paddle.trace.span() traces
from .trace import costs as _trace_costs  # noqa: E402,F401
from . import trace  # noqa: E402
from . import testing  # noqa: E402
from . import utils  # noqa: E402
from . import regularizer  # noqa: E402
from . import compat  # noqa: E402
from . import sysconfig  # noqa: E402
from . import reader  # noqa: E402
from . import dataset  # noqa: E402
from .batch import batch  # noqa: E402
from .nn import ParamAttr  # noqa: E402
from .core.generator import default_generator as _defgen  # noqa: E402


# paddle.set_printoptions parity (reference tensor/to_string.py:34):
# framework-local options consumed by Tensor.__repr__ — already re-exported
# by `from .tensor import *` above; nothing to wrap.


def create_parameter(shape, dtype="float32", name=None, attr=None,
                     is_bias=False, default_initializer=None):
    """paddle.create_parameter parity: a free-standing trainable tensor."""
    from .nn.initializer import Constant, XavierNormal
    import jax.numpy as _jnp

    init = default_initializer or (Constant(0.0) if is_bias else XavierNormal())
    t = Tensor(_jnp.asarray(init(list(shape), dtype)))
    t.stop_gradient = False
    return t


def get_cudnn_version():
    """Reference device.get_cudnn_version parity: None when no cuDNN is
    present — always the case on TPU."""
    return None


def monkey_patch_variable():
    """fluid compat no-op: Tensor operator methods are installed at import
    (tensor/math_patch.py), so the fluid-era static-Variable patching the
    reference runs at startup has nothing left to do here."""
    return None


def get_cuda_rng_state():
    """Compat: returns the framework RNG seed state (no CUDA here; the
    per-device generator is the TPU analog)."""
    return [_defgen().initial_seed()]


def set_cuda_rng_state(state):
    if state:
        seed(int(state[0]))
from .autograd import grad  # noqa: E402
from .framework import io as _fio  # noqa: E402
from .hapi import callbacks  # noqa: E402
from .hapi.model import Model  # noqa: E402
from .hapi.model_summary import summary  # noqa: E402

save = _fio.save
load = _fio.load
DataParallel = distributed.DataParallel
disable_static = static.disable_static
enable_static = static.enable_static
in_dynamic_mode = static.in_dynamic_mode
from .hapi.model import flops  # noqa: E402
