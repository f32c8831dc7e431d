"""Dtype registry for paddle_tpu.

Reference parity: paddle/fluid/framework/framework.proto:106 (VarType.Type) defines the
dtype enumeration (BOOL..COMPLEX128); python/paddle/fluid/data_feeder.py convert_dtype.
TPU-native design: dtypes are jnp dtypes directly; bfloat16 is first-class (MXU native),
float64 is supported but discouraged on TPU.
"""
import numpy as np
import jax.numpy as jnp

# Canonical dtype objects are numpy dtype instances (what jnp uses natively).
bool_ = jnp.bool_.dtype if hasattr(jnp.bool_, "dtype") else np.dtype("bool")
uint8 = np.dtype("uint8")
int8 = np.dtype("int8")
int16 = np.dtype("int16")
int32 = np.dtype("int32")
int64 = np.dtype("int64")
float16 = np.dtype("float16")
bfloat16 = jnp.bfloat16.dtype if hasattr(jnp.bfloat16, "dtype") else np.dtype(jnp.bfloat16)
float32 = np.dtype("float32")
float64 = np.dtype("float64")
complex64 = np.dtype("complex64")
complex128 = np.dtype("complex128")

_NAME_TO_DTYPE = {
    "bool": np.dtype("bool"),
    "uint8": uint8,
    "int8": int8,
    "int16": int16,
    "int32": int32,
    "int64": int64,
    "float16": float16,
    "bfloat16": bfloat16,
    "float32": float32,
    "float64": float64,
    "complex64": complex64,
    "complex128": complex128,
}

_FLOATING = {float16, bfloat16, float32, float64}
_INTEGER = {uint8, int8, int16, int32, int64}
_COMPLEX = {complex64, complex128}


def convert_dtype(dtype):
    """Normalize a user-provided dtype (str | np.dtype | jnp dtype | None) to np.dtype."""
    if dtype is None:
        return None
    if isinstance(dtype, str):
        if dtype not in _NAME_TO_DTYPE:
            raise TypeError(f"Unsupported dtype string: {dtype!r}")
        return _NAME_TO_DTYPE[dtype]
    try:
        return np.dtype(dtype)
    except TypeError:
        raise TypeError(f"Unsupported dtype: {dtype!r}")


def dtype_name(dtype):
    d = convert_dtype(dtype)
    if d == bfloat16:
        return "bfloat16"
    return d.name


def is_floating(dtype):
    return convert_dtype(dtype) in _FLOATING


def is_integer(dtype):
    return convert_dtype(dtype) in _INTEGER


def is_complex(dtype):
    return convert_dtype(dtype) in _COMPLEX


_DEFAULT_DTYPE = [float32]


def set_default_dtype(d):
    """paddle.set_default_dtype parity (python/paddle/framework/framework.py)."""
    d = convert_dtype(d)
    if d not in (float16, bfloat16, float32, float64):
        raise TypeError("set_default_dtype only supports floating dtypes")
    _DEFAULT_DTYPE[0] = d


def get_default_dtype():
    return _DEFAULT_DTYPE[0]
