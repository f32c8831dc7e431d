"""Device/Place abstraction.

Reference parity: paddle/fluid/platform/place.h:26-103 (CPUPlace/CUDAPlace/XPUPlace +
boost::variant Place) and DeviceContextPool (platform/device_context.h:695).
TPU-native design: a Place is a thin view over a jax.Device; there is no DeviceContext /
stream management — XLA owns scheduling. `set_device` picks the default device used by
tensor-creation ops (jax.default_device).

This module also owns the two start-up decisions every entry point shares:
:func:`on_tpu` (THE platform test — the Pallas kernels, the tools and the root
scripts all ask it) and :func:`enable_compile_cache` (where jax's persistent
compilation cache lives).
"""
import os

import jax


def on_tpu():
    """True when the default jax backend is a TPU. The one platform test in
    the tree: it knows ``"tpu"`` and nothing else, and it lets a backend
    start-up failure propagate — a caller that cannot find out where it runs
    must not guess."""
    return jax.devices()[0].platform == "tpu"


#: fallback cache location: <checkout>/.jax_cache, derived from this file's
#: own path so it is the same directory from any working directory (the
#: directory is part of jax's cache key — a path that moves never hits)
_REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def enable_compile_cache():
    """Turn on jax's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set jax already uses it, and no
    directory is set in code; otherwise the cache lives at one fixed path
    inside the checkout (``<repo>/.jax_cache``). chip_smoke.py, the
    benchmark and the profiling tools share this one function: the only
    place a compile cache that outlives the process is turned on."""
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = _REPO_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir


class Place:
    """Base place. Equality is by device kind + index."""

    kind = "undefined"

    def __init__(self, device_id=0):
        self._device_id = int(device_id)

    def get_device_id(self):
        return self._device_id

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self.kind == other.kind
            and self._device_id == other._device_id
        )

    def __hash__(self):
        return hash((self.kind, self._device_id))

    def __repr__(self):
        return f"Place({self.kind}:{self._device_id})"

    def jax_device(self):
        """The jax.Device this place names. Raises when there is none: a
        TPUPlace on a host without a TPU, or an index past the last device,
        is an error — never a different device than the one asked for."""
        devs = [d for d in jax.devices() if _kind_of(d) == self.kind]
        if self._device_id >= len(devs):
            raise ValueError(
                f"{self!r} names device {self._device_id} of kind "
                f"{self.kind!r}, but jax found {len(devs)} such device(s)")
        return devs[self._device_id]


class CPUPlace(Place):
    kind = "cpu"


class TPUPlace(Place):
    kind = "tpu"


class CUDAPlace(Place):  # accepted for API compat; maps to the accelerator if present
    kind = "tpu"


class CUDAPinnedPlace(CPUPlace):
    pass


class XPUPlace(TPUPlace):
    pass


def _kind_of(jdev):
    plat = jdev.platform.lower()
    if plat == "tpu":
        return "tpu"
    if plat in ("gpu", "cuda", "rocm"):
        return "gpu"
    return "cpu"


_CURRENT = [None]


def _default_place():
    for d in jax.devices():
        if _kind_of(d) == "tpu":
            return TPUPlace(0)
    return CPUPlace(0)


def set_device(device):
    """paddle.set_device('tpu'|'cpu'|'tpu:0'|'gpu') parity
    (python/paddle/fluid/framework.py _current_expected_place)."""
    if isinstance(device, Place):
        _CURRENT[0] = device
        return device
    name = str(device).lower()
    idx = 0
    if ":" in name:
        name, idx_s = name.split(":", 1)
        idx = int(idx_s)
    if name in ("tpu", "gpu", "cuda", "xpu", "npu"):
        place = TPUPlace(idx)
    elif name == "cpu":
        place = CPUPlace(idx)
    else:
        raise ValueError(f"Unknown device {device!r}")
    _CURRENT[0] = place
    return place


def get_device():
    p = current_place()
    return f"{p.kind}:{p.get_device_id()}"


def current_place():
    if _CURRENT[0] is None:
        _CURRENT[0] = _default_place()
    return _CURRENT[0]


def is_compiled_with_cuda():
    return False


def is_compiled_with_xpu():
    return False


def is_compiled_with_tpu():
    return True


def device_count():
    return len(jax.devices())
