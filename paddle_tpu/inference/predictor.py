"""Predictor: the AnalysisPredictor analog.

Reference parity: inference/api/analysis_predictor.cc (Run/ZeroCopyRun with named
input/output tensors) and the Config knobs (paddle_analysis_config.h) — device
selection, memory-optim toggles (XLA handles both).

Two load paths:
 1. pdmodel pickle (jit.save product) -> re-jit the Layer (preferred; portable across
    this framework's versions).
 2. stablehlo text + npz params (static/io.py save_inference_model product) -> compile
    via jax.export round-trip when available.
"""
import os
import pickle

import numpy as np
import jax
import jax.numpy as jnp

from ..core.tape import global_tape
from ..core.tensor import Tensor
from ..framework import aot as _aot


class Config:
    def __init__(self, model_path=None, params_path=None):
        self.model_path = model_path
        self.params_path = params_path
        self._device = "tpu"
        self._memory_optim = True

    def enable_use_gpu(self, memory_pool_init_size_mb=100, device_id=0):
        self._device = "tpu"  # accelerator == TPU in this build

    def disable_gpu(self):
        self._device = "cpu"

    def enable_memory_optim(self):
        self._memory_optim = True

    def switch_ir_optim(self, enable=True):
        pass  # XLA always optimizes

    def set_cpu_math_library_num_threads(self, n):
        pass


class _IOTensor:
    """ZeroCopyTensor parity: named handle with copy_from/to_cpu."""

    def __init__(self, store, name):
        self._store = store
        self._name = name

    def copy_from_cpu(self, arr):
        self._store[self._name] = np.ascontiguousarray(arr)

    def copy_to_cpu(self):
        return np.asarray(self._store[self._name])

    def shape(self):
        return list(np.asarray(self._store[self._name]).shape)


class Predictor:
    def __init__(self, config):
        self.config = config
        self._inputs = {}
        self._outputs = {}
        self._layer = None
        self._compiled = None  # CachedJit over _pure_fn (per-shape inside)
        self._input_names = ["input_0"]
        self._load()

    def _load(self):
        path = self.config.model_path
        self._aot = None
        # convert_to_mixed_precision hint: the re-jit path honors it by
        # tracing under amp.auto_cast with the recorded dtype/black_list
        self._precision = None
        if path and os.path.exists(path + ".precision.json"):
            import json

            try:
                with open(path + ".precision.json") as f:
                    self._precision = json.load(f)
            except Exception:
                self._precision = None
        if path and os.path.exists(path + ".pdmodel.jaxexport"):
            # AOT path (save_inference_model artifact): no python Layer, no
            # re-trace — the AnalysisPredictor-on-saved-model analog. The
            # pickled-Layer path (shape-polymorphic) stays as a fallback for
            # corrupt artifacts or off-export input shapes.
            from ..static.io import load_aot_predictor

            try:
                self._aot = load_aot_predictor(path)
            except Exception:
                self._aot = None
        if self._aot is None:
            self._load_pickled_layer(path)

    def _load_pickled_layer(self, path):
        self._compiled = None  # a (re)loaded layer invalidates compiled fns
        if path and os.path.exists(path + ".pdmodel"):
            with open(path + ".pdmodel", "rb") as f:
                self._layer = pickle.load(f)
            if self._layer is None:
                raise RuntimeError("saved model not loadable")
            if os.path.exists(path + ".pdiparams"):
                with open(path + ".pdiparams", "rb") as f:
                    self._layer.set_state_dict(pickle.load(f))
            # else: the pickled layer already carries its weights
            self._layer.eval()
        elif self._aot is None:
            raise FileNotFoundError(f"no model at {path}.pdmodel")

    def get_input_names(self):
        return list(self._input_names)

    def get_output_names(self):
        return list(self._outputs.keys()) or ["output_0"]

    def get_input_handle(self, name):
        if name not in self._input_names:
            self._input_names.append(name)
        return _IOTensor(self._inputs, name)

    def get_output_handle(self, name):
        return _IOTensor(self._outputs, name)

    def _stage_inputs(self, inputs):
        """Bind positional inputs to named slots and collect the call's
        arrays in slot order. Inputs beyond the known names ride along
        POSITIONALLY for this call only (they used to be staged under an
        unlisted name and silently dropped from the forward call) —
        nothing persists for them, so an accidental surplus input fails
        its own call without poisoning later ones."""
        extras = []
        if inputs is not None:
            for i, a in enumerate(inputs):
                if i < len(self._input_names):
                    self._inputs[self._input_names[i]] = a
                else:
                    extras.append(a)
        return [self._inputs[n] for n in self._input_names
                if n in self._inputs] + extras

    def run(self, inputs=None):
        """inputs: optional list of numpy arrays (paddle_infer.Predictor.run parity)."""
        arrs = self._stage_inputs(inputs)
        if self._aot is not None:
            try:
                return self._pack_outputs(self._aot(*arrs))
            except Exception:
                # off-export shape/dtype or corrupt artifact: fall back to the
                # shape-polymorphic pickled-Layer path when it exists
                if self._layer is None:
                    self._load_pickled_layer(self.config.model_path)
                if self._layer is None:
                    raise
                self._aot = None
        if self._compiled is None:
            # one wrapper, one per-shape executable map inside
            # (framework/aot.py)
            self._compiled = _aot.cached_jit(
                self._pure_fn(), site="predictor", label="predictor_run")
        out = self._compiled(*[jnp.asarray(a) for a in arrs])
        return self._pack_outputs(out)

    def _pure_fn(self):
        """The pure forward Run() jits — also handed (un-jitted) to
        paddle_tpu.analysis via analysis_jaxpr, so lint findings refer to
        the exact graph the predictor executes."""
        layer = self._layer
        tape = global_tape()
        hint = self._precision

        low_precision = bool(hint) and \
            hint.get("dtype") in ("bfloat16", "float16")

        def pure(*xs):
            import contextlib

            amp_ctx = contextlib.nullcontext()
            if low_precision:
                from ..amp import auto_cast

                amp_ctx = auto_cast(
                    True, dtype=hint["dtype"],
                    custom_black_list=hint.get("black_list") or None)
            with tape.pause(), amp_ctx:
                out = layer(*[Tensor(x) for x in xs])
            out = jax.tree_util.tree_map(
                lambda v: v._data if isinstance(v, Tensor) else v, out,
                is_leaf=lambda v: isinstance(v, Tensor),
            )
            if low_precision and hint.get("keep_io_types", True):
                out = jax.tree_util.tree_map(
                    lambda v: v.astype(jnp.float32)
                    if hasattr(v, "dtype")
                    and jnp.issubdtype(v.dtype, jnp.floating)
                    and v.dtype != jnp.float32 else v, out)
            return out

        return pure

    def analysis_jaxpr(self, inputs=None):
        """Trace the predictor's forward to a ClosedJaxpr for
        paddle_tpu.analysis.run_passes (tracing only — nothing runs).

        inputs: optional list of numpy arrays; defaults to whatever was
        staged via get_input_handle().copy_from_cpu(). Requires the
        re-jit (pickled-Layer) path — the AOT artifact is already
        compiled HLO with no jaxpr to inspect.
        """
        arrs = self._stage_inputs(inputs)
        if not arrs:
            raise ValueError("analysis_jaxpr: no inputs staged — pass "
                             "inputs= or copy_from_cpu first")
        if self._layer is None:
            self._load_pickled_layer(self.config.model_path)
        if self._layer is None:
            raise RuntimeError("analysis_jaxpr: AOT-only artifact (no "
                               "pickled Layer to re-trace)")
        return jax.make_jaxpr(self._pure_fn())(
            *[jnp.asarray(a) for a in arrs])

    def _pack_outputs(self, out):
        outs = out if isinstance(out, (list, tuple)) else [out]
        self._outputs.clear()
        results = []
        for i, o in enumerate(outs):
            arr = np.asarray(o._data if isinstance(o, Tensor) else o)
            self._outputs[f"output_{i}"] = arr
            results.append(arr)
        return results


def create_predictor(config):
    """paddle_infer.create_predictor / CreatePaddlePredictor (paddle_api.h:350) parity."""
    return Predictor(config)
